"""Self-checks of the yardstick: generator, work counts, trace reduction,
the plain references against the program, the control and the planted
faults coming out as not correct."""

import json
import os

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixture")
PERFBENCH = os.path.dirname(HERE)

TINY = {"PNA": "tiny_pna_train", "EGNN": "tiny_egnn_train"}


def _mix(name):
    with open(os.path.join(PERFBENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def _run(cell, seed, tmp_path, **kw):
    import run

    here = os.getcwd()
    try:
        return run.run_cell(
            cell, seed, 0.3, False, require_chip=False,
            benchmark_file=os.path.join(FIXTURE, "cells.json"),
            files=FIXTURE, out_dir=str(tmp_path / "out"), **kw,
        )
    finally:
        os.chdir(here)


# ---- generator ---------------------------------------------------------------


@pytest.mark.parametrize("name", ["oc20_slabs", "mptrj_clusters"])
def pytest_generator_is_reproducible_and_sizes_are_fixed(name):
    import traffic_gen

    mix = _mix(name)
    a = traffic_gen.make_graphs(mix, 64, 2**31 + 11)
    b = traffic_gen.make_graphs(mix, 64, 2**31 + 11)
    c = traffic_gen.make_graphs(mix, 64, 5)
    for ga, gb in zip(a, b):
        for k in ("x_in", "pos", "y_graph", "y_node"):
            np.testing.assert_array_equal(ga[k], gb[k])
    sizes = lambda gs: sorted(len(g["pos"]) for g in gs)  # noqa: E731
    assert sizes(a) == sizes(c)  # every seed: the same multiset of sizes
    assert any(len(x["pos"]) != len(y["pos"]) for x, y in zip(a, c))
    # ... and of geometries, so of edge counts: only species and order move
    from reference.common import capped_radius_graph

    edges = lambda gs: sorted(  # noqa: E731
        len(capped_radius_graph(g["pos"], g["cell"], mix["radius"], 12)[0])
        for g in gs
    )
    assert edges(a) == edges(c)
    assert not all(np.array_equal(x["x_in"], y["x_in"]) for x, y in zip(
        sorted(a, key=lambda g: g["pos"].tobytes()),
        sorted(c, key=lambda g: g["pos"].tobytes())))
    law = mix["size_law"]
    assert law["min"] <= sizes(a)[0] and sizes(a)[-1] <= law["max"]
    assert a[0]["x_in"].shape[1] == mix["input_dim"]
    assert a[0]["y_node"].shape[1] == mix["node_target_dim"]


def pytest_capped_radius_graph_rule():
    from reference.common import capped_radius_graph

    # four atoms on a line, 1 apart; cutoff 1.5 -> chain; cap 1 keeps the
    # FIRST candidate by sender index, not the nearest
    pos = np.array([[0, 0, 0], [1, 0, 0], [2, 0, 0], [3, 0, 0]], float)
    send, recv = capped_radius_graph(pos, None, 1.5, 8)
    assert sorted(zip(send, recv)) == [(0, 1), (1, 0), (1, 2), (2, 1), (2, 3), (3, 2)]
    send, recv = capped_radius_graph(pos, None, 2.5, 1)
    assert sorted(zip(send, recv)) == [(0, 1), (0, 2), (1, 0), (1, 3)]
    # periodic along x with cell 4: atom 0 and atom 3 become neighbours
    send, recv = capped_radius_graph(pos, np.array([4.0, 50.0, 50.0]), 1.5, 8)
    assert (3, 0) in set(zip(send, recv)) and (0, 3) in set(zip(send, recv))


# ---- required work against a hand count ---------------------------------------


def pytest_work_counts_match_a_hand_count():
    from work import EGNN, PNA

    heads = {"graph": {"num_sharedlayers": 1, "dim_sharedlayers": 2,
                       "num_headlayers": 1, "dim_headlayers": [2]},
             "node": {"num_headlayers": 1, "dim_headlayers": [2], "type": "mlp"}}
    arch = {"hidden_dim": 4, "num_conv_layers": 1, "output_heads": heads,
            "equivariance": True}
    n, e, g = 10, 30, 2
    # PNA, one layer 1 -> 4: products pre 2*(2*10*1*1), post 2*10*17*4,
    # lin 2*10*4*4; heads: shared 2*2*4*2, own 2*2*(2*2+2*1), node
    # 2*10*(4*2+2*1)
    products = 40 + 1360 + 320 + 32 + 24 + 200
    params = (2 + 1) + (17 * 4 + 4) + (16 + 4) + 8 + (8 + 2) + (4 + 2 + 2 + 1) + (8 + 2 + 2 + 1)
    assert PNA.parameters(arch, 1, [1, 1]) == params
    got = PNA.required(arch, 1, [1, 1], n, e, g, steps=1)
    elementwise = 6 * e * 1 + n * (48 + 6 + 32) + n * 4 + 6.0 * params
    assert got["flops"] == pytest.approx(3 * products + 2 * elementwise)
    assert got["bytes"] == pytest.approx(2 * n * 5 * 3 + 16 * e + 28 * params)
    # EGNN, one (last, so no coordinate MLP) layer 1 -> 4
    products = 2 * (2 * n * 1 * 4) + 2 * e * 4 * 4 + 2 * n * 5 * 4 + 2 * n * 4 * 4 + 32 + 24 + 200
    got = EGNN.required(arch, 1, [1, 1], n, e, g, steps=1)
    params = (3 * 4 + 4) + (16 + 4) + (5 * 4 + 4) + (16 + 4) + (8 + 2) + (4 + 2 + 2 + 1) + (8 + 2 + 2 + 1)
    assert EGNN.parameters(arch, 1, [1, 1]) == params
    elementwise = e * (12 + 24) + n * 12 + n * 4 + 6.0 * params
    assert got["flops"] == pytest.approx(3 * products + 2 * elementwise)


# ---- trace reduction -----------------------------------------------------------


def _synthetic_trace():
    ops = [["fusion.1", 100, 50], ["all-gather.2", 150, 30], ["fusion.3", 160, 40],
           ["fusion.1", 400, 50], ["fusion.3", 460, 40]]
    mods = [["jit_train_step(7)", 100, 100], ["jit_train_step(7)", 400, 100],
            ["jit__copy(3)", 700, 10]]
    host = {"main": [["perfbench.train_epoch", 0, 1000], ["dataload", 210, 150]]}
    return {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": mods},
            "/host:CPU": host}


def pytest_trace_reduce_on_a_synthetic_trace():
    import trace_reduce as tr

    s = tr.reduce(_synthetic_trace(), {"train_step": 1}, ["all-gather"],
                  window=(0, 1000))
    assert s["steps"] == 2
    assert s["window_s"] == pytest.approx(1000e-9)
    # busy: [100,200] and [400,450] + [460,500]
    assert s["busiest_busy_s"] == pytest.approx(190e-9)
    assert s["step_busy_s"] == pytest.approx(190e-9)
    assert s["intervals_s"] == pytest.approx([300e-9])
    assert s["collective_s"] == pytest.approx(30e-9)
    # the all-gather [150,180] overlaps fusion.3 from 160: exposed 10
    assert s["collective_exposed_s"] == pytest.approx(10e-9)
    assert s["device_ops"][0][0] == "fusion.1"
    assert tr.module_base("jit_multi_train_step(123)") == "multi_train_step"
    assert tr.span_window(_synthetic_trace(), "perfbench.") == (0, 1000)


def pytest_trace_reduce_on_the_recorded_trace():
    import trace_reduce as tr

    path = os.path.join(FIXTURE, "recorded_trace.json")
    with open(path) as f:
        recorded = json.load(f)
    s = tr.reduce(recorded["trace"], recorded["step_modules"], [],
                  window=tr.span_window(recorded["trace"], "perfbench."))
    for key, value in recorded["expect"].items():
        assert s[key] == pytest.approx(value, rel=1e-9), key
    assert 0 < s["busiest_busy_s"] <= s["window_s"]
    assert s["step_busy_s"] <= s["busiest_busy_s"]


# ---- the references against the program, the control, the faults -----------------


@pytest.mark.parametrize("model", sorted(TINY))
def pytest_reference_agrees_with_the_program_and_the_control_fails(model, tmp_path):
    """f32 on the CPU: the program's first three steps and the plain
    reference's agree to rounding; the fp8 control reads far above it."""
    r = _run(TINY[model], 2**31 + 101, tmp_path, control=("fp8",))
    assert r["correct"] is True, r["compared"]
    assert r["compared"]["loss_gap"]["value"] < 1e-5
    assert r["compared"]["grad_gap"]["value"] < 5e-3
    assert r["compared"]["update_gap"]["value"] < 5e-3
    # the first epoch of a tiny cell holds a ``train_multi`` group too
    assert r["compared"]["multi_loss_gap"]["value"] < 1e-5
    assert r["compared"]["multi_update_gap"]["value"] < 1e-3
    assert r["compared"]["window_graphs_gap"]["value"] == 0.0
    import check

    limits = check.load_limits(TINY[model], FIXTURE)
    ok, _ = check.verdict(r["control"]["fp8"], limits)
    assert ok is False, r["control"]
    assert list(r)[-1] == "compared"  # the numbers compared come last


def _break_steps(monkeypatch, fault):
    """Plant a fault under the timed path: every step program the trainer
    builds is replaced by a broken one (``multi_``: only ``train_multi``)."""
    import jax
    import jax.numpy as jnp

    from hydragnn_tpu.train import trainer as trainer_mod

    real_build = trainer_mod.build_steps

    def halve(batch):
        half = batch.n_node.shape[-1] // 2
        gmask = batch.graph_mask & (jnp.arange(batch.n_node.shape[-1]) < half)
        nmask = batch.node_mask & (batch.node_graph < half)
        return batch.replace(graph_mask=gmask, node_mask=nmask)

    def broken(step):
        if fault.endswith("state_unchanged"):
            return jax.jit(lambda s, b, r: (s, step(s, b, r)[1]))
        return jax.jit(lambda s, b, r: step(s, halve(b), r))

    def build(*args, **kwargs):
        steps = real_build(*args, **kwargs)
        if not fault.startswith("multi_"):
            steps.train_step = broken(steps.train_step)
        steps.train_multi = broken(steps.train_multi)
        return steps

    monkeypatch.setattr(trainer_mod, "build_steps", build)


@pytest.mark.parametrize(
    "fault", ["state_unchanged", "half_batch", "multi_state_unchanged"])
@pytest.mark.parametrize("model", sorted(TINY))
def pytest_a_broken_timed_path_is_not_correct(model, fault, tmp_path, monkeypatch):
    _break_steps(monkeypatch, fault)
    r = _run(TINY[model], 77, tmp_path)
    assert r["correct"] is False, r["compared"]
    value = lambda name: r["compared"][name]["value"]  # noqa: E731
    if fault == "half_batch":
        # half of the padded slots: partial batches lose fewer of their graphs
        assert 0.1 <= value("graphs_gap") <= 0.5
        assert 0.1 <= value("window_graphs_gap") <= 0.5
    if fault == "state_unchanged":
        assert value("grad_gap") == pytest.approx(1.0)
        assert value("update_gap") == pytest.approx(1.0)
    if fault.endswith("state_unchanged"):
        assert value("multi_update_gap") == pytest.approx(1.0)


@pytest.mark.parametrize("model", sorted(TINY))
def pytest_a_loader_that_drops_a_batch_is_not_correct(model, tmp_path, monkeypatch):
    """The rate counts every graph of an epoch; the steps of the window
    must have trained on as many (``window_graphs_gap``). The first epoch,
    which the reference follows, is left whole: only the count sees it."""
    from hydragnn_tpu.data.loaders import GraphLoader

    real_plan = GraphLoader._batch_plan
    epochs_seen = set()

    def plan(self):
        whole = real_plan(self)
        epochs_seen.add(self.epoch)
        return whole if len(epochs_seen) == 1 else whole[:-1]

    monkeypatch.setattr(GraphLoader, "_batch_plan", plan)
    r = _run(TINY[model], 78, tmp_path)
    assert r["correct"] is False, r["compared"]
    assert r["compared"]["window_graphs_gap"]["value"] > 0.05
    others = {k: v for k, v in r["compared"].items() if k != "window_graphs_gap"}
    assert all(v["value"] <= v["limit"] for v in others.values()), others


def pytest_a_compilation_inside_the_window_fails_the_run(tmp_path):
    """A mix without ``epoch_cycle`` shuffles afresh every epoch; at tiny
    size a new shuffle changes the dispatch count and the program compiles
    (``Trainer._acc_read``): no result line, a non-zero exit."""
    import run

    here = os.getcwd()
    try:
        with pytest.raises(SystemExit, match="inside the measured window"):
            run.run_cell(
                "tiny_egnn_train_fresh", 5, 1.0, False, require_chip=False,
                benchmark_file=os.path.join(FIXTURE, "cells.json"),
                files=FIXTURE, out_dir=str(tmp_path / "out"),
            )
    finally:
        os.chdir(here)


def pytest_readers_take_what_their_source_says():
    """``step_mfu_pct.train`` is the trace's: the host's window does not
    move it. The end-to-end readers are the window's and the clock's."""
    import run

    r = {"cell": {"chips": 1}, "peaks": {"bf16_flops_per_s": 100.0,
                                          "hbm_bytes_per_s": 10.0},
         "work": {"flops": 50.0, "bytes": 1.0},
         "trace": {"step_busy_s": 2.0, "steps": 4, "window_s": 8.0},
         "window": {"window_s": 10.0, "graphs": 40}, "setup_s": 3.0}
    mfu = run.load_reader("layer_metrics", "step_mfu_pct.train")
    assert mfu(r) == pytest.approx(25.0)
    assert mfu(dict(r, window={"window_s": 99.0, "graphs": 40})) == pytest.approx(25.0)
    assert mfu(dict(r, trace=None)) is None
    roofline = run.load_reader("layer_metrics", "step_roofline_pct.train")
    assert roofline(r) == pytest.approx(25.0)  # operations bound: the same
    assert run.load_reader("end_to_end", "train_graphs_per_s")(r) == pytest.approx(4.0)
    assert run.load_reader("end_to_end", "setup_s")(r) == 3.0
