"""Self-checks of what the CGCNN cell adds to the yardstick: the plain
reference against the program through a whole tiny run on periodic slabs,
its control and its planted faults coming out as not correct by the numbers
that decide, the work count against a hand count and the real parameter
tree, and the cell's files against the OC20 baseline's widths and the
SchNet cell's slabs."""

import json
import os

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
FIXTURE = os.path.join(HERE, "fixture_cgcnn")
CELL = "tiny_cgcnn_train"
FAULTS = ("fp8", "half_batch", "no_image_offset", "no_edge_bn")
# the numbers a control or fault is refused by (not the window's count,
# which a control cannot move)
DECIDING = ("graphs_gap", "loss_gap", "grad_gap", "update_gap_median",
            "multi_loss_gap", "multi_update_gap_median")


def _run(seed, tmp_path, **kw):
    import run

    here = os.getcwd()
    try:
        return run.run_cell(
            CELL, seed, 0.3, False, require_chip=False,
            benchmark_file=os.path.join(FIXTURE, "cells.json"),
            files=FIXTURE, out_dir=str(tmp_path / "out"), **kw,
        )
    finally:
        os.chdir(here)


def pytest_reference_agrees_and_the_control_and_the_faults_fail(tmp_path):
    """f32 on the CPU: the program's first steps and the reference's agree
    to rounding on slabs whose image edges need their offsets; the fp8
    control, half a batch, the in-cell distance and the edge BatchNorm left
    out each read over a limit of their own."""
    import check

    r = _run(2**31 + 91, tmp_path, control=FAULTS)
    assert r["correct"] is True, r["compared"]
    for name in ("loss_gap", "grad_gap", "update_gap", "update_gap_median",
                 "multi_loss_gap", "multi_update_gap_median"):
        assert r["compared"][name]["value"] < 1e-4, name
    assert r["compared"]["window_graphs_gap"]["value"] == 0.0
    limits = check.load_limits(CELL, FIXTURE)
    for name in FAULTS:
        over = [k for k in DECIDING
                if r["control"][name].get(k, 0.0) > limits[k]]
        assert over, (name, r["control"][name])
    assert r["control"]["half_batch"]["graphs_gap"] >= 0.5
    # each fault of this mechanism is caught by the first gradient alone
    for name in ("no_image_offset", "no_edge_bn"):
        assert r["control"][name]["grad_gap"] > 10 * limits["grad_gap"], name
        assert r["control"][name]["loss_gap"] > limits["loss_gap"], name


def pytest_work_counts_match_a_hand_count_and_the_parameter_tree():
    import jax

    from reference import CGCNN as ref
    from work import CGCNN

    heads = {"graph": {"num_sharedlayers": 1, "dim_sharedlayers": 3,
                       "num_headlayers": 0, "dim_headlayers": []},
             "node": {"num_headlayers": 1, "dim_headlayers": [2], "type": "mlp"}}
    arch = {"hidden_dim": 4, "num_gaussians": 6, "num_conv_layers": 2,
            "output_heads": heads, "radius": 3.0, "task_weights": [1.0, 1.0]}
    n, e, g = 10, 40, 2
    # W_e; per layer W_i, W_j (4 -> 8) per atom, W_g (6 -> 8) per edge
    products = 2 * n * 3 * 4 + 2 * (2 * 2 * n * 4 * 8 + 2 * e * 6 * 8)
    products += 2 * g * 4 * 3 + 2 * g * 3 * 1 + 2 * n * 4 * 2 + 2 * n * 2 * 3
    per_layer = (8 + 6) * 8 + 8 + 2 * 8 + 2 * 4
    params = 3 * 4 + 4 + 2 * per_layer + (4 * 3 + 3) + (3 + 1) + (4 * 2 + 2) + (2 * 3 + 3)
    assert CGCNN.parameters(arch, 3, [1, 3]) == params
    got = CGCNN.required(arch, 3, [1, 3], n, e, g, steps=1)
    elementwise = (e * (8 + 4 * 6) + 2 * (e * (11 * 8 + 9 * 4) + n * 12 * 4)
                   + n * 4 + 6.0 * params)
    assert got["flops"] == pytest.approx(3 * products + 2 * elementwise)
    layer_bytes = 2 * n * (4 + 16 + 8) * 3 + 2 * e * 8 * 5 + 48 * e
    assert got["bytes"] == pytest.approx(
        4 * n * (3 + 4) + 16 * e + 2 * layer_bytes + 28 * params)
    # ... and the count of parameters is the tree's, deeper and wider too
    deep = dict(arch, num_conv_layers=3, hidden_dim=7, num_gaussians=5)
    for input_dim in (1, 3):
        tree = jax.eval_shape(
            lambda k: ref.init_params(k, deep, input_dim, [1, 3]),
            jax.random.PRNGKey(0))
        assert CGCNN.parameters(deep, input_dim, [1, 3]) == sum(
            int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(tree))


def pytest_the_cell_states_the_baselines_widths_on_the_schnet_cells_slabs():
    """The configuration as ocp's S2EF baseline states it, nothing reduced, ocp's
    3,611,649 parameters from its own heads and 92-column embedding; the
    traffic the SchNet cell's slabs at another rung; at the cell's widths
    the bytes bound the required work."""
    from work import CGCNN

    load = lambda *parts: json.load(open(os.path.join(PERFBENCH, *parts)))  # noqa: E731
    config = load("configs", "cgcnn_oc20_h512x3.json")
    arch = config["NeuralNetwork"]["Architecture"]
    assert config["reduced"] == [] and config["model_type"] == "CGCNN"
    stated = {"hidden_dim": 512, "num_gaussians": 100, "num_conv_layers": 3,
              "radius": 6.0, "max_neighbours": 50,
              "periodic_boundary_conditions": True, "cgcnn_conv": True,
              "activation_function": "softplus"}
    assert {k: arch[k] for k in stated} == stated
    assert arch["output_heads"]["graph"] == {
        "num_sharedlayers": 1, "dim_sharedlayers": 128,
        "num_headlayers": 2, "dim_headlayers": [128, 128]}
    assert config["NeuralNetwork"]["Training"]["mixed_precision"] == "auto"
    ocp = dict(arch, output_heads=dict(arch["output_heads"], node={
        "num_headlayers": 0, "dim_headlayers": [], "type": "mlp"}))
    assert CGCNN.parameters(ocp, 92, [1, 0]) == 3_611_649
    mix = load("traffic", "oc20_slabs_r6_cgcnn.json")
    slabs = load("traffic", "oc20_slabs_r6.json")
    differ = {k for k in set(mix) | set(slabs) if mix.get(k) != slabs.get(k)}
    assert differ == {"what", "batch_size", "epoch_cycle"}
    # at this rung shuffle 0 opens on the train_multi group: the first
    # shuffle is one that opens on single steps (PERF.md section 4)
    assert mix["epoch_cycle"] == [4, 1]
    limits = load("limits", "cgcnn_h512x3_train_oc20.json")["limits"]
    assert limits["graphs_gap"] == limits["window_graphs_gap"] == 0.0
    work = CGCNN.required(arch, 3, [1, 3], 20_000, 740_000, 256, 4)
    peaks = load("peaks.json")["TPU v5 lite"]
    assert (work["bytes"] / peaks["hbm_bytes_per_s"]
            > work["flops"] / peaks["bf16_flops_per_s"])


def pytest_the_reference_assembles_offsets_for_itself_alone():
    """Importing ``reference/CGCNN.py`` chains a wrapper over SchNet's: each
    adds ``offset`` only where the caller follows its own reference, and
    another cell's reference assembles exactly what ``common`` does."""
    import check
    import traffic_gen
    from reference import common as C

    mix = json.load(open(os.path.join(FIXTURE, "traffic", "tiny_oc20_slabs_r.json")))
    graphs = traffic_gen.make_graphs(mix, 3, 5)
    nodes = sum(len(g["pos"]) for g in graphs)
    shape = (nodes, 20 * nodes, 3)

    def follow_step(ref):  # the frame check.follow calls it from
        return C.assemble(graphs, 4.0, 20, shape)

    cgcnn, schnet = check.load_reference("CGCNN"), check.load_reference("SchNet")
    plain = C.assemble.__wrapped__(graphs, 4.0, 20, shape)
    batch = follow_step(check.load_reference("PNA"))
    assert set(batch) == set(plain)
    for k in plain:
        np.testing.assert_array_equal(batch[k], plain[k])
    for ref in (cgcnn, schnet):
        ours = follow_step(ref)
        assert set(ours) == set(plain) | {"offset"}
        assert np.any(ours["offset"] != 0.0)


def pytest_the_slot_reader_reads_the_dense_lists_or_nothing(monkeypatch):
    """``edge_slot_waste_pct.train``: 1 - edges / (padded rows x k_in) over
    the window's dense-list collates; nothing where no collate has a
    ``neighbor_lists`` child (the edge list, a program without spans)."""
    import types

    import run
    import span_window

    read = run.load_reader("layer_metrics", "edge_slot_waste_pct.train")
    span = lambda name, id, parent=0, **attrs: types.SimpleNamespace(  # noqa: E731
        name=name, id=id, parent=parent, attrs=attrs or None)
    spans = [span("collate", 1, graphs=3, nodes=10, edges=60, bucket=20, e_pad=80),
             span("neighbor_lists", 2, parent=1, k_in=5, k_out=6),
             span("collate", 3, graphs=2, nodes=6, edges=24, bucket=10, e_pad=40),
             span("neighbor_lists", 4, parent=3, k_in=4, k_out=4)]
    monkeypatch.setattr(span_window, "window_spans",
                        lambda run: {"threads": {"t": spans}})
    assert read({}) == pytest.approx(100.0 * (1 - 84 / (20 * 5 + 10 * 4)))
    del spans[3], spans[1]
    assert read({}) is None
    monkeypatch.setattr(span_window, "window_spans", lambda run: None)
    assert read({}) is None
