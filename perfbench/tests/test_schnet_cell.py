"""Self-checks of what the SchNet cell adds to the yardstick: the plain
reference against the program through a whole tiny run on periodic slabs,
its control and its planted faults coming out as not correct, the work count
against a hand count and the real parameter tree, the cell's files against
the OC20 baseline's widths and the PNA cell's slabs, and the filter reader."""

import json
import os
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
FIXTURE = os.path.join(HERE, "fixture_schnet")
CELL = "tiny_schnet_train"
FAULTS = ("fp8", "half_batch", "no_image_offset", "no_cutoff")


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
    control, half a batch, the in-cell distance and the envelope left out
    all read outside the limits."""
    import check

    r = _run(2**31 + 77, tmp_path, control=FAULTS)
    assert r["correct"] is True, r["compared"]
    for name in ("loss_gap", "grad_gap", "update_gap", "update_gap_median",
                 "multi_loss_gap", "multi_update_gap_median"):
        assert r["compared"][name]["value"] < 1e-5, name
    assert r["compared"]["window_graphs_gap"]["value"] == 0.0
    limits = check.load_limits(CELL, FIXTURE)
    for name in FAULTS:
        # a control's numbers hold no window count: given one, so that the
        # verdict is decided by what the control moved
        numbers = dict(r["control"][name], window_graphs_gap=0.0)
        ok, report = check.verdict(numbers, limits)
        assert ok is False, (name, report)
    # each fault of this mechanism is caught by the first gradient alone
    for name in ("no_image_offset", "no_cutoff"):
        assert r["control"][name]["grad_gap"] > 10 * limits["grad_gap"], name


def pytest_work_counts_match_a_hand_count_and_the_parameter_tree():
    import jax

    from reference import SchNet as ref
    from work import SchNet

    heads = {"graph": {"num_sharedlayers": 1, "dim_sharedlayers": 3,
                       "num_headlayers": 0, "dim_headlayers": []},
             "node": {"num_headlayers": 1, "dim_headlayers": [2], "type": "mlp"}}
    arch = {"hidden_dim": 4, "num_filters": 5, "num_gaussians": 6,
            "num_conv_layers": 2, "output_heads": heads, "radius": 3.0,
            "task_weights": [1.0, 1.0]}
    n, e, g = 10, 40, 2
    # W_e; per layer: filter 6 x 5 + 5 x 5, the weighted sum, W_1, W_2, W_3
    products = 2 * n * 3 * 4 + 2 * (
        2 * e * 6 * 5 + 2 * e * 5 * 5 + 2 * e * 5
        + 2 * n * 4 * 5 + 2 * n * 5 * 4 + 2 * n * 4 * 4)
    products += 2 * g * 4 * 3 + 2 * g * 3 * 1 + 2 * n * 4 * 2 + 2 * n * 2 * 3
    per_layer = (6 * 5 + 5) + (5 * 5 + 5) + 4 * 5 + (5 * 4 + 4) + (4 * 4 + 4)
    params = 3 * 4 + 2 * per_layer + (4 * 3 + 3) + (3 + 1) + (4 * 2 + 2) + (2 * 3 + 3)
    assert SchNet.parameters(arch, 3, [1, 3]) == params
    got = SchNet.required(arch, 3, [1, 3], n, e, g, steps=1)
    elementwise = (e * (11 + 4 * 6) + 2 * (e * 6 * 5 + n * 6 * 4) + n * 4
                   + 6.0 * params)
    assert got["flops"] == pytest.approx(3 * products + 2 * elementwise)
    layer_bytes = 2 * n * (3 * 4 + 2 * 5) * 3 + 2 * e * 5 * 3 + 24 * e
    assert got["bytes"] == pytest.approx(
        4 * n * (3 + 4) + 16 * e + 2 * layer_bytes + 28 * params)
    # ... and the count of parameters is the tree's, deeper and wider too
    deep = dict(arch, num_conv_layers=3, hidden_dim=7, num_filters=4)
    for input_dim in (1, 3):
        tree = jax.eval_shape(
            lambda k: ref.init_params(k, deep, input_dim, [1, 3]),
            jax.random.PRNGKey(0))
        assert SchNet.parameters(deep, input_dim, [1, 3]) == sum(
            int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(tree))


def pytest_the_cell_states_the_baselines_widths_on_the_pna_cells_slabs():
    """The configuration as ISSUE 36 gives it, nothing reduced, 9.1 M
    parameters in the paper's count; the traffic the PNA cell's slabs at 6 A
    with three input columns and force-shaped targets; at the cell's widths
    the operations bound the required work."""
    from work import SchNet

    load = lambda *parts: json.load(open(os.path.join(PERFBENCH, *parts)))  # noqa: E731
    config = load("configs", "schnet_oc20_h1024x5.json")
    arch = config["NeuralNetwork"]["Architecture"]
    assert config["reduced"] == [] and config["model_type"] == "SchNet"
    stated = {"hidden_dim": 1024, "num_filters": 256, "num_gaussians": 200,
              "num_conv_layers": 5, "radius": 6.0, "max_neighbours": 50,
              "periodic_boundary_conditions": True, "interaction_block": True,
              "activation_function": "ssp"}
    assert {k: arch[k] for k in stated} == stated
    assert config["NeuralNetwork"]["Training"]["mixed_precision"] == "auto"
    # the paper's 9.1 M: five interactions, its 100-row embedding, its
    # output block (1,024 -> 512 -> 1)
    paper = SchNet.parameters(dict(arch, output_heads={
        "graph": {"num_sharedlayers": 1, "dim_sharedlayers": 512,
                  "num_headlayers": 0, "dim_headlayers": []},
        "node": {"num_headlayers": 0, "dim_headlayers": [], "type": "mlp"}}),
        100, [1, 0])
    assert 9.05e6 < paper < 9.15e6
    mix, slabs = load("traffic", "oc20_slabs_r6.json"), load("traffic", "oc20_slabs.json")
    differ = {k for k in set(mix) | set(slabs) if mix.get(k) != slabs.get(k)}
    assert differ == {"what", "radius", "input_dim", "node_target_dim",
                      "batch_size"}
    assert (mix["radius"], mix["input_dim"], mix["node_target_dim"]) == (6.0, 3, 3)
    limits = load("limits", "schnet_h1024x5_train_oc20.json")["limits"]
    assert limits["graphs_gap"] == limits["window_graphs_gap"] == 0.0
    work = SchNet.required(arch, 3, [1, 3], 20_000, 740_000, 256, 4)
    peaks = load("peaks.json")["TPU v5 lite"]
    assert (work["flops"] / peaks["bf16_flops_per_s"]
            > work["bytes"] / peaks["hbm_bytes_per_s"])


def pytest_the_edge_reader_reads_the_filter_rows_or_nothing(monkeypatch):
    """On the edge list, the cell's family, the filter network runs over
    every edge slot, so ``edge_padding_waste_pct.train`` (1 - edges /
    ``e_pad`` over the window's collates) is the share of its rows that
    hold no edge: no reader of its own (PR 36's review)."""
    import run
    import span_window

    read = run.load_reader("layer_metrics", "edge_padding_waste_pct.train")
    span = lambda name, id, **attrs: types.SimpleNamespace(  # noqa: E731
        name=name, id=id, parent=0, attrs=attrs or None)
    spans = [span("collate", 1, graphs=3, nodes=10, edges=60, bucket=20, e_pad=80),
             span("collate", 3, graphs=2, nodes=6, edges=24, bucket=10, e_pad=40)]
    monkeypatch.setattr(span_window, "window_spans",
                        lambda run: {"threads": {"t": spans}})
    assert read({}) == pytest.approx(100.0 * (1 - 84 / 120))
    spans[:] = []
    assert read({}) is None
    monkeypatch.setattr(span_window, "window_spans", lambda run: None)
    assert read({}) is None
