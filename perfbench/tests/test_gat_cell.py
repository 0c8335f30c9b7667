"""Self-checks of what the GATv2 cell adds to the yardstick: the plain
reference against the program through a whole tiny run, its control and its
planted faults coming out as not correct, the work count against a hand
count and the real parameter tree, the cell's files against the papers'
widths and the PNA cell's traffic, and the attention reader."""

import json
import os
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
FIXTURE = os.path.join(HERE, "fixture_gat")
CELL = "tiny_gat_train"
FAULTS = ("fp8", "half_batch", "no_softmax", "no_self_loop")


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
    to rounding; the fp8 control, half a batch, uniform weights in place of
    the softmax and a reference without self-loops all read outside the
    limits."""
    import check

    r = _run(2**31 + 321, tmp_path, control=FAULTS)
    assert r["correct"] is True, r["compared"]
    assert r["compared"]["loss_gap"]["value"] < 1e-5
    assert r["compared"]["grad_gap"]["value"] < 5e-3
    assert r["compared"]["update_gap_median"]["value"] < 1e-4
    assert r["compared"]["multi_loss_gap"]["value"] < 1e-5
    assert r["compared"]["multi_update_gap_median"]["value"] < 1e-4
    assert r["compared"]["window_graphs_gap"]["value"] == 0.0
    limits = check.load_limits(CELL, FIXTURE)
    for name in FAULTS:
        # a control's numbers hold no window count: given one, so that the
        # verdict is decided by what the control moved
        numbers = dict(r["control"][name], window_graphs_gap=0.0)
        ok, report = check.verdict(numbers, limits)
        assert ok is False, (name, report)


def pytest_work_counts_match_a_hand_count_and_the_parameter_tree():
    import jax

    from reference import GAT as ref
    from work import GAT

    heads = {"graph": {"num_sharedlayers": 1, "dim_sharedlayers": 2,
                       "num_headlayers": 1, "dim_headlayers": [2]},
             "node": {"num_headlayers": 1, "dim_headlayers": [2], "type": "mlp"}}
    arch = {"heads": 2, "hidden_dim": 4, "num_conv_layers": 2,
            "negative_slope": 0.2, "output_heads": heads,
            "task_weights": [1.0, 1.0]}
    n, e, g = 10, 40, 2
    rows = e + n
    # layer 1: 1 -> 2 x 4 concatenated; layer 2: 8 -> 2 x 4 averaged to 4
    assert GAT.layer_widths(arch, 1) == [(1, 8, 8), (8, 8, 4)]
    projections = 2 * 2 * n * 1 * 8 + 2 * 2 * n * 8 * 8
    attention = 2 * (2 * (2 * rows * 8))
    head_level = 2 * g * 4 * 2 + (2 * g * 2 * 2 + 2 * g * 2 * 1) + (
        2 * n * 4 * 2 + 2 * n * 2 * 1)
    products = projections + attention + head_level
    params = (2 * (1 * 8 + 8) + 8 + 8 + 16) + (2 * (8 * 8 + 8) + 8 + 4 + 8) + (
        4 * 2 + 2) + (2 * 2 + 2 + 2 + 1) + (4 * 2 + 2 + 2 + 1)
    assert GAT.parameters(arch, 1, [1, 1]) == params
    got = GAT.required(arch, 1, [1, 1], n, e, g, steps=1)
    elementwise = (2 * rows * (2 * 8 + 5 * 2) + n * (0 + 10 * 8)
                   + n * (4 + 10 * 4) + n * 4 + 6.0 * params)
    assert got["flops"] == pytest.approx(3 * products + 2 * elementwise)
    node_bytes = 2 * n * (1 + 8 + 16) * 3 + 2 * n * (8 + 4 + 16) * 3
    assert got["bytes"] == pytest.approx(
        node_bytes + 2 * (2 * rows * 8 * 3 + 16 * e) + 28 * params)
    # ... and the count of parameters is the tree's, at three layers too
    deep = dict(arch, num_conv_layers=3, hidden_dim=6, heads=4)
    for input_dim in (1, 3):
        tree = jax.eval_shape(
            lambda k: ref.init_params(k, deep, input_dim, [1, 3]),
            jax.random.PRNGKey(0))
        assert GAT.parameters(deep, input_dim, [1, 3]) == sum(
            int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(tree))


def pytest_the_cell_states_the_papers_widths_on_the_pna_cells_slabs():
    """The configuration as ISSUE 32 gives it, nothing reduced; the traffic
    the PNA cell's but for its rung; at the cell's widths the bytes bound
    the required work."""
    from work import GAT

    load = lambda *parts: json.load(open(os.path.join(PERFBENCH, *parts)))  # noqa: E731
    config = load("configs", "gatv2_oc20_h4x256.json")
    arch = config["NeuralNetwork"]["Architecture"]
    assert config["reduced"] == [] and config["model_type"] == "GAT"
    stated = {"heads": 4, "hidden_dim": 256, "num_conv_layers": 3,
              "negative_slope": 0.2, "dropout": 0.0,
              "activation_function": "elu", "radius": 4.0, "max_neighbours": 12,
              "periodic_boundary_conditions": True}
    assert {k: arch[k] for k in stated} == stated
    pna = load("configs", "pna_oc20_h256.json")["NeuralNetwork"]
    assert arch["output_heads"] == pna["Architecture"]["output_heads"]
    assert config["NeuralNetwork"]["Training"] == pna["Training"]
    mix, slabs = load("traffic", "oc20_slabs_w1024.json"), load("traffic", "oc20_slabs.json")
    differ = {k for k in set(mix) | set(slabs) if mix.get(k) != slabs.get(k)}
    assert differ <= {"what", "batch_size", "epoch_cycle"}
    limits = load("limits", "gatv2_h4x256_train_oc20.json")["limits"]
    assert limits["graphs_gap"] == limits["window_graphs_gap"] == 0.0
    work = GAT.required(arch, 1, [1, 1], 90_000, 1_080_000, 1024, 4)
    peaks = load("peaks.json")["TPU v5 lite"]
    assert (work["bytes"] / peaks["hbm_bytes_per_s"]
            > work["flops"] / peaks["bf16_flops_per_s"])


def pytest_the_attention_reader_reads_the_counters_or_nothing(monkeypatch):
    import run
    import span_window

    read = run.load_reader("layer_metrics", "attention_padding_waste_pct.train")
    span = lambda name, id, parent=0, **attrs: types.SimpleNamespace(  # noqa: E731
        name=name, id=id, parent=parent, attrs=attrs or None)
    spans = [span("collate", 1, graphs=3, nodes=10, edges=20, bucket=20),
             span("neighbor_lists", 2, parent=1, k_in=4, k_out=5),
             span("collate", 3, graphs=2, nodes=6, edges=24, bucket=10),
             span("neighbor_lists", 4, parent=3, k_in=4, k_out=5),
             span("collate", 5, graphs=2, nodes=6, edges=24, bucket=10)]
    monkeypatch.setattr(span_window, "window_spans",
                        lambda run: {"threads": {"t": spans}})
    # 20 x 5 + 10 x 5 slots, 30 + 30 real; the edge-list collate counts nowhere
    assert read({}) == pytest.approx(60.0)
    spans[:] = [spans[0], spans[4]]  # no neighbour lists: nothing, no raise
    assert read({}) is None
    monkeypatch.setattr(span_window, "window_spans", lambda run: None)
    assert read({}) is None
