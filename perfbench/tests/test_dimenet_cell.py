"""Self-checks of what the DimeNet++ cell adds to the yardstick: the plain
reference against the program through a whole tiny run, its control and its
planted faults coming out as not correct, the work count against a hand
count and the real parameter tree, the reference's Bessel roots, and the
triplet reader."""

import os
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixture_dimenet")
CELL = "tiny_dimenet_train"


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
    to rounding; the fp8 control, half a batch and a reference without its
    directional term all read outside the limits."""
    import check

    r = _run(2**31 + 301, tmp_path,
             control=("fp8", "half_batch", "no_directional"))
    assert r["correct"] is True, r["compared"]
    assert r["compared"]["loss_gap"]["value"] < 1e-5
    assert r["compared"]["grad_gap"]["value"] < 5e-3
    assert r["compared"]["update_gap"]["value"] < 5e-3
    assert r["compared"]["multi_loss_gap"]["value"] < 1e-5
    assert r["compared"]["multi_update_gap"]["value"] < 1e-3
    assert r["compared"]["window_graphs_gap"]["value"] == 0.0
    limits = check.load_limits(CELL, FIXTURE)
    for name in ("fp8", "half_batch", "no_directional"):
        ok, report = check.verdict(r["control"][name], limits)
        assert ok is False, (name, report)


def pytest_work_counts_match_a_hand_count_and_the_parameter_tree():
    import jax

    from reference import DimeNet as ref
    from work import DimeNet

    heads = {"graph": {"num_sharedlayers": 1, "dim_sharedlayers": 2,
                       "num_headlayers": 1, "dim_headlayers": [2]},
             "node": {"num_headlayers": 1, "dim_headlayers": [2], "type": "mlp"}}
    arch = {"hidden_dim": 4, "num_conv_layers": 1, "output_heads": heads,
            "num_radial": 2, "num_spherical": 3, "basis_emb_size": 2,
            "int_emb_size": 3, "out_emb_size": 5, "num_before_skip": 1,
            "num_after_skip": 1, "max_neighbours": 8, "radius": 5.0,
            "envelope_exponent": 5, "task_weights": [1.0, 1.0]}
    n, e, g = 10, 40, 2
    t = e * e / n - e  # 120
    assert DimeNet.triplets_at_least(n, e) == t
    # one layer 1 -> 4, so 4 wide inside: node level lin 2*10*1*4, the two
    # halves of emb_lin 2 * 2*10*4*4, output 2*10*(4*5 + 5*5 + 5*4)
    node_level = 80 + 640 + 1300
    # edge level: three radial products 3 * 2*40*2*4, emb_lin's r 2*40*4*4,
    # ji + kj 2 * 2*40*4*4, down and up 2 * 2*40*4*3, the radial half of
    # the directional sum 2*40*6*3, five (w, w) of the skips and int_lin
    edge_level = 1920 + 1280 + 2560 + 1920 + 1440 + 5 * 1280
    triplet_level = 2 * t * 3 * 3
    head_level = 32 + 24 + 200
    products = node_level + edge_level + triplet_level + head_level
    params = (2 + 8 + 12 + 52 + 4 + 8 + 12 + 6 + 40 + 12 + 12 + 100 + 8
              + 20 + 30 + 20) + (8 + 2) + (4 + 2 + 2 + 1) + (8 + 2 + 2 + 1)
    assert DimeNet.parameters(arch, 1, [1, 1]) == params
    got = DimeNet.required(arch, 1, [1, 1], n, e, g, steps=1)
    elementwise = (e * (12 + 8 + 48) + t * 45 + e * (56 * 4 + 15) + e * 9
                   + n * 24 + n * 4 + 6.0 * params)
    assert got["flops"] == pytest.approx(3 * products + 2 * elementwise)
    assert got["bytes"] == pytest.approx(2 * n * 5 * 3 + 16 * e + 36 * n + 28 * params)
    # ... and the count of parameters is the tree's, at both input widths
    # (at 4 inputs the first layer is 4 wide inside, at 1 it is 4 = hidden)
    deep = dict(arch, num_conv_layers=2, hidden_dim=6)
    for input_dim in (1, 4):
        tree = jax.eval_shape(
            lambda k: ref.init_params(k, deep, input_dim, [1, 3]),
            jax.random.PRNGKey(0))
        assert DimeNet.parameters(deep, input_dim, [1, 3]) == sum(
            int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(tree))


def pytest_the_reference_finds_the_bessel_roots_itself():
    from reference import DimeNet as ref

    roots = ref.bessel_roots(7, 6)
    assert roots.shape == (7, 6)
    np.testing.assert_allclose(roots[0], np.arange(1, 7) * np.pi, rtol=1e-15)
    # Abramowitz & Stegun, table 10.6: first zeros of j_1, j_2, j_6
    np.testing.assert_allclose(roots[1, :2], [4.493409458, 7.725251837], rtol=1e-9)
    np.testing.assert_allclose(roots[2, 0], 5.763459197, rtol=1e-9)
    np.testing.assert_allclose(roots[6, 0], 10.512835408, rtol=1e-9)
    for l in range(7):
        assert np.abs(ref.spherical_jl(l, roots[l], np)).max() < 1e-12
        assert np.all(np.diff(roots[l]) > 0)


def pytest_the_triplet_reader_reads_the_counters_or_nothing(monkeypatch):
    import run
    import span_window

    read = run.load_reader("layer_metrics", "triplet_padding_waste_pct.train")
    span = lambda name, **attrs: types.SimpleNamespace(  # noqa: E731
        name=name, attrs=attrs or None)
    spans = [span("collate", graphs=3), span("neighbor_lists", k_in=4, k_out=5),
             span("neighbor_lists", triplets=30, triplet_slots=100),
             span("triplets", triplets=10, triplet_slots=100)]
    monkeypatch.setattr(span_window, "window_spans",
                        lambda run: {"threads": {"t": spans}})
    assert read({}) == pytest.approx(80.0)
    spans[:] = spans[:2]  # a program without the counters: nothing, no raise
    assert read({}) is None
    monkeypatch.setattr(span_window, "window_spans", lambda run: None)
    assert read({}) is None
