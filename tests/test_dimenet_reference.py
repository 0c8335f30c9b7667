"""DimeNet++ against its plain reference (``perfbench/reference/DimeNet.py``)
at a tiny size, f32, on the CPU: the program is built by the driver's own
calls (``perfbench/build.py build_program`` ->
``train/driver.py _build_model_and_trainer``), given seeded weights through
the reference's ``to_program``, and its loss and every gradient leaf are set
beside the reference's on the same eight clusters, on both aggregation
families and at both input widths (at one input feature a layer is
``hidden_dim`` wide inside, at four it is four wide: ``DIMEStack.get_conv``).
The reference enumerates triplets on its OWN radius graph, so the counts of
the two sides are compared three ways too.
"""

import copy
import os
import sys

import numpy as np
import pytest

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERFBENCH = os.path.join(ROOT, "perfbench")

ARCH = {
    "model_type": "DimeNet", "radius": 5.0, "max_neighbours": 32,
    "periodic_boundary_conditions": False, "hidden_dim": 16,
    "num_conv_layers": 2, "int_emb_size": 8, "basis_emb_size": 4,
    "out_emb_size": 24, "num_spherical": 7, "num_radial": 6,
    "envelope_exponent": 5, "num_before_skip": 1, "num_after_skip": 2,
    "output_heads": {
        "graph": {"num_sharedlayers": 2, "dim_sharedlayers": 16,
                  "num_headlayers": 2, "dim_headlayers": [32, 16]},
        "node": {"num_headlayers": 2, "dim_headlayers": [20, 20],
                 "type": "mlp"},
    },
    "task_weights": [1.0, 1.0],
}
GRAPHS = 8
# f32 sums in another order (slot grids or a triplet table against the
# reference's blocks of edges, hoisted against per-layer bases, rsqrt
# against arctan2 + cos): a leaf's gap stays at a few 1e-6 of its norm
TOLERANCE = 1e-4


@pytest.fixture(scope="module")
def bench():
    """``perfbench``'s modules, importable for this file only."""
    added = [p for p in (PERFBENCH, ROOT) if p not in sys.path]
    sys.path[:0] = added
    import build
    import check
    import traffic_gen
    from reference import common

    yield {"build": build, "traffic_gen": traffic_gen, "common": common,
           "ref": check.load_reference("DimeNet")}
    for p in added:
        sys.path.remove(p)


def _files(input_dim, dense, bf16=False):
    config = {"model_type": "DimeNet", "NeuralNetwork": {
        "Architecture": dict(copy.deepcopy(ARCH), dense_aggregation=dense),
        "Variables_of_interest": {
            "input_node_features": list(range(input_dim)),
            "output_names": ["energy", "forces"],
            "output_index": [0, input_dim], "type": ["graph", "node"],
            "denormalize_output": False},
        "Training": {
            "num_epoch": 1, "perc_train": 0.7, "batch_buckets": 1,
            "contiguous_buckets": True, "steps_per_dispatch": 1,
            "device_prefetch": 0, "mixed_precision": bf16,
            "loss_function_type": "mse",
            "Optimizer": {"type": "AdamW", "learning_rate": 1e-3}},
    }}
    mix = {"shape": "cluster", "lattice_a": 2.5, "jitter": 0.08,
           "radius": 5.0, "species": 5, "input_dim": input_dim,
           "node_target_dim": 3, "geometry_seed": 7, "training": {},
           "size_law": {"median": 9, "sigma": 0.4, "min": 5, "max": 14},
           "dataset_batches": 1, "eval_graphs": 2,
           "batch_size": {"1": GRAPHS}}
    return config, mix


def _build(bench, input_dim, dense, tmp_path, monkeypatch, bf16=False,
           end_run=True):
    """(raw graphs, cfg, loader, model, trainer, state) through the
    driver's calls, on one batch of ``GRAPHS`` clusters of 5-14 atoms;
    ``end_run=False`` leaves the telemetry run open (the caller ends it)."""
    from hydragnn_tpu.obs import runtime as obs

    build = bench["build"]
    config, mix = _files(input_dim, dense, bf16)
    graphs = bench["traffic_gen"].make_graphs(mix, GRAPHS, 3)
    monkeypatch.chdir(tmp_path)  # the program writes ./logs
    paths = build.write_dataset(str(tmp_path), graphs, graphs[:2])
    cfg = build.hydragnn_config(
        config, mix, {"name": "tiny", "chips": 1}, paths, GRAPHS)
    cfg, loader, model, trainer, state, _, _ = build.build_program(cfg)
    if end_run:
        obs.deactivate(status="complete")
    return graphs, cfg, loader, model, trainer, state


@pytest.mark.parametrize("input_dim", [1, 4])
@pytest.mark.parametrize("dense", [True, False], ids=["dense", "triplets"])
def pytest_loss_and_every_gradient_leaf_agree(bench, dense, input_dim,
                                              tmp_path, monkeypatch):
    ref, C = bench["ref"], bench["common"]
    graphs, cfg, loader, model, trainer, state = _build(
        bench, input_dim, dense, tmp_path, monkeypatch)
    arch = cfg["NeuralNetwork"]["Architecture"]
    assert arch["dense_aggregation"] is dense and len(loader) == 1
    ref_params = ref.init_params(
        jax.random.PRNGKey(5), arch, input_dim,
        [int(d) for d in arch["output_dim"]])
    ours = ref.to_program(ref_params)
    shapes = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: (a.shape, str(a.dtype)), t)
    assert shapes(ours) == shapes(state.params)

    host = next(iter(loader))
    assert ("out_edge" in host.extras) is dense
    assert ("trip_kj" in host.extras) is (not dense)
    batch = trainer.put_batch(host)

    def program_loss(params):
        outputs = model.apply({"params": params}, batch, train=True)
        return model.loss(outputs, batch)[0]

    loss, grads = jax.jit(jax.value_and_grad(program_loss))(ours)
    nodes = sum(len(g["pos"]) for g in graphs)
    ref_batch = C.assemble(graphs, arch["radius"], arch["max_neighbours"],
                           (nodes, 32 * nodes, GRAPHS))
    (ref_loss, _), ref_grads = jax.jit(jax.value_and_grad(
        lambda p, b: ref.loss_fn(p, b, arch, {}), has_aux=True,
    ))(ref_params, ref_batch)
    assert abs(float(loss) - float(ref_loss)) <= 1e-5 * abs(float(ref_loss))
    ref_leaves = jax.tree_util.tree_leaves_with_path(ref.to_program(ref_grads))
    leaves = jax.tree_util.tree_leaves_with_path(grads)
    assert len(leaves) == len(ref_leaves) > 40
    for (path, got), (ref_path, want) in zip(leaves, ref_leaves):
        assert path == ref_path
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        assert np.linalg.norm(want) > 0, jax.tree_util.keystr(path)
        gap = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert gap <= TOLERANCE, (jax.tree_util.keystr(path), gap)


def _counts(bench, dense, tmp_path, monkeypatch):
    """(the batch on the host, the counts its collate span carries)."""
    from hydragnn_tpu.utils import tracer

    _, _, loader, _, _, _ = _build(bench, 1, dense, tmp_path, monkeypatch)
    tracer.reset()
    host = next(iter(loader))
    name = "neighbor_lists" if dense else "triplets"
    span = [s for s in tracer.spans().records if s.name == name][-1]
    return host, span.attrs


def pytest_triplets_are_counted_alike_three_ways(bench, tmp_path, monkeypatch):
    """A dense batch's valid slot pairs, the triplet tables' real rows and
    the reference's own enumeration hold the same triplets; the counters
    on the collate spans read those counts."""
    ref, C = bench["ref"], bench["common"]
    _, mix = _files(1, True)
    graphs = bench["traffic_gen"].make_graphs(mix, GRAPHS, 3)
    by_reference = 0
    for g in graphs:
        send, recv = C.capped_radius_graph(g["pos"], None, 5.0, 32)
        by_reference += ref.count_triplets(send, recv, len(g["pos"]), 32)
    assert by_reference > 1000

    (tmp_path / "dense").mkdir()
    host, counted = _counts(bench, True, tmp_path / "dense", monkeypatch)
    ex = host.extras
    i_of_out = np.asarray(host.receivers)[ex["out_edge"]]  # [N, K_out]
    valid = (
        ex["rev_mask"][:, :, None] & ex["nbr_mask"][:, None, :]
        & (ex["nbr_idx"][:, None, :] != i_of_out[:, :, None])
    )
    assert int(valid.sum()) == by_reference
    n_pad, k_in = ex["nbr_idx"].shape
    assert counted["triplets"] == by_reference
    assert counted["triplet_slots"] == n_pad * ex["out_edge"].shape[1] * k_in

    (tmp_path / "table").mkdir()
    host, counted = _counts(bench, False, tmp_path / "table", monkeypatch)
    assert int(host.extras["trip_mask"].sum()) == by_reference
    assert counted["triplets"] == by_reference
    assert counted["triplet_slots"] == host.extras["trip_mask"].shape[0]


def pytest_a_bf16_step_keeps_the_bessel_frequencies_f32(bench, tmp_path,
                                                        monkeypatch):
    """``mixed_precision: true`` casts every parameter of the step to bf16
    but the Bessel layer's ``freq`` (``DIMEStack.f32_params``): n * pi at 8
    bits would move the radial basis's zeros off the cutoff. Read off the
    step program itself: no f32 -> bf16 conversion of a ``[num_radial]``
    array, many of the kernels'."""
    import re

    _, _, loader, _, trainer, state = _build(
        bench, 1, True, tmp_path, monkeypatch, bf16=True)
    dev = trainer.put_batch(next(iter(loader)))
    text = str(jax.make_jaxpr(trainer._train_step)(
        state, dev, jax.random.PRNGKey(0)))
    to_bf16 = re.findall(
        r":bf16\[([\d,]*)\] = convert_element_type\[\s*new_dtype=bfloat16", text)
    assert "16,16" in to_bf16 and "6,16" in to_bf16  # kernels, radial ones too
    assert str(ARCH["num_radial"]) not in to_bf16  # freq [6]: never


def pytest_a_dense_trace_reports_the_family_alone(bench, tmp_path, monkeypatch):
    """A dense DimeNet trace reports the layout's family and nothing
    else: its row movers (``gather_rows_to_slots``, ``slots_to_rows``,
    ``group_sum``) have one implementation, so there is no choice of
    operands to report (``kernels: 0`` on the compile events says so)."""
    import json

    from hydragnn_tpu.obs import runtime as obs

    _, _, loader, model, trainer, state = _build(
        bench, 1, True, tmp_path, monkeypatch, end_run=False)
    try:
        batch = trainer.put_batch(next(iter(loader)))
        jax.jit(lambda p: model.apply({"params": p}, batch, train=True)
                ).lower(state.params)
    finally:
        obs.deactivate(status="complete")
    events = []
    for root, _, files in os.walk(tmp_path / "logs"):
        for name in files:
            if name == "events.jsonl":
                with open(os.path.join(root, name)) as f:
                    events += [json.loads(line) for line in f]
    n_pad = batch.extras["nbr_idx"].shape[0]
    e_pad = batch.senders.shape[0]
    choices = {(e["bucket"], e["choice"], e["source"]) for e in events
               if e.get("event") == "agg_choice"}
    assert choices == {(f"DimeNet/n{n_pad}/e{e_pad}/d16", "dense", "layout")}
