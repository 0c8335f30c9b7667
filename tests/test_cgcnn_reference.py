"""The published CGCNN block (``Architecture.cgcnn_conv``) against its plain
reference (``perfbench/reference/CGCNN.py``) at a tiny size, f32, on the
CPU: the program is built by the training entry's own calls
(``perfbench/build.py build_program`` -> ``_build_model_and_trainer``), given
seeded weights through the reference's ``to_program``, and its loss, every
gradient leaf and the edge BatchNorm's running statistics after a step are
set beside the reference's on the same eight periodic slabs, on both
aggregation families. Then: the same graphs at a wider padding give the same
numbers, the work count's parameters are the built model's, the rules key on
the conv width, and HydraGNN's form is kept where the key is absent.
"""

import copy
import os
import sys
from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERFBENCH = os.path.join(ROOT, "perfbench")

ARCH = {
    "model_type": "CGCNN", "cgcnn_conv": True, "radius": 4.0,
    "max_neighbours": 20, "periodic_boundary_conditions": True,
    "hidden_dim": 16, "num_gaussians": 10, "num_conv_layers": 2,
    "activation_function": "softplus",
    "output_heads": {
        "graph": {"num_sharedlayers": 1, "dim_sharedlayers": 8,
                  "num_headlayers": 1, "dim_headlayers": [8]},
        "node": {"num_headlayers": 1, "dim_headlayers": [8], "type": "mlp"},
    },
    "task_weights": [1.0, 1.0],
}
GRAPHS = 8
# f32 sums in another order (the split product, one fused scatter or a
# K-axis sum, against the reference's concatenated product over blocks of
# edge rows): a leaf's gap stays at a few 1e-6
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
    from work import CGCNN as work

    yield {"build": build, "traffic_gen": traffic_gen, "common": common,
           "ref": check.load_reference("CGCNN"), "work": work}
    for p in added:
        sys.path.remove(p)


def _build(bench, tmp_path, monkeypatch, dense=False, **arch_keys):
    """(raw graphs, cfg, loader, model, trainer, state) through the
    training entry's calls, on one batch of ``GRAPHS`` periodic slabs."""
    from hydragnn_tpu.obs import runtime as obs

    build = bench["build"]
    arch = dict(copy.deepcopy(ARCH), dense_aggregation=dense, **arch_keys)
    config = {"model_type": "CGCNN", "NeuralNetwork": {
        "Architecture": {k: v for k, v in arch.items() if v is not None},
        "Variables_of_interest": {
            "input_node_features": [0, 1, 2],
            "output_names": ["mean_coordination", "pair_force"],
            "output_index": [0, 3], "type": ["graph", "node"],
            "denormalize_output": False},
        "Training": {
            "num_epoch": 1, "perc_train": 0.7, "batch_buckets": 1,
            "contiguous_buckets": True, "steps_per_dispatch": 1,
            "device_prefetch": 0, "mixed_precision": False,
            "loss_function_type": "mse",
            "Optimizer": {"type": "AdamW", "learning_rate": 1e-3}},
    }}
    # slabs of 4 x 4 sites at least: 10 A across, over twice the cutoff
    mix = {"shape": "slab", "lattice_a": 2.5, "jitter": 0.08, "layers": 3,
           "vacuum": 15.0, "occupancy": 0.92, "radius": 4.0, "species": 3,
           "input_dim": 3, "node_target_dim": 3, "geometry_seed": 11,
           "training": {},
           "size_law": {"median": 16, "sigma": 0.4, "min": 8, "max": 30},
           "dataset_batches": 1, "eval_graphs": 2,
           "batch_size": {"1": GRAPHS}}
    graphs = bench["traffic_gen"].make_graphs(mix, GRAPHS, 3)
    monkeypatch.chdir(tmp_path)  # the program writes ./logs
    paths = build.write_dataset(str(tmp_path), graphs, graphs[:2])
    cfg = build.hydragnn_config(
        config, mix, {"name": "tiny", "chips": 1}, paths, GRAPHS)
    cfg, loader, model, trainer, state, _, _ = build.build_program(cfg)
    obs.deactivate(status="complete")
    return graphs, cfg, loader, model, trainer, state


def _seeded(bench, cfg, state):
    """(reference weights, the same as the program's tree), checked leaf
    for leaf against the program's own."""
    ref = bench["ref"]
    arch = cfg["NeuralNetwork"]["Architecture"]
    ref_params = ref.init_params(
        jax.random.PRNGKey(5), arch, 3, [int(d) for d in arch["output_dim"]])
    ours = ref.to_program(ref_params)
    shapes = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: (a.shape, str(a.dtype)), t)
    assert shapes(ours) == shapes(state.params)
    return ref_params, ours


def _ref_batch(bench, graphs, arch):
    nodes = sum(len(g["pos"]) for g in graphs)
    # the reference's wrapper adds ``offset`` for a caller whose ``ref`` is
    # the CGCNN reference, as check.follow's is here
    ref = bench["ref"]  # noqa: F841
    return bench["common"].assemble(
        graphs, arch["radius"], arch["max_neighbours"],
        (nodes, 20 * nodes, GRAPHS))


def _loss_and_grads(model, params, stats, batch):
    """Train mode, as the step runs it: the edge BatchNorm normalises with
    the batch's own statistics."""
    def loss(params):
        out, _ = model.apply({"params": params, "batch_stats": stats}, batch,
                             train=True, mutable=["batch_stats"])
        return model.loss(out, batch)[0]

    return jax.jit(jax.value_and_grad(loss))(params)


@pytest.mark.parametrize("dense", [True, False], ids=["dense", "edges"])
def pytest_loss_and_every_gradient_leaf_agree(bench, dense, tmp_path,
                                              monkeypatch):
    ref = bench["ref"]
    graphs, cfg, loader, model, trainer, state = _build(
        bench, tmp_path, monkeypatch, dense)
    arch = cfg["NeuralNetwork"]["Architecture"]
    assert arch["dense_aggregation"] is dense and len(loader) == 1
    ref_params, ours = _seeded(bench, cfg, state)
    host = next(iter(loader))
    assert ("nbr_idx" in host.extras) is dense
    offset = np.asarray(host.extras["edge_offset"])
    assert (np.any(offset != 0.0, axis=1) & np.asarray(host.edge_mask)).sum() > 20
    batch = trainer.put_batch(host)
    loss, grads = _loss_and_grads(model, ours, state.batch_stats, batch)

    ref_batch = _ref_batch(bench, graphs, arch)
    assert int(ref_batch["edge_mask"].sum()) == int(host.edge_mask.sum()) > 200
    (ref_loss, _), ref_grads = jax.jit(jax.value_and_grad(
        lambda p, b: ref.loss_fn(p, b, arch, {}), has_aux=True,
    ))(ref_params, ref_batch)
    assert abs(float(loss) - float(ref_loss)) <= 1e-5 * abs(float(ref_loss))
    ref_leaves = jax.tree_util.tree_leaves_with_path(ref.to_program(ref_grads))
    leaves = jax.tree_util.tree_leaves_with_path(grads)
    # embedding 2, 2 x (W, b, BN 2, LayerNorm 2), graph head 3 x 2, node 2 x 2
    assert len(leaves) == len(ref_leaves) == 2 + 2 * 6 + 6 + 4
    median = np.median([np.linalg.norm(l) for _, l in ref_leaves])
    for (path, got), (ref_path, want) in zip(leaves, ref_leaves):
        assert path == ref_path
        name = jax.tree_util.keystr(path)
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        if name.endswith("['bias1']"):
            # a shift ahead of a train-mode BatchNorm is taken out again
            # with the batch mean: its gradient is nought but for rounding
            assert max(np.linalg.norm(got), np.linalg.norm(want)) < 1e-5 * median
            continue
        assert np.linalg.norm(want) > 0, name
        gap = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert gap <= TOLERANCE, (name, gap)


@pytest.mark.parametrize("dense", [True, False], ids=["dense", "edges"])
def pytest_the_gaussians_read_true_periodic_distances(bench, dense, tmp_path,
                                                      monkeypatch):
    """What the convs read, as the step is given it (positions shipped, not
    a placeholder): the Gaussians of each real edge's ``|p_j + o - p_i|``,
    the reference's own distances from its own pairs and images."""
    ref = bench["ref"]
    graphs, _, loader, model, trainer, _ = _build(
        bench, tmp_path, monkeypatch, dense)
    assert model.conv_needs_pos and model.needs_edge_offsets
    host = next(iter(loader))
    rbf = np.asarray(model._prepare_batch(trainer.put_batch(host))
                     .extras["cgcnn_rbf"], np.float64)
    real = np.asarray(host.edge_mask)
    if dense:  # slot (i, k) holds edge nbr_edge[i, k]
        slots = np.asarray(host.extras["nbr_mask"])
        by_edge = np.zeros((len(real), rbf.shape[-1]))
        by_edge[np.asarray(host.extras["nbr_edge"])[slots]] = rbf[slots]
        rbf = by_edge
    mu = np.linspace(0.0, 4.0, 10)
    gamma = 0.5 / (4.0 / 9) ** 2
    # a Gaussian's centre is where it peaks: the distance, back from it
    got = np.sort((rbf[real] * mu).sum(-1) / rbf[real].sum(-1))
    want = []
    for g in graphs:
        s, r, o = ref.periodic_pairs(g["pos"], g["cell"], 4.0, 20)
        p = np.asarray(g["pos"], np.float64)
        d = np.linalg.norm(p[s] + o - p[r], axis=1)
        e = np.exp(-gamma * (d[:, None] - mu) ** 2)
        want.append((e * mu).sum(-1) / e.sum(-1))
    np.testing.assert_allclose(got, np.sort(np.concatenate(want)), atol=1e-4)


@pytest.mark.parametrize("dense", [True, False], ids=["dense", "edges"])
def pytest_edge_batchnorm_running_statistics_after_a_step(bench, dense,
                                                          tmp_path,
                                                          monkeypatch):
    """One train step of the program moves each conv's edge statistics by
    torch's rule (momentum 0.1, the unbiased variance) towards the mean and
    variance of ``W [h_i ; h_j ; g] + b`` over the REAL edges alone, which
    the reference computes from its own edge list; padded rows and slots do
    not count."""
    ref = bench["ref"]
    graphs, cfg, loader, model, trainer, state = _build(
        bench, tmp_path, monkeypatch, dense)
    arch = cfg["NeuralNetwork"]["Architecture"]
    ref_params, ours = _seeded(bench, cfg, state)
    # the step donates its state; the reference keeps its own arrays
    state = state.replace(params=jax.tree_util.tree_map(jnp.copy, ours))
    batch = trainer.put_batch(next(iter(loader)))
    after, _ = trainer._train_step(state, batch, jax.random.PRNGKey(1))

    ref_batch = _ref_batch(bench, graphs, arch)
    real = ref_batch["edge_mask"]
    s, r = ref_batch["send"][real], ref_batch["recv"][real]
    send, recv, dist, mask = ref._edges(ref_batch, None)
    k = arch["num_gaussians"]
    mu = np.linspace(0.0, arch["radius"], k)
    gamma = 0.5 / (arch["radius"] / (k - 1)) ** 2
    d = np.asarray(dist).reshape(-1)[np.asarray(mask).reshape(-1)]
    g = np.exp(-gamma * (d[:, None] - mu) ** 2)
    h = np.asarray(bench["common"].dense(
        jnp.asarray(ref_batch["x"]), ref_params["embedding"], "f32"))
    for i, layer in enumerate(ref_params["layers"]):
        w = np.asarray(layer["lin1"]["kernel"], np.float64)
        z = np.concatenate([h[r], h[s], g], -1) @ w + np.asarray(
            layer["lin1"]["bias"])
        got = after.batch_stats[f"encoder_conv_{i}"]["bn1"]
        np.testing.assert_allclose(
            np.asarray(got["mean"]), 0.1 * z.mean(0), rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(got["var"]), 0.9 + 0.1 * z.var(0, ddof=1),
            rtol=1e-4, atol=1e-6)
        h = np.asarray(ref._conv(layer, jnp.asarray(h), (send, recv, dist, mask),
                                 arch, "f32", None))


@pytest.mark.parametrize("dense", [True, False], ids=["dense", "edges"])
def pytest_wider_padding_moves_nothing(bench, dense, tmp_path, monkeypatch):
    """The same graphs collated at a larger node, edge and graph padding
    (and on dense lists a wider K) give the same loss and gradients: the
    edge BatchNorm's statistics, the gate and the sums see real edges
    alone."""
    from hydragnn_tpu.data.loaders import collate_for_layout

    graphs, cfg, loader, model, trainer, state = _build(
        bench, tmp_path, monkeypatch, dense)
    _, ours = _seeded(bench, cfg, state)
    layout = loader.layout
    layout = getattr(layout, "layouts", [layout])[0]
    samples = list(loader.dataset)
    wide = replace(layout, n_pad=layout.n_pad + 40, e_pad=layout.e_pad + 512,
                   g_pad=layout.g_pad + 8,
                   k_in=layout.k_in + 3 * dense, k_out=layout.k_out + 3 * dense)
    out = []
    for lay in (layout, wide):
        batch = trainer.put_batch(collate_for_layout(samples, lay))
        if dense:
            assert batch.extras["nbr_idx"].shape[1] == lay.k_in
        out.append(_loss_and_grads(model, ours, state.batch_stats, batch))
    (loss, grads), (wide_loss, wide_grads) = out
    assert abs(float(loss) - float(wide_loss)) <= 1e-6 * abs(float(loss))
    leaves = [np.asarray(l, np.float64) for l in jax.tree_util.tree_leaves(grads)]
    # over the median leaf at least: the shift ahead of the edge BatchNorm
    # has a gradient of rounding alone
    median = np.median([np.linalg.norm(l) for l in leaves])
    for a, b in zip(leaves, jax.tree_util.tree_leaves(wide_grads)):
        gap = np.linalg.norm(a - np.asarray(b, np.float64))
        assert gap <= 1e-5 * max(np.linalg.norm(a), median)


def pytest_work_counts_the_built_models_parameters(bench, tmp_path,
                                                   monkeypatch):
    """``work/CGCNN.py parameters`` is the built model's count, at the tiny
    widths and at the cell's (3,665,156; ocp's own model 3,611,649)."""
    import json

    from hydragnn_tpu.models.create import create_model_config

    work = bench["work"]
    _, cfg, loader, model, _, state = _build(bench, tmp_path, monkeypatch)
    arch = cfg["NeuralNetwork"]["Architecture"]
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(tree))
    assert work.parameters(arch, 3, [1, 3]) == count(state.params)
    with open(os.path.join(PERFBENCH, "configs", "cgcnn_oc20_h512x3.json")) as f:
        cell = json.load(f)["NeuralNetwork"]["Architecture"]
    big = create_model_config(dict(
        arch, **{k: cell[k] for k in ("hidden_dim", "num_gaussians",
                                      "num_conv_layers", "output_heads")}))
    shapes = jax.eval_shape(
        lambda: big.init(jax.random.PRNGKey(0), next(iter(loader))))
    assert count(shapes["params"]) == work.parameters(cell, 3, [1, 3]) == 3_665_156
    assert shapes["params"]["encoder_conv_0"]["lin1"].shape == (1124, 1024)


def pytest_the_rules_key_on_the_conv_width():
    """The family and precision rows read the width the convs run at: the
    published block's hidden_dim, HydraGNN's constant-width input_dim."""
    from hydragnn_tpu.models.create import (
        create_model_config,
        needs_edge_offsets,
        resolve_precision,
    )
    from hydragnn_tpu.ops import agg_policy as ap

    published = {"model_type": "CGCNN", "cgcnn_conv": True, "input_dim": 3}
    plain = {"model_type": "CGCNN", "input_dim": 3}
    for width in (64, 512):
        assert ap.conv_width(dict(published, hidden_dim=width)) == width
        assert ap.conv_width(dict(plain, hidden_dim=width)) == 3
    # the published block's row holds for runs that compute in bf16, as
    # the one precision rule resolves it from the conv width
    row = ap.DENSE_AUTO_MIN_HIDDEN["CGCNN"]
    for training, dense in (({"mixed_precision": "auto"}, True),
                            ({"mixed_precision": False}, False)):
        nn = {"Architecture": dict(published, hidden_dim=row),
              "Training": training}
        assert ap.needs_dense_neighbors(ap.arch_for_auto_policy(nn)) is dense
    assert not ap.needs_dense_neighbors(
        dict(published, hidden_dim=row // 2, bf16_compute=True))
    # HydraGNN's form: its input width, inversely, whatever the precision
    nn = {"Architecture": dict(plain, hidden_dim=row),
          "Training": {"mixed_precision": False}}
    assert ap.arch_for_auto_policy(nn) is nn["Architecture"]
    assert ap.needs_dense_neighbors(nn["Architecture"])
    # offsets on periodic data for the published block alone
    assert needs_edge_offsets(dict(published, periodic_boundary_conditions=True))
    assert not needs_edge_offsets(dict(plain, periodic_boundary_conditions=True))
    assert not needs_edge_offsets(dict(published))
    heads = {"graph": {"num_sharedlayers": 1, "dim_sharedlayers": 8,
                       "num_headlayers": 0, "dim_headlayers": []}}
    common = {"output_dim": [1], "output_type": ["graph"],
              "output_heads": heads, "num_conv_layers": 1,
              "num_gaussians": 10, "radius": 4.0}
    auto = {"mixed_precision": "auto"}
    for arch, width, mixed in ((published, 512, True), (published, 64, False),
                               (plain, 512, False)):
        model = create_model_config(dict(arch, hidden_dim=width, **common))
        assert resolve_precision(model, auto)["mixed"] is mixed


def pytest_without_the_key_hydragnns_form_is_kept(bench, tmp_path,
                                                  monkeypatch):
    """No embedding, convs at the input width with BatchNorm and the
    activation between them, no Gaussians and no offsets: ``CGCNNStack``
    as before."""
    from hydragnn_tpu.models.cgcnn import CGCNNStack

    _, _, loader, model, trainer, state = _build(
        bench, tmp_path, monkeypatch, cgcnn_conv=None,
        activation_function="relu", periodic_boundary_conditions=False)
    assert isinstance(model, CGCNNStack) and model.cgcnn_conv is False
    assert model.hidden_dim == 3 and model.conv_use_batchnorm
    assert model.conv_activation and not model.needs_edge_offsets
    assert "embedding" not in state.params
    assert set(state.params["encoder_conv_0"]) == {"lin_f", "lin_s"}
    assert "encoder_bn_0" in state.params
    host = next(iter(loader))
    assert "edge_offset" not in (host.extras or {})
    batch = model._prepare_batch(trainer.put_batch(host))
    assert "cgcnn_rbf" not in (batch.extras or {})
