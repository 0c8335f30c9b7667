"""SchNet's interaction block against its plain reference
(``perfbench/reference/SchNet.py``) at a tiny size, f32, on the CPU: the
program is built by the driver's own calls (``perfbench/build.py
build_program`` -> ``train/driver.py _build_model_and_trainer``), given
seeded weights through the reference's ``to_program``, and its loss and every
gradient leaf are set beside the reference's on the same eight graphs, on both
aggregation families, on periodic slabs (where the true distance needs each
edge's image offset) and on clusters. Then the pieces one by one: the image
offsets against the reference's own vectors, the residual and the embedding,
the widths as named, the edge_offset leaf only where a stack reads it, and
HydraGNN's form where the key is absent.
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
    "model_type": "SchNet", "radius": 4.0, "max_neighbours": 20,
    "interaction_block": True, "hidden_dim": 16, "num_filters": 12,
    "num_gaussians": 10, "num_conv_layers": 3,
    "activation_function": "ssp",
    "output_heads": {
        "graph": {"num_sharedlayers": 1, "dim_sharedlayers": 8,
                  "num_headlayers": 0, "dim_headlayers": []},
        "node": {"num_headlayers": 1, "dim_headlayers": [8], "type": "mlp"},
    },
    "task_weights": [1.0, 1.0],
}
GRAPHS = 8
# f32 sums in another order (one fused scatter or a K-axis sum against the
# reference's blocks of edge rows): a leaf's gap stays at a few 1e-6
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
           "ref": check.load_reference("SchNet")}
    for p in added:
        sys.path.remove(p)


def _files(dense, periodic, **arch_keys):
    arch = dict(copy.deepcopy(ARCH), dense_aggregation=dense,
                periodic_boundary_conditions=periodic, **arch_keys)
    config = {"model_type": "SchNet", "NeuralNetwork": {
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
    mix = {"shape": "slab" if periodic else "cluster", "lattice_a": 2.5,
           "jitter": 0.08, "layers": 3, "vacuum": 15.0, "occupancy": 0.92,
           "radius": 4.0, "species": 3, "input_dim": 3,
           "node_target_dim": 3, "geometry_seed": 11, "training": {},
           "size_law": {"median": 16, "sigma": 0.4, "min": 8, "max": 30},
           "dataset_batches": 1, "eval_graphs": 2,
           "batch_size": {"1": GRAPHS}}
    return config, mix


def _build(bench, tmp_path, monkeypatch, dense=False, periodic=True,
           **arch_keys):
    """(raw graphs, cfg, loader, model, trainer, state) through the
    driver's calls, on one batch of ``GRAPHS`` graphs."""
    from hydragnn_tpu.obs import runtime as obs

    build = bench["build"]
    config, mix = _files(dense, periodic, **arch_keys)
    graphs = bench["traffic_gen"].make_graphs(mix, GRAPHS, 3)
    monkeypatch.chdir(tmp_path)  # the program writes ./logs
    paths = build.write_dataset(str(tmp_path), graphs, graphs[:2])
    cfg = build.hydragnn_config(
        config, mix, {"name": "tiny", "chips": 1}, paths, GRAPHS)
    cfg, loader, model, trainer, state, _, _ = build.build_program(cfg)
    obs.deactivate(status="complete")
    return graphs, cfg, loader, model, trainer, state


def _image_edges(host):
    """Real edges that reach their sender through another image."""
    offset = np.asarray(host.extras["edge_offset"])
    real = np.asarray(host.edge_mask)
    return real & np.any(offset != 0.0, axis=1)


@pytest.mark.parametrize("periodic", [True, False], ids=["slabs", "clusters"])
@pytest.mark.parametrize("dense", [True, False], ids=["dense", "edges"])
def pytest_loss_and_every_gradient_leaf_agree(bench, dense, periodic, tmp_path,
                                              monkeypatch):
    ref, C = bench["ref"], bench["common"]
    graphs, cfg, loader, model, trainer, state = _build(
        bench, tmp_path, monkeypatch, dense, periodic)
    arch = cfg["NeuralNetwork"]["Architecture"]
    assert arch["dense_aggregation"] is dense and len(loader) == 1
    ref_params = ref.init_params(
        jax.random.PRNGKey(5), arch, 3, [int(d) for d in arch["output_dim"]])
    ours = ref.to_program(ref_params)
    shapes = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: (a.shape, str(a.dtype)), t)
    assert shapes(ours) == shapes(state.params)

    host = next(iter(loader))
    assert ("nbr_idx" in (host.extras or {})) is dense
    assert ("edge_offset" in (host.extras or {})) is periodic
    if periodic:
        assert _image_edges(host).sum() > 20
    batch = trainer.put_batch(host)

    def program_loss(params):
        return model.loss(model.apply({"params": params}, batch), batch)[0]

    loss, grads = jax.jit(jax.value_and_grad(program_loss))(ours)
    nodes = sum(len(g["pos"]) for g in graphs)
    # the reference's wrapper adds ``offset`` for a caller whose ``ref`` is
    # the SchNet reference, as check.follow's is here
    ref_batch = C.assemble(graphs, arch["radius"], arch["max_neighbours"],
                           (nodes, 20 * nodes, GRAPHS))
    assert int(ref_batch["edge_mask"].sum()) == int(host.edge_mask.sum()) > 200
    (ref_loss, _), ref_grads = jax.jit(jax.value_and_grad(
        lambda p, b: ref.loss_fn(p, b, arch, {}), has_aux=True,
    ))(ref_params, ref_batch)
    assert abs(float(loss) - float(ref_loss)) <= 1e-5 * abs(float(ref_loss))
    ref_leaves = jax.tree_util.tree_leaves_with_path(ref.to_program(ref_grads))
    leaves = jax.tree_util.tree_leaves_with_path(grads)
    # embedding, 3 x (two filter layers, W_1, W_2, b_2, W_3, b_3), 3 + 4 heads
    assert len(leaves) == len(ref_leaves) == 1 + 3 * 9 + 4 + 4
    for (path, got), (ref_path, want) in zip(leaves, ref_leaves):
        assert path == ref_path
        name = jax.tree_util.keystr(path)
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        assert np.linalg.norm(want) > 0, name
        gap = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert gap <= TOLERANCE, (name, gap)


@pytest.mark.parametrize("dense", [True, False], ids=["dense", "edges"])
def pytest_image_edges_read_the_true_periodic_distance(bench, dense, tmp_path,
                                                       monkeypatch):
    """The distance the program computes for every real edge or slot is the
    reference's own ``|p_j + o - p_i|`` from its own pairs and images; the
    in-cell difference, what the program read before the offsets were
    carried, misses on every image edge, by up to a cell's width."""
    from hydragnn_tpu.models.schnet import edge_vectors

    ref = bench["ref"]
    graphs, _, loader, _, trainer, _ = _build(
        bench, tmp_path, monkeypatch, dense)
    host = next(iter(loader))
    batch = trainer.put_batch(host)
    diff, slots = edge_vectors(batch.pos, batch)
    got = np.linalg.norm(np.asarray(diff, np.float64), axis=-1)
    pos = np.asarray(host.pos, np.float64)
    want = []
    for g in graphs:  # the loader's order is its shuffle's: compared sorted
        s, r, o = ref.periodic_pairs(g["pos"], g["cell"], 4.0, 20)
        p = np.asarray(g["pos"], np.float64)
        want.append(np.linalg.norm(p[s] + o - p[r], axis=1))
    want = np.sort(np.concatenate(want))
    real = np.asarray(host.edge_mask)
    if dense:
        # slot (i, k) holds edge nbr_edge[i, k]: the same distances, by edge
        mask = np.asarray(slots)
        by_edge = np.zeros(len(real))
        by_edge[np.asarray(host.extras["nbr_edge"])[mask]] = got[mask]
        got = by_edge
    np.testing.assert_allclose(np.sort(got[real]), want, rtol=0, atol=1e-5)
    assert want.max() <= 4.0
    images = _image_edges(host)
    in_cell = np.linalg.norm(
        pos[host.senders] - pos[host.receivers], axis=-1)
    assert np.all(np.abs(in_cell[images] - got[images]) > 1.0)
    assert in_cell[images].max() > 6.0


def pytest_the_residual_and_the_embedding(bench, tmp_path, monkeypatch):
    """With ``W_3`` and ``b_3`` nought an interaction hands its input on
    unchanged (the residual, no activation around it); the embedding is
    ``x @ W_e``, no bias, and is what the first interaction reads."""
    from hydragnn_tpu.models.schnet import CFConv

    _, _, loader, model, trainer, state = _build(bench, tmp_path, monkeypatch)
    assert set(state.params["embedding"]) == {"kernel"}
    assert state.params["embedding"]["kernel"].shape == (3, 16)
    batch = trainer.put_batch(next(iter(loader)))
    conv = CFConv(in_dim=16, out_dim=16, num_filters=12, num_gaussians=10,
                  cutoff=4.0, equivariant=False, use_edge_attr=False,
                  interaction=True)
    x = jax.random.normal(jax.random.PRNGKey(0), (batch.x.shape[0], 16))
    params = dict(state.params["encoder_conv_0"])
    out, _ = conv.apply({"params": params}, x, batch.pos, batch)
    assert np.abs(np.asarray(out - x)).max() > 1e-3
    params.update(lin3=jnp.zeros_like(params["lin3"]),
                  bias3=jnp.zeros_like(params["bias3"]))
    out, _ = conv.apply({"params": params}, x, batch.pos, batch)
    assert np.array_equal(np.asarray(out), np.asarray(x))

    # every interaction an identity: the heads read the embedding itself
    quiet = jax.tree_util.tree_map(lambda a: a, state.params)
    for i in range(3):
        layer = dict(quiet[f"encoder_conv_{i}"])
        layer.update(lin3=jnp.zeros_like(layer["lin3"]),
                     bias3=jnp.zeros_like(layer["bias3"]))
        quiet[f"encoder_conv_{i}"] = layer
    _, inter = model.apply({"params": quiet}, batch,
                           capture_intermediates=True, mutable=["intermediates"])
    h = inter["intermediates"]["encoder_conv_2"]["__call__"][0][0]
    want = batch.x @ quiet["embedding"]["kernel"]
    np.testing.assert_allclose(np.asarray(h), np.asarray(want), rtol=1e-6,
                               atol=1e-6)


def pytest_filters_and_gaussians_are_the_widths_they_are_named(
        bench, tmp_path, monkeypatch):
    """The factory no longer swaps them (HydraGNN passes them positionally in
    the other order): 12 filters over 10 Gaussians, in both forms."""
    for key, first_in in ((True, 16), (False, 3)):
        (tmp_path / str(key)).mkdir()
        _, _, _, model, _, state = _build(
            bench, tmp_path / str(key), monkeypatch, interaction_block=key)
        assert (model.num_filters, model.num_gaussians) == (12, 10)
        conv = state.params["encoder_conv_0"]
        assert conv["filter_0"]["kernel"].shape == (10, 12)
        assert conv["filter_1"]["kernel"].shape == (12, 12)
        assert conv["lin1"].shape == (first_in, 12)
        assert conv["lin2"].shape == (12, 16)


def pytest_without_the_key_hydragnns_form_is_kept(bench, tmp_path,
                                                  monkeypatch):
    """No embedding, no atom-wise layer, the first conv reads the inputs,
    Base's activation after every conv: ``SCFStack`` as before, and its
    forward is the hand-written form of it."""
    from hydragnn_tpu.models.common import get_activation
    from hydragnn_tpu.models.schnet import CFConv, SCFStack

    _, cfg, loader, model, trainer, state = _build(
        bench, tmp_path, monkeypatch, interaction_block=None,
        activation_function="relu")
    assert isinstance(model, SCFStack) and model.interaction_block is False
    assert model.conv_activation is True
    assert "embedding" not in state.params
    assert "lin3" not in state.params["encoder_conv_0"]
    batch = trainer.put_batch(next(iter(loader)))
    _, inter = model.apply({"params": state.params}, batch,
                           capture_intermediates=True, mutable=["intermediates"])
    stages = inter["intermediates"]
    x = batch.x
    for i in range(3):
        c = stages[f"encoder_conv_{i}"]["__call__"][0][0]
        conv = CFConv(in_dim=x.shape[1], out_dim=16, num_filters=12,
                      num_gaussians=10, cutoff=4.0, equivariant=False,
                      use_edge_attr=False)
        want, _ = conv.apply({"params": state.params[f"encoder_conv_{i}"]},
                             x, batch.pos, model._prepare_batch(batch))
        np.testing.assert_allclose(np.asarray(c), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
        x = jax.nn.relu(c)
    assert float(get_activation("ssp")(jnp.float32(0.0))) == 0.0


def pytest_the_offset_leaf_only_where_a_stack_reads_it(bench, tmp_path,
                                                       monkeypatch):
    """Periodic SchNet batches carry ``edge_offset`` (zero on padding); the
    same slabs collated for PNA carry no such leaf and the same arrays as
    before, so its collate, pool and put do not grow."""
    from hydragnn_tpu.data.loaders import collate_for_layout

    _, _, loader, _, _, _ = _build(bench, tmp_path, monkeypatch)
    host = next(iter(loader))
    offset = np.asarray(host.extras["edge_offset"])
    assert offset.dtype == np.float32 and offset.shape == (host.senders.shape[0], 3)
    assert not offset[~np.asarray(host.edge_mask)].any()
    layout = loader.layout
    layouts = getattr(layout, "layouts", [layout])
    assert all(lay.need_offsets for lay in layouts)
    samples = list(loader.dataset)[:3]
    plain = collate_for_layout(samples, replace(layouts[0], need_offsets=False))
    assert plain.extras is None
    # a sample with no image offsets (a cluster) gets zeros
    bare = samples[0].clone()
    del bare.extras["edge_offset"]
    mixed = collate_for_layout([bare] + samples[1:], layouts[0])
    e0 = bare.num_edges
    assert not np.asarray(mixed.extras["edge_offset"])[:e0].any()
    np.testing.assert_array_equal(
        np.asarray(mixed.extras["edge_offset"])[e0:],
        np.asarray(collate_for_layout(samples, layouts[0]).extras["edge_offset"])[e0:])


def pytest_needs_edge_offsets_is_the_stacks_own_answer():
    """The one question every layout builder asks: SchNet on periodic data,
    no other stack and no non-periodic data."""
    from hydragnn_tpu.models.create import STACKS, needs_edge_offsets

    for name in STACKS:
        for periodic in (True, False):
            arch = {"model_type": name,
                    "periodic_boundary_conditions": periodic}
            assert needs_edge_offsets(arch) is (name == "SchNet" and periodic)


@pytest.mark.parametrize("dense", [True, False], ids=["dense", "edges"])
def pytest_a_periodic_batch_without_offsets_is_refused(bench, dense, tmp_path,
                                                       monkeypatch):
    """A periodic SchNet handed a batch without ``edge_offset`` (a layout
    builder that did not ask ``needs_edge_offsets``) raises at trace time
    instead of reading in-cell differences."""
    _, _, loader, model, trainer, state = _build(
        bench, tmp_path, monkeypatch, dense)
    assert model.periodic and model.needs_edge_offsets
    batch = trainer.put_batch(next(iter(loader)))
    model.apply({"params": state.params}, batch)
    extras = {k: v for k, v in batch.extras.items() if k != "edge_offset"}
    with pytest.raises(ValueError, match="edge_offset"):
        model.apply({"params": state.params}, batch.replace(extras=extras))


@pytest.mark.parametrize("builder", ["serving_plan", "graph_partition"])
def pytest_other_layout_builders_carry_the_offsets(bench, builder, tmp_path,
                                                   monkeypatch):
    """A serving plan and a graph partition built with ``need_offsets``
    carry each real edge's image offset with its edge, zero on padding."""
    _, _, loader, _, _, _ = _build(bench, tmp_path, monkeypatch)
    samples = list(loader.dataset)[:3]
    if builder == "serving_plan":
        from hydragnn_tpu.serve.buckets import plan_from_samples

        plan = plan_from_samples(samples, max_batch_graphs=4, num_buckets=1,
                                 need_offsets=True)
        batch, _ = plan.pack(samples, 0)
        want = np.concatenate([s.extras["edge_offset"] for s in samples])
        real = np.asarray(batch.edge_mask)
        offset = np.asarray(batch.extras["edge_offset"])
        np.testing.assert_array_equal(offset[real], want)
        assert not offset[~real].any()
        bare = plan_from_samples(samples, max_batch_graphs=4, num_buckets=1)
        assert bare.pack(samples, 0)[0].extras is None
    else:
        from hydragnn_tpu.parallel.graph_partition import partition_graph

        g = samples[0]
        batch, _ = partition_graph(g, 2, need_offsets=True)
        real = np.asarray(batch.edge_mask)
        offset = np.asarray(batch.extras["edge_offset"])
        assert real.sum() == g.num_edges and not offset[~real].any()
        # edges are grouped by their receiver's part: compare as sets of rows
        np.testing.assert_array_equal(
            np.sort(offset[real], axis=0),
            np.sort(np.asarray(g.extras["edge_offset"]), axis=0))
        assert "edge_offset" not in partition_graph(g, 2)[0].extras


def pytest_the_reference_assembles_offsets_for_itself_alone(bench):
    """Importing ``reference/SchNet.py`` wraps ``common.assemble``; the
    wrapper adds ``offset`` only where the caller follows the SchNet
    reference, so another cell's reference in the same process assembles
    exactly what ``common`` does."""
    import check

    C, schnet = bench["common"], bench["ref"]
    config, mix = _files(False, True)
    graphs = bench["traffic_gen"].make_graphs(mix, 4, 3)
    nodes = sum(len(g["pos"]) for g in graphs)
    shape = (nodes, 20 * nodes, 4)

    def follow_step(ref):  # the frame check.follow calls it from
        return C.assemble(graphs, 4.0, 20, shape)

    plain = C.assemble.__wrapped__(graphs, 4.0, 20, shape)
    for other in (check.load_reference("PNA"), None):
        batch = follow_step(other)
        assert set(batch) == set(plain)
        for k in plain:
            np.testing.assert_array_equal(batch[k], plain[k])
    ours = follow_step(schnet)
    assert set(ours) == set(plain) | {"offset"}
    assert np.any(ours["offset"] != 0.0)
