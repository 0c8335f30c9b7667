"""GATv2 against its plain reference (``perfbench/reference/GAT.py``) at a
tiny size, f32, on the CPU: the program is built by the driver's own calls
(``perfbench/build.py build_program`` -> ``train/driver.py
_build_model_and_trainer``), given seeded weights through the reference's
``to_program``, and its loss and every gradient leaf are set beside the
reference's on the same eight graphs of 5-14 atoms, on both aggregation
families and at two head counts, over three layers (so a concatenating
middle layer is there), on periodic slabs and on clusters. The reference
attends over an EDGE list with explicit self-loops; the program over slot
lists with the self-loop held apart, or over its own edge list.
"""

import copy
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERFBENCH = os.path.join(ROOT, "perfbench")

ARCH = {
    "model_type": "GAT", "radius": 3.0, "max_neighbours": 12,
    "hidden_dim": 8, "num_conv_layers": 3, "negative_slope": 0.2,
    "dropout": 0.0, "activation_function": "elu",
    "output_heads": {
        "graph": {"num_sharedlayers": 2, "dim_sharedlayers": 8,
                  "num_headlayers": 2, "dim_headlayers": [12, 8]},
        "node": {"num_headlayers": 2, "dim_headlayers": [10, 10],
                 "type": "mlp"},
    },
    "task_weights": [1.0, 1.0],
}
GRAPHS = 8
# f32 sums in another order (a K-axis contraction over slots and a self
# term apart, or one fused scatter, against the reference's blocks of edge
# rows and its two segment sums): a leaf's gap stays at a few 1e-6 of its
# norm
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
           "ref": check.load_reference("GAT")}
    for p in added:
        sys.path.remove(p)


def _files(heads, dense, periodic, **arch_keys):
    arch = dict(copy.deepcopy(ARCH), heads=heads, dense_aggregation=dense,
                periodic_boundary_conditions=periodic, **arch_keys)
    config = {"model_type": "GAT", "NeuralNetwork": {
        "Architecture": {k: v for k, v in arch.items() if v is not None},
        "Variables_of_interest": {
            "input_node_features": [0],
            "output_names": ["mean_coordination", "coordination"],
            "output_index": [0, 1], "type": ["graph", "node"],
            "denormalize_output": False},
        "Training": {
            "num_epoch": 1, "perc_train": 0.7, "batch_buckets": 1,
            "contiguous_buckets": True, "steps_per_dispatch": 1,
            "device_prefetch": 0, "mixed_precision": False,
            "loss_function_type": "mse",
            "Optimizer": {"type": "AdamW", "learning_rate": 1e-3}},
    }}
    mix = {"shape": "slab" if periodic else "cluster", "lattice_a": 2.5,
           "jitter": 0.08, "layers": 3, "vacuum": 15.0, "occupancy": 0.92,
           "radius": 3.0, "species": 3, "input_dim": 1,
           "node_target_dim": 1, "geometry_seed": 11, "training": {},
           "size_law": {"median": 9, "sigma": 0.4, "min": 5, "max": 14},
           "dataset_batches": 1, "eval_graphs": 2,
           "batch_size": {"1": GRAPHS}}
    return config, mix


def _build(bench, tmp_path, monkeypatch, heads=2, dense=True, periodic=False,
           **arch_keys):
    """(raw graphs, cfg, loader, model, trainer, state) through the
    driver's calls, on one batch of ``GRAPHS`` graphs of 5-14 atoms."""
    from hydragnn_tpu.obs import runtime as obs

    build = bench["build"]
    config, mix = _files(heads, dense, periodic, **arch_keys)
    graphs = bench["traffic_gen"].make_graphs(mix, GRAPHS, 3)
    monkeypatch.chdir(tmp_path)  # the program writes ./logs
    paths = build.write_dataset(str(tmp_path), graphs, graphs[:2])
    cfg = build.hydragnn_config(
        config, mix, {"name": "tiny", "chips": 1}, paths, GRAPHS)
    cfg, loader, model, trainer, state, _, _ = build.build_program(cfg)
    obs.deactivate(status="complete")
    return graphs, cfg, loader, model, trainer, state


@pytest.mark.parametrize("heads", [2, 4])
@pytest.mark.parametrize("dense", [True, False], ids=["dense", "edges"])
def pytest_loss_and_every_gradient_leaf_agree(bench, dense, heads, tmp_path,
                                              monkeypatch):
    """Periodic slabs where ``dense == (heads == 2)``, clusters else: each
    family and each head count sees both."""
    ref, C = bench["ref"], bench["common"]
    periodic = dense == (heads == 2)
    graphs, cfg, loader, model, trainer, state = _build(
        bench, tmp_path, monkeypatch, heads, dense, periodic)
    arch = cfg["NeuralNetwork"]["Architecture"]
    assert arch["dense_aggregation"] is dense and len(loader) == 1
    assert (model.heads, model.negative_slope, model.dropout) == (heads, 0.2, 0.0)
    ref_params = ref.init_params(
        jax.random.PRNGKey(5), arch, 1, [int(d) for d in arch["output_dim"]])
    ours = ref.to_program(ref_params)
    shapes = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: (a.shape, str(a.dtype)), t)
    assert shapes(ours) == shapes(state.params)
    # the middle layer concatenates: its projections read heads x 8 columns
    assert ours["encoder_conv_1"]["w_l"].shape == (heads * 8, heads * 8)
    assert ours["encoder_conv_2"]["bias"].shape == (8,)

    host = next(iter(loader))
    assert ("nbr_idx" in (host.extras or {})) is dense
    batch = trainer.put_batch(host)

    def program_loss(params):
        outputs = model.apply(
            {"params": params}, batch, train=True, mutable=["batch_stats"],
        )[0]
        return model.loss(outputs, batch)[0]

    loss, grads = jax.jit(jax.value_and_grad(program_loss))(ours)
    nodes = sum(len(g["pos"]) for g in graphs)
    ref_batch = C.assemble(graphs, arch["radius"], arch["max_neighbours"],
                           (nodes, 12 * nodes, GRAPHS))
    assert int(ref_batch["edge_mask"].sum()) == int(host.edge_mask.sum()) > 50
    (ref_loss, _), ref_grads = jax.jit(jax.value_and_grad(
        lambda p, b: ref.loss_fn(p, b, arch, {}), has_aux=True,
    ))(ref_params, ref_batch)
    assert abs(float(loss) - float(ref_loss)) <= 1e-5 * abs(float(ref_loss))
    ref_leaves = jax.tree_util.tree_leaves_with_path(ref.to_program(ref_grads))
    leaves = jax.tree_util.tree_leaves_with_path(grads)
    assert len(leaves) == len(ref_leaves) == 3 * 8 + 16
    for (path, got), (ref_path, want) in zip(leaves, ref_leaves):
        assert path == ref_path
        name = jax.tree_util.keystr(path)
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        if name.endswith("['bias']") and "encoder_conv" in name:
            # train-mode BatchNorm takes a conv's output bias out again:
            # its gradient is nought to rounding on both sides
            assert np.linalg.norm(got) < 1e-5 and np.linalg.norm(want) < 1e-5
            continue
        assert np.linalg.norm(want) > 0, name
        gap = np.linalg.norm(got - want) / np.linalg.norm(want)
        # b_l rides every message to the output, where BatchNorm takes it
        # out again: what is left of its gradient is the scores' share, a
        # sum whose larger part has cancelled, so its rounding shows more
        room = 5 if name.endswith("['b_l']") else 1
        assert gap <= room * TOLERANCE, (name, gap)


def pytest_weights_sum_to_one_and_padding_adds_nothing(bench, tmp_path,
                                                       monkeypatch):
    """One conv on a dense batch. A channel that is 1 on every node and
    that the scores do not read comes out as the sum of a receiver's
    weights: 1 on every real row and head, 0 on padded rows. Then garbage
    in the padded nodes' rows and in what the padded slots point at leaves
    every real row as it was, bit for bit."""
    from hydragnn_tpu.models.gat import GATv2Conv

    _, _, loader, _, trainer, _ = _build(bench, tmp_path, monkeypatch)
    host = next(iter(loader))
    n_pad, k_in = host.extras["nbr_idx"].shape
    real = np.asarray(host.node_mask)
    assert 0 < real.sum() < n_pad and not host.extras["nbr_mask"].all()
    heads, width, in_dim = 2, 8, 5
    conv = GATv2Conv(in_dim=in_dim, out_dim=width, heads=heads,
                     negative_slope=0.2, dropout=0.0, concat=True)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(n_pad, in_dim)), jnp.float32)
    batch = trainer.put_batch(host)
    params = conv.init(jax.random.PRNGKey(1), x, None, batch)["params"]
    last = np.arange(heads) * width + width - 1  # each head's last channel
    params = dict(
        params,
        w_l=params["w_l"].at[:, last].set(0.0),
        b_l=jnp.asarray(rng.normal(size=heads * width), jnp.float32
                        ).at[last].set(1.0),
        b_r=jnp.asarray(rng.normal(size=heads * width), jnp.float32),
        att=params["att"].at[..., width - 1].set(0.0),
    )
    apply = jax.jit(lambda x, b: conv.apply({"params": params}, x, None, b)[0])
    out = np.asarray(apply(x, batch))
    sums = out[:, last]
    np.testing.assert_allclose(sums[real], 1.0, rtol=0, atol=2e-6)
    assert np.all(out[~real] == 0.0)
    others = np.delete(out[real], last, axis=1)
    assert np.abs(others).max() > 0.1 and np.std(others) > 0.1

    mask = np.asarray(host.extras["nbr_mask"])
    wild_idx = np.where(mask, host.extras["nbr_idx"],
                        rng.integers(0, n_pad, (n_pad, k_in))).astype(np.int32)
    wild = batch.replace(extras=dict(batch.extras, nbr_idx=jnp.asarray(wild_idx)))
    wild_x = jnp.where(real[:, None], x, 1e4 * (1.0 + jnp.abs(x)))
    again = np.asarray(apply(wild_x, wild))
    assert np.array_equal(again[real], out[real])
    assert np.all(again[~real] == 0.0)


def pytest_the_factory_hears_the_three_keys_or_keeps_the_reference(
        bench, tmp_path, monkeypatch):
    from hydragnn_tpu.models.create import create_model_config
    from hydragnn_tpu.models.gat import GATStack
    from hydragnn_tpu.train.driver import _arch_for_factory

    (tmp_path / "silent").mkdir()
    _, cfg, loader, model, trainer, state = _build(
        bench, tmp_path / "silent", monkeypatch, heads=None,
        negative_slope=None, dropout=None)
    arch = _arch_for_factory(cfg)
    assert not {"heads", "negative_slope", "dropout"} & set(arch)
    assert (model.heads, model.negative_slope, model.dropout) == (6, 0.05, 0.25)
    # leaf by leaf what the factory built before it read the keys
    common = {f: getattr(model, f) for f in (
        "input_dim", "hidden_dim", "output_dim", "output_type", "config_heads",
        "activation", "loss_function_type", "equivariance", "loss_weights",
        "num_conv_layers", "num_nodes", "conv_checkpointing", "initial_bias",
        "loss_nll", "partition_axis")}
    before = GATStack(heads=6, negative_slope=0.05, **common)
    assert before == model
    batch = trainer.put_batch(next(iter(loader)))
    want = trainer.init_state(batch, seed=0).params
    got = state.params
    assert jax.tree_util.tree_structure(want) == jax.tree_util.tree_structure(got)
    assert got["encoder_conv_0"]["att"].shape == (1, 6, 8)
    for a, b in zip(jax.tree_util.tree_leaves(want), jax.tree_util.tree_leaves(got)):
        assert np.array_equal(np.asarray(a), np.asarray(b))

    def losses(trainer, state, batch):
        fresh = lambda: jax.tree_util.tree_map(jnp.copy, state)  # noqa: E731
        return [float(trainer._train_step(  # the step donates its state
            fresh(), batch, jax.random.PRNGKey(s))[1]["loss"]) for s in (1, 2)]

    first, second = losses(trainer, state, batch)
    assert first != second  # dropout 0.25 draws from the step's rng

    (tmp_path / "stated").mkdir()
    _, cfg, loader, model, trainer, state = _build(
        bench, tmp_path / "stated", monkeypatch, heads=4)
    assert create_model_config(_arch_for_factory(cfg)) == model
    assert (model.heads, model.negative_slope, model.dropout) == (4, 0.2, 0.0)
    assert state.params["encoder_conv_0"]["att"].shape == (1, 4, 8)
    first, second = losses(trainer, state, trainer.put_batch(next(iter(loader))))
    assert first == second  # dropout 0.0: no draw


def pytest_the_attention_counts_read_what_the_batch_holds(bench, tmp_path,
                                                          monkeypatch):
    """What the benchmark's ``attention_padding_waste_pct.train`` works out
    from a batch's spans is what the batch holds: ``bucket x (k_in + 1)``
    (``collate``, ``neighbor_lists``) is what the softmax is computed over
    (k_in slots and a self-loop slot of every padded row), ``edges + nodes``
    what it is over in the reference (its own edges and one self-loop an
    atom). The loader is told nothing about the model for it."""
    from hydragnn_tpu.utils import tracer

    C = bench["common"]
    graphs, _, loader, _, _, _ = _build(bench, tmp_path, monkeypatch,
                                        periodic=True)
    tracer.reset()
    host = next(iter(loader))
    records = tracer.spans().records
    collate = [s for s in records if s.name == "collate"][0]
    lists = [s for s in records if s.name == "neighbor_lists"][0]
    assert lists.parent == collate.id
    n_pad, k_in = host.extras["nbr_idx"].shape
    assert collate.attrs["bucket"] * (lists.attrs["k_in"] + 1) == n_pad * (k_in + 1)
    real = int(host.extras["nbr_mask"].sum()) + int(host.node_mask.sum())
    assert collate.attrs["edges"] + collate.attrs["nodes"] == real
    by_reference = sum(
        len(C.capped_radius_graph(g["pos"], g["cell"], 3.0, 12)[0])
        + len(g["pos"]) for g in graphs)
    assert by_reference == real < n_pad * (k_in + 1)
