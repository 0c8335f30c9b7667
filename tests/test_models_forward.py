"""Forward/init smoke tests: every stack builds, runs, and yields finite
outputs and losses on a padded random batch (single-head graph + node)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hydragnn_tpu.graph import GraphBatch, collate_graphs, pad_sizes_for
from hydragnn_tpu.models import (
    MODEL_TYPES,
    compute_triplets,
    create_model_config,
    init_model_params,
)


class FakeData:
    def __init__(self, rng, n):
        self.x = rng.random((n, 1)).astype(np.float32)
        self.pos = rng.random((n, 3)).astype(np.float32)
        # ring graph, both directions
        src = np.arange(n)
        dst = (src + 1) % n
        self.edge_index = np.stack(
            [np.concatenate([src, dst]), np.concatenate([dst, src])]
        ).astype(np.int64)
        d = np.linalg.norm(
            self.pos[self.edge_index[0]] - self.pos[self.edge_index[1]], axis=1
        )
        self.edge_attr = d[:, None].astype(np.float32)
        self.targets = [
            np.array([self.x.sum()], dtype=np.float32),  # graph head
            self.x.astype(np.float32),  # node head
        ]


def make_batch(num_graphs=3, max_n=6, with_triplets=False):
    rng = np.random.default_rng(0)
    samples = [FakeData(rng, rng.integers(3, max_n + 1)) for _ in range(num_graphs)]
    n_pad, e_pad, g_pad = pad_sizes_for(
        max_n, 2 * max_n, num_graphs, graph_multiple=8
    )
    batch = collate_graphs(
        samples,
        n_pad,
        e_pad,
        g_pad,
        head_types=("graph", "node"),
        head_dims=(1, 1),
    )
    if with_triplets:
        t_pad = 8 * e_pad
        ti = np.full((t_pad,), n_pad - 1, np.int32)
        tj = np.full((t_pad,), n_pad - 1, np.int32)
        tk = np.full((t_pad,), n_pad - 1, np.int32)
        tkj = np.zeros((t_pad,), np.int32)
        tji = np.zeros((t_pad,), np.int32)
        tmask = np.zeros((t_pad,), bool)
        off_n = 0
        off_e = 0
        off_t = 0
        for s in samples:
            a, b, c, kj, ji = compute_triplets(s.edge_index, s.x.shape[0])
            t = a.shape[0]
            ti[off_t : off_t + t] = a + off_n
            tj[off_t : off_t + t] = b + off_n
            tk[off_t : off_t + t] = c + off_n
            tkj[off_t : off_t + t] = kj + off_e
            tji[off_t : off_t + t] = ji + off_e
            tmask[off_t : off_t + t] = True
            off_t += t
            off_n += s.x.shape[0]
            off_e += s.edge_index.shape[1]
        batch = batch.replace(
            extras={
                "trip_i": ti,
                "trip_j": tj,
                "trip_k": tk,
                "trip_kj": tkj,
                "trip_ji": tji,
                "trip_mask": tmask,
            }
        )
    return jax.tree_util.tree_map(jnp.asarray, batch)


def arch_config(model_type):
    cfg = {
        "model_type": model_type,
        "input_dim": 1,
        "hidden_dim": 8,
        "output_dim": [1, 1],
        "output_type": ["graph", "node"],
        "output_heads": {
            "graph": {
                "num_sharedlayers": 2,
                "dim_sharedlayers": 4,
                "num_headlayers": 2,
                "dim_headlayers": [10, 10],
            },
            "node": {"num_headlayers": 2, "dim_headlayers": [4, 4], "type": "mlp"},
        },
        "task_weights": [1.0, 1.0],
        "num_conv_layers": 2,
        "num_nodes": 6,
        "max_neighbours": 10,
        "edge_dim": None,
        "pna_deg": [0, 2, 10, 4],
        "num_gaussians": 50,
        "num_filters": 16,
        "radius": 2.0,
        "basis_emb_size": 8,
        "envelope_exponent": 5,
        "int_emb_size": 16,
        "out_emb_size": 16,
        "num_after_skip": 2,
        "num_before_skip": 1,
        "num_radial": 6,
        "num_spherical": 7,
        "equivariance": False,
    }
    return cfg


@pytest.mark.parametrize("model_type", MODEL_TYPES)
def pytest_forward_finite(model_type):
    batch = make_batch(with_triplets=(model_type == "DimeNet"))
    model = create_model_config(arch_config(model_type))
    variables = init_model_params(model, batch)
    outputs, _ = model.apply(
        variables,
        batch,
        train=True,
        mutable=["batch_stats"],
        rngs={"dropout": jax.random.PRNGKey(2)},
    )
    assert len(outputs) == 2
    assert outputs[0].shape == (batch.num_graphs, 1)
    assert outputs[1].shape == (batch.num_nodes, 1)
    tot, tasks = model.loss(outputs, batch)
    assert jnp.isfinite(tot), f"{model_type} loss not finite"
    for t in tasks:
        assert jnp.isfinite(t)


@pytest.mark.parametrize("model_type", ["SchNet", "EGNN"])
def pytest_equivariant_forward(model_type):
    batch = make_batch()
    cfg = arch_config(model_type)
    cfg["equivariance"] = True
    model = create_model_config(cfg)
    variables = init_model_params(model, batch)
    outputs = model.apply(variables, batch, train=False)
    tot, _ = model.loss(outputs, batch)
    assert jnp.isfinite(tot)


def pytest_egnn_fused_edge_mlp_matches_concat():
    """The E_GCL algebraic edge-MLP fusion (node-axis projections of the
    first Linear) must reproduce the naive concat formulation exactly
    (same parameters, same math — only float contraction order differs)."""
    from hydragnn_tpu.graph import segment_sum
    from hydragnn_tpu.models.egnn import E_GCL, _safe_sqrt

    batch = make_batch()
    x, pos = batch.x, batch.pos
    conv = E_GCL(
        in_dim=1, out_dim=8, hidden_dim=8, edge_attr_dim=1, equivariant=True
    )
    variables = conv.init(jax.random.PRNGKey(3), x, pos, batch)
    h_fused, pos_fused = conv.apply(variables, x, pos, batch)

    p = variables["params"]
    row, col = batch.senders, batch.receivers
    n = x.shape[0]
    coord_diff = pos[row] - pos[col]
    radial = (coord_diff * coord_diff).sum(-1, keepdims=True)
    coord_diff = coord_diff / (_safe_sqrt(radial) + 1.0)
    parts = jnp.concatenate([x[row], x[col], radial, batch.edge_attr], axis=-1)
    e = jax.nn.relu(parts @ p["edge_mlp_0"]["kernel"] + p["edge_mlp_0"]["bias"])
    e = jax.nn.relu(e @ p["edge_mlp_1"]["kernel"] + p["edge_mlp_1"]["bias"])
    e = jnp.where(batch.edge_mask[:, None], e, 0.0)
    cw = jax.nn.relu(e @ p["coord_mlp_0"]["kernel"] + p["coord_mlp_0"]["bias"])
    cw = jnp.tanh(cw @ p["coord_mlp_1"])
    trans = jnp.clip(coord_diff * cw, -100.0, 100.0)
    trans = jnp.where(batch.edge_mask[:, None], trans, 0.0)
    agg = segment_sum(e, row, n)
    coord_agg = segment_sum(trans, row, n)
    cnt = segment_sum(batch.edge_mask.astype(trans.dtype), row, n)
    pos_naive = pos + coord_agg / jnp.maximum(cnt, 1.0)[:, None]
    h = jnp.concatenate([x, agg], axis=-1)
    h = jax.nn.relu(h @ p["node_mlp_0"]["kernel"] + p["node_mlp_0"]["bias"])
    h_naive = h @ p["node_mlp_1"]["kernel"] + p["node_mlp_1"]["bias"]

    np.testing.assert_allclose(h_fused, h_naive, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(pos_fused, pos_naive, atol=2e-5, rtol=1e-5)


def pytest_egnn_fused_dense_edge_attr_matches_segment():
    """The dense-frame E_GCL fusion with edge attributes (the
    project-then-gather edge-attr branch) must agree with the segment path
    on the same parameters — covers the ('EGNN', edge_attr) combination no
    other test exercises."""
    from hydragnn_tpu.models.egnn import E_GCL
    from hydragnn_tpu.ops.dense_agg import attach_neighbor_lists

    batch = make_batch()
    x, pos = batch.x, batch.pos
    conv = E_GCL(
        in_dim=1, out_dim=8, hidden_dim=8, edge_attr_dim=1, equivariant=True
    )
    variables = conv.init(jax.random.PRNGKey(5), x, pos, batch)
    h_seg, pos_seg = conv.apply(variables, x, pos, batch)
    dense_batch = attach_neighbor_lists(batch)
    h_dense, pos_dense = conv.apply(variables, x, pos, dense_batch)
    np.testing.assert_allclose(h_dense, h_seg, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(pos_dense, pos_seg, atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize(
    "model_type,equivariant,n_leaves,names,abs_sum",
    [
        ("PNA", False, 32, "7a1d9d911af1ab86", 180.57219943185555),
        ("EGNN", False, 32, "810e152fad1155af", 162.90563737403136),
        ("EGNN", True, 35, "fa507d4a665d0d9f", 176.556326065358),
    ],
)
def pytest_parameter_tree_as_recorded_at_72f8b5d(
    model_type, equivariant, n_leaves, names, abs_sum
):
    """Leaf paths, shapes and seeded values of the two stacks that lost a
    kernel branch in PR 28, against what commit 72f8b5d initialised from
    the same seed: checkpoints and seeded trajectories carry over."""
    import hashlib

    cfg = arch_config(model_type)
    cfg["equivariance"] = equivariant
    params = init_model_params(create_model_config(cfg), make_batch())["params"]
    leaves = jax.tree_util.tree_leaves_with_path(params)
    listed = "\n".join(
        f"{jax.tree_util.keystr(path)} {tuple(leaf.shape)}"
        for path, leaf in leaves
    )
    assert len(leaves) == n_leaves
    assert hashlib.sha256(listed.encode()).hexdigest()[:16] == names, listed
    total = sum(np.abs(np.asarray(a, np.float64)).sum() for _, a in leaves)
    np.testing.assert_allclose(total, abs_sum, rtol=1e-9)
