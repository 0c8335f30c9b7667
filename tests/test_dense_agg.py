"""Dense neighbor-list aggregation: numerical parity with the segment
path (forward AND gradients — the custom VJP routes the backward pass
through reverse neighbor lists) plus host-side list construction."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hydragnn_tpu.graph import collate_graphs, pad_sizes_for
from hydragnn_tpu.models import create_model_config, init_model_params
from hydragnn_tpu.ops.dense_agg import (
    build_neighbor_lists,
    dense_minmax,
    dense_moments,
    dense_sum,
    gather_neighbors,
    max_degree,
)

from test_models_forward import arch_config, make_batch


from hydragnn_tpu.ops.dense_agg import attach_neighbor_lists as _with_neighbors


def pytest_neighbor_list_construction():
    senders = np.array([0, 2, 1, 0, 3])
    receivers = np.array([1, 1, 0, 3, 3])
    mask = np.array([True, True, True, True, False])  # last edge is padding
    k_in, k_out = max_degree(senders, receivers, mask)
    assert (k_in, k_out) == (2, 2)
    ex = build_neighbor_lists(senders, receivers, mask, 4, k_in, k_out)
    # node 1 receives from 0 and 2, in edge order
    assert ex["nbr_idx"][1].tolist() == [0, 2]
    assert ex["nbr_mask"][1].tolist() == [True, True]
    assert ex["nbr_edge"][1].tolist() == [0, 1]
    # node 2 receives nothing
    assert ex["nbr_mask"][2].tolist() == [False, False]
    # padding edge 4 excluded: node 3 receives only edge 3 (from node 0)
    assert ex["nbr_mask"][3].tolist() == [True, False]
    assert ex["nbr_idx"][3, 0] == 0
    # reverse list: node 0 sends edges 0 (slot 0 of node 1) and 3 (slot 0
    # of node 3) -> flat positions 1*2+0 and 3*2+0
    assert sorted(ex["rev_idx"][0][ex["rev_mask"][0]].tolist()) == [2, 6]


def pytest_gather_neighbors_vjp_matches_autodiff():
    """The reverse-list backward equals the scatter-add the plain gather
    would produce."""
    rng = np.random.default_rng(0)
    n, d = 40, 8
    senders = rng.integers(0, n, 160)
    receivers = rng.integers(0, n, 160)
    mask = np.ones(160, bool)
    k_in, k_out = max_degree(senders, receivers, mask)
    ex = build_neighbor_lists(senders, receivers, mask, n, k_in, k_out)
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    nbr = jnp.asarray(ex["nbr_idx"])
    nmask = jnp.asarray(ex["nbr_mask"])
    rev = jnp.asarray(ex["rev_idx"])
    rmask = jnp.asarray(ex["rev_mask"])

    def f_custom(x):
        g = gather_neighbors(x, nbr, rev, rmask)
        return (jnp.where(nmask[..., None], g, 0.0) ** 2).sum()

    def f_plain(x):
        g = x[nbr]
        return (jnp.where(nmask[..., None], g, 0.0) ** 2).sum()

    g_custom = jax.grad(f_custom)(x)
    g_plain = jax.grad(f_plain)(x)
    np.testing.assert_allclose(
        np.asarray(g_custom), np.asarray(g_plain), rtol=1e-5, atol=1e-5
    )


def pytest_dense_reductions_match_segment():
    rng = np.random.default_rng(1)
    n, e, d = 30, 120, 16
    senders = rng.integers(0, n, e)
    receivers = rng.integers(0, n - 5, e)  # leave some empty receivers
    mask = rng.random(e) < 0.8
    # the collate contract: padding edges target the padding node slot, so
    # their zeroed data never reaches a real receiver's min/max
    senders[~mask] = n - 1
    receivers[~mask] = n - 1
    k_in, k_out = max_degree(senders, receivers, mask)
    ex = build_neighbor_lists(senders, receivers, mask, n, k_in, k_out)
    h_edges = rng.standard_normal((e, d)).astype(np.float32)

    from hydragnn_tpu.graph import segment_minmax_fused, segment_moments_fused

    hm = jnp.where(jnp.asarray(mask)[:, None], jnp.asarray(h_edges), 0.0)
    s, cnt, sq = segment_moments_fused(
        hm, jnp.asarray(receivers), n, weights=jnp.asarray(mask)
    )
    deg_ref = jnp.maximum(cnt, 1.0)
    mean_ref = s / deg_ref
    std_ref = jnp.sqrt(jnp.maximum(sq / deg_ref - mean_ref**2, 0.0) + 1e-5)
    mn_ref, mx_ref = segment_minmax_fused(
        hm, jnp.asarray(receivers), n, has=cnt > 0
    )

    # dense path: messages arranged [N, K, D] via nbr_edge
    h_dense = jnp.asarray(h_edges)[jnp.asarray(ex["nbr_edge"])]
    nmask = jnp.asarray(ex["nbr_mask"])
    mean_d, std_d, deg_d, has_d = dense_moments(h_dense, nmask)
    mn_d, mx_d = dense_minmax(h_dense, nmask, has_d)

    np.testing.assert_allclose(mean_d, mean_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(std_d, std_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(mn_d, mn_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(mx_d, mx_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        dense_sum(h_dense, nmask), s, rtol=1e-5, atol=1e-6
    )


# default tier: one combo per aggregation STRUCTURE (multi-aggregator,
# plain receiver-sum, edge-conditioned, sender-side equivariant x2);
# HYDRAGNN_FULL_TEST=1 runs the whole matrix
_COMBOS = [
    ("PNA", "edges"),
    ("GAT", "plain"),
    ("DimeNet", "plain"),
    ("GIN", "plain"),
    ("SchNet", "equivariant"),
    ("EGNN", "equivariant"),
]
if int(os.getenv("HYDRAGNN_FULL_TEST", "0")) == 1:
    _COMBOS += [
        ("PNA", "plain"),
        ("SAGE", "plain"),
        ("MFC", "plain"),
        ("CGCNN", "edges"),
        ("SchNet", "plain"),
        ("EGNN", "plain"),
    ]


@pytest.mark.parametrize("model_type,variant", _COMBOS)
def pytest_dense_path_parity(model_type, variant):
    """Full stacks: identical outputs and parameter gradients through the
    dense and segment paths (receiver-side AND sender-side aggregations,
    equivariant coordinate updates included)."""
    batch = make_batch(with_triplets=(model_type == "DimeNet"))
    cfg = arch_config(model_type)
    if variant == "edges":
        cfg["edge_dim"] = 1
    if variant == "equivariant":
        cfg["equivariance"] = True
    model = create_model_config(cfg)
    params = init_model_params(model, batch)
    dense_batch = _with_neighbors(batch)

    def loss(p, b):
        outputs = model.apply(p, b, train=False)
        return sum(jnp.sum(o**2) for o in outputs)

    l_seg, g_seg = jax.value_and_grad(loss)(params, batch)
    l_den, g_den = jax.value_and_grad(loss)(params, dense_batch)
    np.testing.assert_allclose(float(l_seg), float(l_den), rtol=1e-4)
    flat_seg = jax.tree_util.tree_leaves(g_seg)
    flat_den = jax.tree_util.tree_leaves(g_den)
    for a, b in zip(flat_seg, flat_den):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        )


# ---------------------------------------------------------------------------
# host-side list construction: slots per edge + one assembly
# ---------------------------------------------------------------------------

_LIST_KEYS = ("nbr_idx", "nbr_edge", "nbr_mask", "rev_idx", "rev_mask")
_SLOT_KEYS = ("out_edge", "edge_slot", "out_slot")


def _frozen_group_lists(owner_ids, valid_mask, num_groups, k):
    """The sort-based grouping as it stood before slots moved to the
    sample (PR 24's ``build_group_lists``), frozen here as a reference."""
    owner_ids = np.asarray(owner_ids, np.int64)
    rows = np.arange(owner_ids.shape[0])
    if valid_mask is not None:
        keep = np.asarray(valid_mask, bool)
        owner_ids, rows = owner_ids[keep], rows[keep]
    lists = np.zeros((num_groups, k), np.int32)
    mask = np.zeros((num_groups, k), bool)
    order = np.argsort(owner_ids, kind="stable")
    o_sorted = owner_ids[order]
    slot = np.arange(o_sorted.shape[0]) - np.searchsorted(
        o_sorted, o_sorted, side="left"
    )
    lists[o_sorted, slot] = rows[order]
    mask[o_sorted, slot] = True
    return lists, mask


def _frozen_neighbor_lists(senders, receivers, edge_mask, n, k_in, k_out):
    """PR 24's whole-batch ``build_neighbor_lists`` (slot tables included),
    frozen: two stable sorts, two ``nonzero`` passes, fancy-index scatters."""
    senders = np.asarray(senders, np.int64)
    nbr_edge, nbr_mask = _frozen_group_lists(receivers, edge_mask, n, k_in)
    nbr_idx = np.where(nbr_mask, senders[nbr_edge], 0).astype(np.int32)
    flat_of_edge = np.zeros(senders.shape[0], np.int64)
    rr, ss = np.nonzero(nbr_mask)
    flat_of_edge[nbr_edge[rr, ss]] = rr * k_in + ss
    out_edge, rev_mask = _frozen_group_lists(senders, edge_mask, n, k_out)
    rev_idx = np.where(rev_mask, flat_of_edge[out_edge], 0).astype(np.int32)
    slot_out_of_edge = np.zeros(senders.shape[0], np.int64)
    rr, ss = np.nonzero(rev_mask)
    slot_out_of_edge[out_edge[rr, ss]] = rr * k_out + ss
    return dict(
        nbr_idx=nbr_idx, nbr_edge=nbr_edge, nbr_mask=nbr_mask,
        rev_idx=rev_idx, rev_mask=rev_mask, out_edge=out_edge,
        edge_slot=flat_of_edge.astype(np.int32),
        out_slot=slot_out_of_edge.astype(np.int32),
    )


def _oracle_neighbor_lists(senders, receivers, edge_mask, n, k_in, k_out):
    """Independent oracle: walk the edges in row order, appending each to
    its receiver's and its sender's list."""
    e = len(senders)
    out = dict(
        nbr_idx=np.zeros((n, k_in), np.int32),
        nbr_edge=np.zeros((n, k_in), np.int32),
        nbr_mask=np.zeros((n, k_in), bool),
        rev_idx=np.zeros((n, k_out), np.int32),
        rev_mask=np.zeros((n, k_out), bool),
        out_edge=np.zeros((n, k_out), np.int32),
        edge_slot=np.zeros(e, np.int32),
        out_slot=np.zeros(e, np.int32),
    )
    fill_in, fill_out = [0] * n, [0] * n
    for row in range(e):
        if not edge_mask[row]:
            continue
        s, r = int(senders[row]), int(receivers[row])
        ki, ko = fill_in[r], fill_out[s]
        fill_in[r] += 1
        fill_out[s] += 1
        out["nbr_idx"][r, ki] = s
        out["nbr_edge"][r, ki] = row
        out["nbr_mask"][r, ki] = True
        out["rev_idx"][s, ko] = r * k_in + ki
        out["rev_mask"][s, ko] = True
        out["out_edge"][s, ko] = row
        out["edge_slot"][row] = r * k_in + ki
        out["out_slot"][row] = s * k_out + ko
    return out


def _list_graph(n, edge_index, rng):
    from hydragnn_tpu.data.dataobj import GraphData

    d = GraphData(
        x=rng.random((n, 2)).astype(np.float32),
        pos=rng.random((n, 3)).astype(np.float32),
        edge_index=np.asarray(edge_index, np.int64).reshape(2, -1),
    )
    d.targets = [np.asarray([1.0], np.float32)]
    d.target_types = ["graph"]
    return d


def _list_case(kind, rng):
    """Samples of one multi-graph batch, per the named hazard."""
    graphs = []
    for g in range(6):
        n = int(rng.integers(2, 14))
        e = int(rng.integers(1, 5 * n))
        ei = rng.integers(0, n, (2, e))
        if kind == "edgeless_graph" and g in (0, 3):
            ei = np.zeros((2, 0), np.int64)
        elif kind == "duplicate_edges":
            ei = np.concatenate([ei, ei[:, : e // 2 + 1], ei[:, :1]], 1)
        elif kind == "self_loops":
            ei[1, ::2] = ei[0, ::2]
        elif kind == "shuffled_order":
            # sorted by receiver, then shuffled: slot order must follow
            # the rows as they stand, not any canonical order
            ei = ei[:, np.argsort(ei[1], kind="stable")]
            ei = ei[:, rng.permutation(ei.shape[1])]
        graphs.append(_list_graph(n, ei, rng))
    return graphs


@pytest.mark.parametrize("with_slot_tables", [False, True])
@pytest.mark.parametrize(
    "kind",
    ["random_padded", "edgeless_graph", "duplicate_edges", "self_loops",
     "shuffled_order"],
)
def pytest_neighbor_lists_equal_oracle_and_frozen_sort(kind, with_slot_tables):
    """The slot-then-assemble construction — through the loader's
    per-sample slots AND through ``build_neighbor_lists`` on the batch's
    edge list — is bit-identical (values, dtypes, padded slots) to a plain
    per-edge loop and to the parent's sort-based builder."""
    from hydragnn_tpu.data.loaders import BatchLayout, collate_for_layout

    rng = np.random.default_rng(sum(map(ord, kind)))
    samples = _list_case(kind, rng)
    n_pad = sum(s.num_nodes for s in samples) + 5
    e_pad = sum(s.num_edges for s in samples) + 11
    ki = max(int(np.bincount(s.edge_index[1]).max()) for s in samples
             if s.num_edges)
    ko = max(int(np.bincount(s.edge_index[0]).max()) for s in samples
             if s.num_edges)
    layout = BatchLayout(
        n_pad=n_pad, e_pad=e_pad, g_pad=len(samples) + 1,
        head_types=("graph",), head_dims=(1,),
        need_triplets=with_slot_tables, need_neighbors=True,
        k_in=ki + 1, k_out=ko,  # one spare in-slot: padding inside rows
    )
    batch = collate_for_layout(samples, layout)
    args = (batch.senders, batch.receivers, batch.edge_mask, n_pad,
            layout.k_in, layout.k_out)
    oracle = _oracle_neighbor_lists(*args)
    frozen = _frozen_neighbor_lists(*args)
    direct = build_neighbor_lists(*args, with_slot_tables=with_slot_tables)
    keys = _LIST_KEYS + (_SLOT_KEYS if with_slot_tables else ())
    assert set(direct) == set(keys)
    assert set(keys) <= set(batch.extras)
    assert with_slot_tables or not set(_SLOT_KEYS) & set(batch.extras)
    for key in keys:
        for got in (batch.extras[key], direct[key]):
            assert got.dtype == frozen[key].dtype, key
            np.testing.assert_array_equal(got, oracle[key], err_msg=key)
            np.testing.assert_array_equal(got, frozen[key], err_msg=key)
    # all eight, whatever the layout asked for: the oracle and the frozen
    # sort agree with the full construction
    full = build_neighbor_lists(*args, with_slot_tables=True)
    for key in _LIST_KEYS + _SLOT_KEYS:
        np.testing.assert_array_equal(full[key], oracle[key], err_msg=key)
        np.testing.assert_array_equal(full[key], frozen[key], err_msg=key)


def pytest_neighbor_lists_unmasked_and_scattered_mask():
    """``build_neighbor_lists`` on a raw edge list: no mask at all, and a
    mask whose False rows sit between real ones (not the collate prefix)."""
    rng = np.random.default_rng(11)
    n, e = 17, 90
    senders = rng.integers(0, n, e)
    receivers = rng.integers(0, n, e)
    for mask in (None, rng.random(e) < 0.7):
        m = np.ones(e, bool) if mask is None else mask
        k_in, k_out = max_degree(senders, receivers, m)
        got = build_neighbor_lists(
            senders, receivers, mask, n, k_in, k_out, with_slot_tables=True
        )
        want = _oracle_neighbor_lists(senders, receivers, m, n, k_in, k_out)
        for key in _LIST_KEYS + _SLOT_KEYS:
            np.testing.assert_array_equal(got[key], want[key], err_msg=key)


@pytest.mark.parametrize("route", ["edge_list", "sample_slots"])
@pytest.mark.parametrize("label", ["k_in", "k_out"])
def pytest_neighbor_list_overflow_raises(label, route):
    """A list wider than the layout's budget raises the same ValueError,
    with the same words, on both routes."""
    from hydragnn_tpu.data.loaders import BatchLayout, collate_for_layout

    rng = np.random.default_rng(3)
    # node 0 receives 4 edges and sends 3
    ei = np.array([[1, 2, 3, 4, 0, 0, 0], [0, 0, 0, 0, 1, 2, 3]])
    k_in, k_out = (3, 3) if label == "k_in" else (4, 2)
    msg = f"group size exceeds layout {label}={dict(k_in=3, k_out=2)[label]}"
    with pytest.raises(ValueError, match=msg + "; recompute the layout"):
        if route == "edge_list":
            build_neighbor_lists(
                ei[0], ei[1], np.ones(7, bool), 6, k_in, k_out
            )
        else:
            layout = BatchLayout(
                n_pad=8, e_pad=8, g_pad=2, head_types=("graph",),
                head_dims=(1,), need_neighbors=True, k_in=k_in, k_out=k_out,
            )
            collate_for_layout([_list_graph(5, ei, rng)], layout)


def pytest_edge_slots_are_ranks_in_row_order():
    from hydragnn_tpu.ops.dense_agg import edge_slots

    senders = np.array([0, 2, 1, 0, 3, 0])
    receivers = np.array([1, 1, 0, 3, 3, 1])
    slots = edge_slots(senders, receivers)
    assert slots.dtype == np.uint8 and slots.shape == (2, 6)
    assert slots[0].tolist() == [0, 1, 0, 0, 1, 2]  # by receiver
    assert slots[1].tolist() == [0, 0, 0, 1, 0, 2]  # by sender
    assert (slots.max(axis=1).astype(int) + 1).tolist() == list(
        max_degree(senders, receivers)
    )
    empty = edge_slots(np.zeros(0, np.int64), np.zeros(0, np.int64))
    assert empty.shape == (2, 0) and empty.dtype == np.uint8
    # a hub wider than a byte widens the dtype, not the values
    hub = edge_slots(np.arange(300), np.zeros(300, np.int64))
    assert hub.dtype == np.uint16 and hub[0].tolist() == list(range(300))
