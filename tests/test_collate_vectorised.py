"""``graph/batch.py collate_graphs`` lays a batch out with a fixed number of
numpy calls a leaf (PR 35). Its oracle is the per-sample loop it replaced,
kept here and nowhere in the package: every leaf must be bitwise equal to
the loop's (values, dtype, shape), fresh and through a pool slot that held
a larger batch just before."""

import numpy as np
import pytest

import jax

from hydragnn_tpu.data.dataobj import GraphData
from hydragnn_tpu.data.loaders import collate_for_layout, compute_layout
from hydragnn_tpu.graph.batch import GraphBatch, collate_graphs
from hydragnn_tpu.graph.slots import SlotPool, filled


def _loop_collate(
    samples,
    n_pad,
    e_pad,
    g_pad,
    head_types=(),
    head_dims=(),
    to_device=False,
    slot=None,
):
    """``collate_graphs`` as it was up to PR 34: one pass over the samples."""
    num_graphs = len(samples)
    total_nodes = int(sum(s.x.shape[0] for s in samples))
    total_edges = int(sum(s.edge_index.shape[1] for s in samples))
    if num_graphs > g_pad - 1:
        raise ValueError(f"batch of {num_graphs} graphs exceeds g_pad-1={g_pad - 1}")
    if total_nodes > n_pad - 1:
        raise ValueError(f"{total_nodes} nodes exceed n_pad-1={n_pad - 1}")
    if total_edges > e_pad:
        raise ValueError(f"{total_edges} edges exceed e_pad={e_pad}")

    feat_dim = samples[0].x.shape[1]
    x = filled(slot, "x", (n_pad, feat_dim), np.float32)
    pos = filled(slot, "pos", (n_pad, 3), np.float32)
    # padding edges point at the last node slot (always a padding node since
    # total_nodes <= n_pad - 1) and live in the padding graph.
    senders = filled(slot, "senders", (e_pad,), np.int32, n_pad - 1)
    receivers = filled(slot, "receivers", (e_pad,), np.int32, n_pad - 1)
    edge_dim = None
    if samples[0].edge_attr is not None:
        edge_dim = samples[0].edge_attr.shape[1]
        edge_attr = filled(slot, "edge_attr", (e_pad, edge_dim), np.float32)
    node_graph = filled(slot, "node_graph", (n_pad,), np.int32, g_pad - 1)
    n_node = filled(slot, "n_node", (g_pad,), np.int32)
    n_edge = filled(slot, "n_edge", (g_pad,), np.int32)
    node_mask = filled(slot, "node_mask", (n_pad,), bool)
    edge_mask = filled(slot, "edge_mask", (e_pad,), bool)
    graph_mask = filled(slot, "graph_mask", (g_pad,), bool)

    targets = [
        filled(
            slot, f"target{ih}", (g_pad if t == "graph" else n_pad, d),
            np.float32,
        )
        for ih, (t, d) in enumerate(zip(head_types, head_dims))
    ]

    node_off = 0
    edge_off = 0
    for g, s in enumerate(samples):
        n = s.x.shape[0]
        e = s.edge_index.shape[1]
        x[node_off : node_off + n] = s.x
        if s.pos is not None:
            pos[node_off : node_off + n] = s.pos
        senders[edge_off : edge_off + e] = s.edge_index[0] + node_off
        receivers[edge_off : edge_off + e] = s.edge_index[1] + node_off
        if edge_dim is not None:
            edge_attr[edge_off : edge_off + e] = s.edge_attr
        node_graph[node_off : node_off + n] = g
        n_node[g] = n
        n_edge[g] = e
        node_mask[node_off : node_off + n] = True
        edge_mask[edge_off : edge_off + e] = True
        graph_mask[g] = True
        for ih, t in enumerate(head_types):
            tgt = np.asarray(s.targets[ih], dtype=np.float32)
            if t == "graph":
                targets[ih][g] = tgt.reshape(-1)
            else:
                targets[ih][node_off : node_off + n] = tgt.reshape(n, -1)
        node_off += n
        edge_off += e

    # padding nodes all sit in the padding graph; record its node count so
    # segment means over the padding graph stay well-defined.
    n_node[g_pad - 1] = n_pad - node_off
    n_edge[g_pad - 1] = e_pad - edge_off

    return GraphBatch(
        x=x,
        pos=pos,
        senders=senders,
        receivers=receivers,
        edge_attr=edge_attr if edge_dim is not None else None,
        node_graph=node_graph,
        n_node=n_node,
        n_edge=n_edge,
        node_mask=node_mask,
        edge_mask=edge_mask,
        graph_mask=graph_mask,
        targets=tuple(targets),
    )


# ---- the batches ------------------------------------------------------------


def _sample(rng, n, e, feat=2, edge_dim=1, heads=(("graph", 1), ("node", 1)),
            target_kind="float32", with_pos=True, float64=False,
            strided=False):
    ftype = np.float64 if float64 else np.float32
    edge_index = rng.integers(0, max(n, 1), (2, e))
    if float64:
        edge_index = edge_index.astype(np.int32)
    if strided:  # a [e, 2] table read through its transpose (distdataset)
        edge_index = np.ascontiguousarray(edge_index.T).T
    g = GraphData(
        x=rng.standard_normal((n, feat)).astype(ftype),
        pos=rng.standard_normal((n, 3)).astype(ftype) if with_pos else None,
        edge_index=edge_index,
        edge_attr=(rng.standard_normal((e, edge_dim)).astype(ftype)
                   if edge_dim else None),
    )
    targets = []
    for kind, d in heads:
        shape = (d,) if kind == "graph" else (n, d)
        t = rng.standard_normal(shape) * 100
        if target_kind == "float32":
            t = t.astype(np.float32)
        elif target_kind == "int":
            t = t.astype(np.int64)
        elif target_kind == "list":
            t = t.tolist()
        elif target_kind == "scalar" and kind == "graph" and d == 1:
            t = float(t[0])
        targets.append(t)  # float64: as drawn
    g.targets = targets
    g.target_types = [kind for kind, _ in heads]
    return g


CASES = {
    "edge_attr": dict(),
    "no_edge_attr": dict(edge_dim=0),
    "pos_none": dict(pos_none=(1, 3)),
    "zero_edge": dict(zero_edge=(0, 2, 4)),
    "single": dict(graphs=1),
    "exactly_full": dict(full=True),
    "wide_heads": dict(heads=(("graph", 3), ("node", 2))),
    "float64_targets": dict(target_kind="float64"),
    "int_targets": dict(target_kind="int"),
    "list_targets": dict(target_kind="list"),
    "scalar_graph_targets": dict(target_kind="scalar"),
    "no_heads": dict(heads=()),
    "float64_inputs": dict(float64=True),
    "strided_edge_index": dict(strided=True),
}


def _case(name, seed=0):
    """``(samples, n_pad, e_pad, g_pad, heads, edge_dim)`` of a case."""
    spec = dict(CASES[name])
    rng = np.random.default_rng(seed)
    graphs = spec.pop("graphs", 5)
    pos_none = spec.pop("pos_none", ())
    zero_edge = spec.pop("zero_edge", ())
    full = spec.pop("full", False)
    heads = spec.setdefault("heads", (("graph", 1), ("node", 1)))
    edge_dim = spec.setdefault("edge_dim", 1)
    nodes = rng.integers(3, 9, graphs)
    edges = np.array([0 if g in zero_edge else 3 * n
                      for g, n in enumerate(nodes)])
    samples = [
        _sample(rng, int(n), int(e), with_pos=g not in pos_none, **spec)
        for g, (n, e) in enumerate(zip(nodes, edges))
    ]
    if full:
        n_pad, e_pad, g_pad = int(nodes.sum()) + 1, int(edges.sum()), graphs + 1
    else:
        n_pad, e_pad, g_pad = int(nodes.sum()) + 13, int(edges.sum()) + 21, 9
    return samples, n_pad, e_pad, g_pad, heads, edge_dim


def _larger(n_pad, e_pad, g_pad, heads, edge_dim, seed=7):
    """A batch that fills every row of every leaf of these pads: what a
    reused slot held before."""
    rng = np.random.default_rng(seed)
    graphs = g_pad - 1
    nodes = np.full(graphs, (n_pad - 1) // graphs)
    nodes[0] += (n_pad - 1) - nodes.sum()
    edges = np.full(graphs, e_pad // graphs)
    edges[0] += e_pad - edges.sum()
    return [
        _sample(rng, int(n), int(e), edge_dim=edge_dim, heads=heads)
        for n, e in zip(nodes, edges)
    ]


def _assert_bitwise(got, want):
    assert jax.tree_util.tree_structure(got) == (
        jax.tree_util.tree_structure(want))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()


# ---- bitwise the loop's -----------------------------------------------------


@pytest.mark.parametrize("mode", ["fresh", "reused_slot"])
@pytest.mark.parametrize("case", list(CASES))
def pytest_every_leaf_is_bitwise_the_loops(case, mode):
    samples, n_pad, e_pad, g_pad, heads, edge_dim = _case(case)
    kinds = tuple(k for k, _ in heads)
    dims = tuple(d for _, d in heads)
    want = _loop_collate(samples, n_pad, e_pad, g_pad, kinds, dims)
    slot = None
    if mode == "reused_slot":
        pool = SlotPool()
        slot = pool.acquire("k")
        big = _larger(n_pad, e_pad, g_pad, heads, edge_dim)
        held = collate_graphs(big, n_pad, e_pad, g_pad, kinds, dims,
                              slot=slot)
        _assert_bitwise(
            held, _loop_collate(big, n_pad, e_pad, g_pad, kinds, dims))
        held_ids = {id(a) for a in jax.tree_util.tree_leaves(held)}
        slot.release()
        slot = pool.acquire("k")
        assert slot.state == "reused"
    got = collate_graphs(samples, n_pad, e_pad, g_pad, kinds, dims,
                         slot=slot)
    _assert_bitwise(got, want)
    if mode == "reused_slot":  # the same memory, rewritten
        leaves = jax.tree_util.tree_leaves(got)
        assert {id(a) for a in leaves} <= held_ids
        assert pool.counts()["made"] == 1


def pytest_the_three_overflows_still_raise():
    samples, n_pad, e_pad, g_pad, _, _ = _case("exactly_full")
    with pytest.raises(ValueError, match=r"graphs exceeds g_pad-1="):
        collate_graphs(samples, n_pad, e_pad, g_pad - 1)
    with pytest.raises(ValueError, match=r"nodes exceed n_pad-1="):
        collate_graphs(samples, n_pad - 1, e_pad, g_pad)
    with pytest.raises(ValueError, match=r"edges exceed e_pad="):
        collate_graphs(samples, n_pad, e_pad - 1, g_pad)


# ---- a second pooled collate makes nothing ---------------------------------


@pytest.mark.parametrize("need_neighbors", [False, True])
def pytest_a_second_pooled_collate_makes_no_array(need_neighbors):
    samples = _case("edge_attr")[0] * 3
    layout = compute_layout([samples], batch_size=len(samples),
                            need_triplets=False,
                            need_neighbors=need_neighbors)
    pool = SlotPool()
    slot = pool.acquire("k")
    collate_for_layout(samples, layout, slot=slot)
    arrays = dict(slot._arrays)
    nbytes = pool.counts()["bytes"]
    assert {"edge_index", "edge_shift"} <= set(arrays)  # the scratch too
    slot.release()
    again = pool.acquire("k")
    got = collate_for_layout(samples[::-1], layout, slot=again)
    assert again is slot
    assert pool.counts()["made"] == 1 and pool.counts()["reused"] == 1
    assert slot._arrays.keys() == arrays.keys()
    assert all(slot._arrays[k] is a for k, a in arrays.items())
    assert pool.counts()["bytes"] == nbytes
    _assert_bitwise(got, collate_for_layout(samples[::-1], layout))
