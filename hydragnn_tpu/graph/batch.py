"""Statically-shaped padded graph batches.

The reference batches graphs with torch_geometric's ragged ``Batch`` — shapes
change every step, which is fine for eager CUDA but poison for XLA (every new
shape is a recompile). Here a batch is ONE static shape: node/edge/graph arrays
padded to fixed sizes, with a dedicated trailing *padding graph* that absorbs
all padding nodes and edges (so pooled/graph-level math needs no special
cases — the padding rows simply fall into graph ``G-1`` and are masked out).

This replaces the reference's variable-graph-size machinery
(``hydragnn/preprocess/utils.py:25-80`` detection + PyG dynamic batching) with
the TPU-idiomatic design: pad once, compile once.

Multi-task labels: the reference packs all heads into a flat ``data.y`` plus a
``y_loc`` index table (``hydragnn/preprocess/utils.py:237-278``) and re-slices
it every step (``train/train_validate_test.py:302-365``). We store one target
array per head instead — graph heads ``[G, dim]``, node heads ``[N, dim]`` —
which removes the index gymnastics from the hot loop entirely.
"""

from typing import Optional, Tuple

import numpy as np
import jax.numpy as jnp
from flax import struct

from hydragnn_tpu.graph.slots import filled


@struct.dataclass
class GraphBatch:
    """A padded multigraph batch (pytree; every field is a device array).

    Shapes: N = padded node count, E = padded edge count, G = padded graph
    count (always >= num real graphs + 1: the last slot is the padding graph).
    """

    x: jnp.ndarray  # [N, F] node input features
    pos: jnp.ndarray  # [N, 3] node positions
    senders: jnp.ndarray  # [E] int32, source node of each edge (j of j->i)
    receivers: jnp.ndarray  # [E] int32, target node of each edge
    edge_attr: Optional[jnp.ndarray]  # [E, De] or None
    node_graph: jnp.ndarray  # [N] int32, graph id of each node
    n_node: jnp.ndarray  # [G] int32
    n_edge: jnp.ndarray  # [G] int32
    node_mask: jnp.ndarray  # [N] bool, True on real nodes
    edge_mask: jnp.ndarray  # [E] bool
    graph_mask: jnp.ndarray  # [G] bool
    targets: Tuple[jnp.ndarray, ...] = ()  # per head: [G, d] or [N, d]
    # model-specific precomputed index arrays (e.g. DimeNet triplets),
    # padded to static budgets host-side
    extras: Optional[dict] = None

    @property
    def num_nodes(self) -> int:
        return self.x.shape[0]

    @property
    def num_edges(self) -> int:
        return self.senders.shape[0]

    @property
    def num_graphs(self) -> int:
        return self.n_node.shape[0]


def _round_up(value: int, multiple: int) -> int:
    return int(-(-value // multiple) * multiple)


def pad_sizes_for(
    max_nodes: int,
    max_edges: int,
    batch_size: int,
    node_multiple: int = 8,
    edge_multiple: int = 8,
    graph_multiple: int = 1,
) -> Tuple[int, int, int]:
    """Static pad sizes for a batch of up to ``batch_size`` graphs.

    Worst-case sizing (every graph maximal) plus one guaranteed padding node
    and one padding graph, rounded up so XLA tiles land on lane boundaries.
    ``graph_multiple``/``node_multiple`` should be divisible by the
    data-parallel axis size so sharded batches split evenly across devices.
    """
    n_pad = _round_up(batch_size * max_nodes + 1, node_multiple)
    e_pad = _round_up(max(batch_size * max_edges, 1), edge_multiple)
    g_pad = _round_up(batch_size + 1, graph_multiple)
    return n_pad, e_pad, g_pad


def pack_triplets(
    triplets, n_pad: int, t_pad: Optional[int] = None, slot=None
):
    """Pack per-sample DimeNet triplet tables into one padded extras dict.

    ``triplets``: list of ``(t_i, t_j, t_k, t_kj, t_ji, n_nodes, n_edges)``
    per sample, in batch order (node/edge offsets accumulate exactly as
    ``collate_graphs`` lays the samples out). Padded triplet slots point at
    the padding node ``n_pad - 1`` with mask False. ``t_pad`` defaults to
    the total rounded up to 8. The ONE canonical packer — the loader, the
    benches and the driver entry all route through here. ``slot``
    (``graph/slots.py``) gives the tables to fill, reset to what fresh ones
    hold; without it they are allocated.
    """
    total = sum(t[0].shape[0] for t in triplets)
    if t_pad is None:
        t_pad = _round_up(max(total, 1), 8)
    if total > t_pad:
        raise ValueError(f"{total} triplets exceed t_pad={t_pad}")
    ti = filled(slot, "trip_i", (t_pad,), np.int32, n_pad - 1)
    tj = filled(slot, "trip_j", (t_pad,), np.int32, n_pad - 1)
    tk = filled(slot, "trip_k", (t_pad,), np.int32, n_pad - 1)
    tkj = filled(slot, "trip_kj", (t_pad,), np.int32)
    tji = filled(slot, "trip_ji", (t_pad,), np.int32)
    tmask = filled(slot, "trip_mask", (t_pad,), bool)
    off_n = off_e = off_t = 0
    for a, b, c, kj, ji, n_nodes, n_edges in triplets:
        t = a.shape[0]
        ti[off_t : off_t + t] = a + off_n
        tj[off_t : off_t + t] = b + off_n
        tk[off_t : off_t + t] = c + off_n
        tkj[off_t : off_t + t] = kj + off_e
        tji[off_t : off_t + t] = ji + off_e
        tmask[off_t : off_t + t] = True
        off_t += t
        off_n += int(n_nodes)
        off_e += int(n_edges)
    return {
        "trip_i": ti,
        "trip_j": tj,
        "trip_k": tk,
        "trip_kj": tkj,
        "trip_ji": tji,
        "trip_mask": tmask,
    }


def stack_batches(batches):
    """Stack K same-shape collated batches along a new leading axis.

    Producer-side counterpart of the trainer's scan-based multi-step
    dispatch: one host->device transfer and ONE XLA dispatch then run K
    optimizer steps on device (``lax.scan``), amortizing per-step dispatch
    latency — the TPU answer to the reference's per-batch eager hot loop
    (``train/train_validate_test.py:463-520``), where each step pays full
    Python + launch overhead.
    """
    import jax

    return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *batches)


def stack_into(stacked, batch, index, count, slot=None):
    """:func:`stack_batches` one batch at a time: lay ``batch`` into row
    ``index`` of ``stacked`` (``None`` on the first call, which makes it,
    out of ``slot``'s arrays where one is given) and return it. After
    ``count`` calls it equals ``stack_batches`` of the batches, and the
    copying was done while the later ones were still being collated
    (``Trainer._group_plan``)."""
    import jax

    if stacked is None:
        leaves, treedef = jax.tree_util.tree_flatten(batch)
        stacked = treedef.unflatten([
            filled(
                slot, f"stack/{i}", (count,) + np.shape(x),
                np.asarray(x).dtype, None,
            )
            for i, x in enumerate(leaves)
        ])
    jax.tree_util.tree_map(
        lambda out, x: out.__setitem__(index, x), stacked, batch
    )
    return stacked


def _concat(parts, out, axis=0):
    """``parts`` laid end to end into ``out``, each cast as an assignment
    would (``out[a:b] = part``): one numpy call for the whole batch."""
    np.concatenate(parts, axis=axis, out=out, casting="unsafe")


def _repeat_into(out, values, starts, counts):
    """``out[:] = np.repeat(values, counts)`` with no array of ``out``'s
    size made: each run's first row gets its value, a running maximum
    carries it down the run. ``values`` must not decrease (row offsets,
    graph ids); ``starts`` are the runs' first rows."""
    out.fill(0)
    runs = counts > 0  # an empty run has no row (and may start at the end)
    out[starts[runs]] = values[runs]
    np.maximum.accumulate(out, out=out)


def collate_graphs(
    samples,
    n_pad: int,
    e_pad: int,
    g_pad: int,
    head_types: Tuple[str, ...] = (),
    head_dims: Tuple[int, ...] = (),
    to_device: bool = False,
    slot=None,
    offsets: bool = False,
):
    """Collate a list of ``GraphData``-like samples into one padded batch.

    Each sample must expose numpy arrays: ``x [n,F]``, ``pos [n,3]``,
    ``edge_index [2,e]``, optional ``edge_attr [e,De]``, and (if ``head_types``
    given) ``targets`` — a list with one array per head (graph head: ``[d]``,
    node head: ``[n, d]``).

    Runs on the host in numpy: this is the producer side of the input
    pipeline; the arrays are shipped to HBM once per step. Each leaf is
    one pass over the batch (a concatenation of the samples' arrays, a
    slice write), not one per sample. ``slot`` (``graph/slots.py``) gives
    the arrays to fill and the ``[2, E]`` / ``[E]`` scratch: each leaf's
    head is written once and only its padding tail reset, so it holds
    exactly what a fresh one would; without it they are allocated.

    ``offsets`` adds ``extras["edge_offset"]`` ``[E, 3]`` float32: each
    edge's periodic image, the samples' own ``extras["edge_offset"]``
    (``data/radius_graph.py radius_graph_pbc``), zero on padding and for a
    sample that has none (a non-periodic graph). Only the stacks that read
    positions ask for it (``BatchLayout.need_offsets``).
    """
    G = len(samples)
    # per-sample counts, read once; every leaf below is laid out from them
    # with a fixed number of numpy calls, whatever the batch's size
    nodes = np.fromiter((s.x.shape[0] for s in samples), np.int64, G)
    edges = np.fromiter((s.edge_index.shape[1] for s in samples), np.int64, G)
    N, E = int(nodes.sum()), int(edges.sum())
    if G > g_pad - 1:
        raise ValueError(f"batch of {G} graphs exceeds g_pad-1={g_pad - 1}")
    if N > n_pad - 1:
        raise ValueError(f"{N} nodes exceed n_pad-1={n_pad - 1}")
    if E > e_pad:
        raise ValueError(f"{E} edges exceed e_pad={e_pad}")
    node_off = np.cumsum(nodes) - nodes  # first node row of each sample
    edge_off = np.cumsum(edges) - edges

    # each leaf: its head [:N] / [:E] / [:G] written once below, its tail
    # reset to what a fresh array holds there
    feat_dim = samples[0].x.shape[1]
    x = filled(slot, "x", (n_pad, feat_dim), np.float32, start=N)
    _concat([s.x for s in samples], x[:N])

    pos = filled(slot, "pos", (n_pad, 3), np.float32, start=N)
    parts = [s.pos for s in samples]
    if any(p is None for p in parts):  # a sample without positions: zeros
        blank = np.zeros((int(nodes.max()), 3), np.float32)
        parts = [blank[:n] if p is None else p for p, n in zip(parts, nodes)]
    _concat(parts, pos[:N])

    # padding edges point at the last node slot (always a padding node since
    # N <= n_pad - 1) and live in the padding graph. Real edges: each
    # sample's local indices (its [2, e] whole: numpy gives the GIL up for
    # every piece over 500 items, so one piece a sample, not one a row),
    # then + its first node row, the node offsets repeated by the edge counts
    local = filled(slot, "edge_index", (2, e_pad), np.int32, None)[:, :E]
    _concat([s.edge_index for s in samples], local, axis=1)
    shift = filled(slot, "edge_shift", (e_pad,), np.int32, None)[:E]
    _repeat_into(shift, node_off, edge_off, edges)
    senders = filled(slot, "senders", (e_pad,), np.int32, n_pad - 1, start=E)
    np.add(local[0], shift, out=senders[:E])
    receivers = filled(
        slot, "receivers", (e_pad,), np.int32, n_pad - 1, start=E
    )
    np.add(local[1], shift, out=receivers[:E])
    edge_dim = None
    if samples[0].edge_attr is not None:
        edge_dim = samples[0].edge_attr.shape[1]
        edge_attr = filled(
            slot, "edge_attr", (e_pad, edge_dim), np.float32, start=E
        )
        _concat([s.edge_attr for s in samples], edge_attr[:E])
    extras = None
    if offsets:
        edge_offset = filled(
            slot, "edge_offset", (e_pad, 3), np.float32, start=E
        )
        parts = [getattr(s, "extras", {}).get("edge_offset") for s in samples]
        if any(p is None for p in parts):  # a sample without images: zeros
            blank = np.zeros((int(edges.max()), 3), np.float32)
            parts = [blank[:e] if p is None else p
                     for p, e in zip(parts, edges)]
        _concat(parts, edge_offset[:E])
        extras = {"edge_offset": edge_offset}

    node_graph = filled(
        slot, "node_graph", (n_pad,), np.int32, g_pad - 1, start=N
    )
    _repeat_into(node_graph[:N], np.arange(G), node_off, nodes)
    n_node = filled(slot, "n_node", (g_pad,), np.int32, start=G)
    n_node[:G] = nodes
    n_edge = filled(slot, "n_edge", (g_pad,), np.int32, start=G)
    n_edge[:G] = edges
    # padding nodes all sit in the padding graph; record its node count so
    # segment means over the padding graph stay well-defined.
    n_node[g_pad - 1] = n_pad - N
    n_edge[g_pad - 1] = e_pad - E
    node_mask = filled(slot, "node_mask", (n_pad,), bool, start=N)
    node_mask[:N] = True
    edge_mask = filled(slot, "edge_mask", (e_pad,), bool, start=E)
    edge_mask[:E] = True
    graph_mask = filled(slot, "graph_mask", (g_pad,), bool, start=G)
    graph_mask[:G] = True

    # a head's targets, arrays of any dtype or lists: a graph head's [d]
    # rows (or scalars) flattened in sample order, a node head's [n, d]
    # blocks stacked (flattening them would copy each with the GIL given up)
    targets = []
    for ih, (t, d) in enumerate(zip(head_types, head_dims)):
        parts = [s.targets[ih] for s in samples]
        if t == "graph":
            tgt = filled(slot, f"target{ih}", (g_pad, d), np.float32, start=G)
            _concat(parts, tgt.reshape(-1)[: G * d], axis=None)
        else:
            tgt = filled(slot, f"target{ih}", (n_pad, d), np.float32, start=N)
            _concat(parts, tgt[:N])
        targets.append(tgt)

    batch = GraphBatch(
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
        extras=extras,
    )
    if to_device:
        import jax

        batch = jax.tree_util.tree_map(jnp.asarray, batch)
    return batch
