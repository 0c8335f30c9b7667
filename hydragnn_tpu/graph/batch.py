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


def collate_graphs(
    samples,
    n_pad: int,
    e_pad: int,
    g_pad: int,
    head_types: Tuple[str, ...] = (),
    head_dims: Tuple[int, ...] = (),
    to_device: bool = False,
    slot=None,
):
    """Collate a list of ``GraphData``-like samples into one padded batch.

    Each sample must expose numpy arrays: ``x [n,F]``, ``pos [n,3]``,
    ``edge_index [2,e]``, optional ``edge_attr [e,De]``, and (if ``head_types``
    given) ``targets`` — a list with one array per head (graph head: ``[d]``,
    node head: ``[n, d]``).

    Runs on the host in numpy: this is the producer side of the input
    pipeline; the arrays are shipped to HBM once per step. ``slot``
    (``graph/slots.py``) gives the arrays to fill, each reset to exactly
    what a fresh one holds; without it they are allocated.
    """
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
    )
    if to_device:
        import jax

        batch = jax.tree_util.tree_map(jnp.asarray, batch)
    return batch
