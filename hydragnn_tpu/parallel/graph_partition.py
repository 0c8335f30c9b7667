"""Graph-partition parallelism — the long-context analog for GNNs.

The reference cannot split one graph across devices at all: its scaling axis
is data parallelism over many small graphs (DDP, ``utils/distributed.py``),
and "large" means *many samples* (DDStore/ADIOS streaming). The TPU-native
framework goes further: ONE giant graph (a large atomistic system, a mesh, a
polymer) is sharded node-wise over a mesh axis, the exact structural analog of
sequence/context parallelism for transformers (ring attention's KV exchange
becomes halo exchange of remote-sender node features; SURVEY.md §5 names
static-shape bucketing as the in-domain replacement — this module is the
scale-out half of that story).

Design:

* **Ownership** — nodes are split into ``P`` contiguous shards after a
  locality-preserving reorder (Morton/Z-curve over positions, so radius-graph
  neighbors tend to share a shard and the halo stays small). Every directed
  edge is owned by its *receiver's* shard, so all receiver-side aggregations
  (the message-passing hot path) are shard-local segment ops. On the 2-D
  ``("data", "model")`` mesh (``parallel/mesh.py``) ownership lives on the
  ``model`` axis: each model group holds one graph's shards, and the batch
  placement + in-program ``with_sharding_constraint`` on the node table,
  edge features and halo buffers let XLA place the all_to_all/psum
  collectives against that layout instead of replicating.
* **Halo exchange** (``halo_extend``) — before every conv layer, each shard
  gathers the rows remote peers need (a host-precomputed, statically padded
  send list) and trades them with ONE ``lax.all_to_all`` over ICI. Convs run
  unmodified on the extended table ``[local ; halo]``; the local slice is
  kept. Autodiff through the collective yields the reverse scatter-add —
  gradients flow across shards with no hand-written backward.
* **Halo reduce** (``halo_reduce``) — the transpose operation, for the two
  stacks that aggregate at *senders* (EGNN / equivariant SchNet coordinate
  updates): partial sums landing on halo rows are all_to_all'd back to their
  owner shard and scatter-added into the local rows.
* **Exact numerics** — BatchNorm statistics, global pooling and every loss
  numerator/denominator are ``psum``'d over the axis (``models/common.py``,
  ``models/base.py``), so a partitioned model computes bit-for-bit the same
  math as the unpartitioned one; the tests assert output/gradient parity.

No counterpart exists in the reference (capability superset); the closest
public pattern is jraph's sharded_graphnet / DGL's DistDGL halo design.
"""

import math
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from hydragnn_tpu.graph.batch import GraphBatch
from hydragnn_tpu.parallel.mesh import GRAPH_AXIS


# --------------------------------------------------------------------------
# device-side collectives (called inside shard_map / the model)
# --------------------------------------------------------------------------


def halo_extend(x, halo_send, axis_name):
    """Extend the local node table with fresh halo rows from peer shards.

    ``x``: ``[NL, ...]`` local rows. ``halo_send``: ``[P, H]`` int32 — row ids
    this shard must send to each peer (padded entries point at the dummy
    row). Returns ``[NL + P*H, ...]``: local rows, then peer ``p``'s rows at
    ``NL + p*H + h`` — the layout the partitioner's remapped sender indices
    reference.
    """
    sends = x[halo_send]  # [P, H, ...]
    recv = jax.lax.all_to_all(sends, axis_name, split_axis=0, concat_axis=0)
    return jnp.concatenate([x, recv.reshape((-1,) + x.shape[1:])], axis=0)


def halo_reduce(y_ext, halo_send, axis_name):
    """Fold sender-side partial aggregations back onto their owner shards.

    ``y_ext``: ``[NL + P*H, ...]`` — a segment reduction over the extended
    table where rows ``NL + p*H + h`` hold partial sums belonging to peer
    ``p``'s node ``halo_send[p, h]`` (as seen on peer ``p``). Sends each halo
    block to its owner and scatter-adds into the local rows. Returns
    ``[NL + P*H, ...]`` with complete local rows and a zeroed halo region.
    """
    p, h = halo_send.shape
    nl = y_ext.shape[0] - p * h
    local = y_ext[:nl]
    halo = y_ext[nl:].reshape((p, h) + y_ext.shape[1:])
    back = jax.lax.all_to_all(halo, axis_name, split_axis=0, concat_axis=0)
    local = local.at[halo_send.reshape(-1)].add(
        back.reshape((p * h,) + y_ext.shape[1:])
    )
    return jnp.concatenate([local, jnp.zeros_like(y_ext[nl:])], axis=0)


# --------------------------------------------------------------------------
# host-side partitioner
# --------------------------------------------------------------------------


def _morton_order(pos: np.ndarray) -> np.ndarray:
    """Z-curve ordering of 3-D positions — cheap locality-preserving reorder
    so contiguous node chunks are spatially compact (small halo cut)."""
    q = pos - pos.min(axis=0, keepdims=True)
    denom = np.maximum(q.max(axis=0, keepdims=True), 1e-12)
    bits = 10
    cells = np.minimum((q / denom * ((1 << bits) - 1)).astype(np.uint64), (1 << bits) - 1)

    def spread(v):
        v = v & np.uint64(0x3FF)
        v = (v | (v << np.uint64(16))) & np.uint64(0x030000FF)
        v = (v | (v << np.uint64(8))) & np.uint64(0x0300F00F)
        v = (v | (v << np.uint64(4))) & np.uint64(0x030C30C3)
        v = (v | (v << np.uint64(2))) & np.uint64(0x09249249)
        return v

    code = spread(cells[:, 0]) | (spread(cells[:, 1]) << np.uint64(1)) | (
        spread(cells[:, 2]) << np.uint64(2)
    )
    return np.argsort(code, kind="stable")


class _HaloTable:
    """Vectorized halo bookkeeping for one item kind (nodes or edges).

    Built from (consumer part, global item id) request pairs; deduplicates,
    assigns dense per-(owner, consumer) slots, and produces the ``[P, P, H]``
    send table plus a vectorized ``extended_ids`` lookup — no Python loops
    over items, so partitioning stays O(sort) for giant graphs.
    """

    def __init__(self, req_q, req_item, part_of, local_of, P, multiple, dummy,
                 min_h: int = 0):
        num_items = part_of.shape[0]
        req_q = np.asarray(req_q, np.int64)
        req_item = np.asarray(req_item, np.int64)
        owner = part_of[req_item]
        remote = owner != req_q
        key = req_q[remote] * num_items + req_item[remote]
        uniq = np.unique(key)  # sorted
        uq = uniq // num_items
        uitem = uniq % num_items
        up = part_of[uitem]
        # dense slot index within each (owner p, consumer q) group
        order = np.lexsort((uitem, uq, up))
        sp, sq = up[order], uq[order]
        change = np.r_[True, (sp[1:] != sp[:-1]) | (sq[1:] != sq[:-1])]
        group_id = np.cumsum(change) - 1
        group_start = np.nonzero(change)[0]
        slot_sorted = np.arange(order.shape[0]) - group_start[group_id]
        counts = np.bincount(group_id) if order.shape[0] else np.zeros(1, np.int64)
        natural = max(int(counts.max()) if order.shape[0] else 0, 1)
        self.h = max(int(-(-natural // multiple) * multiple), int(min_h))
        self.send = np.full((P, P, self.h), dummy, np.int32)
        self.send[sp, sq, slot_sorted] = local_of[uitem[order]].astype(np.int32)
        self._uniq = uniq
        self._slot = np.empty(uniq.shape[0], np.int64)
        self._slot[order] = slot_sorted
        self._num_items = num_items
        self._part_of = part_of
        self._local_of = local_of

    def extended_ids(self, q, items, base: int) -> np.ndarray:
        """Remap global item ids to consumer-local extended coordinates:
        local id when owned by ``q``, else ``base + owner*H + slot``."""
        q = np.asarray(q, np.int64)
        items = np.asarray(items, np.int64)
        owner = self._part_of[items]
        out = self._local_of[items].astype(np.int64)
        remote = owner != q
        if remote.any():
            key = q[remote] * self._num_items + items[remote]
            idx = np.searchsorted(self._uniq, key)
            out[remote] = base + owner[remote] * self.h + self._slot[idx]
        return out.astype(np.int32)


class PartitionInfo:
    """Static partition geometry + the inverse maps to un-partition outputs."""

    def __init__(self, num_parts, nl, el, halo, node_perm, part_of_node,
                 local_of_node, n_real, halo_edges=0, tl=0, k_in=0, k_out=0):
        self.num_parts = num_parts
        self.nl = nl  # local node budget (incl. 1 dummy row)
        self.el = el  # local edge budget
        self.halo = halo  # per-peer halo budget H
        self.node_perm = node_perm  # [n] global node id -> (implicit) order
        self.part_of_node = part_of_node  # [n] owning shard per global node
        self.local_of_node = local_of_node  # [n] local row per global node
        self.n_real = n_real
        self.halo_edges = halo_edges  # per-peer EDGE halo budget (triplets)
        self.tl = tl  # local triplet budget
        self.k_in = k_in  # dense neighbor-list widths (0 = lists not built)
        self.k_out = k_out

    @property
    def budgets(self) -> dict:
        return {
            "nl": self.nl,
            "el": self.el,
            "halo": self.halo,
            "halo_edges": self.halo_edges,
            "tl": self.tl,
            "k_in": self.k_in,
            "k_out": self.k_out,
        }

    def gather_nodes(self, per_part_rows: np.ndarray) -> np.ndarray:
        """``[P*NL, ...]`` stacked per-part rows -> ``[n, ...]`` in the
        original global node order (drops dummy/halo padding)."""
        flat_idx = self.part_of_node * self.nl + self.local_of_node
        return np.asarray(per_part_rows)[flat_idx]


def partition_graph(
    sample,
    num_parts: int,
    head_types: Tuple[str, ...] = (),
    head_dims: Tuple[int, ...] = (),
    order: str = "morton",
    node_multiple: int = 8,
    edge_multiple: int = 8,
    halo_multiple: int = 8,
    need_triplets: bool = False,
    need_neighbors: bool = False,
    budgets: Optional[dict] = None,
    need_offsets: bool = False,
) -> Tuple[GraphBatch, PartitionInfo]:
    """Split one giant graph into ``num_parts`` static-shape shards.

    ``sample`` exposes numpy ``x [n,F]``, ``pos [n,3]``, ``edge_index [2,e]``,
    optional ``edge_attr``, and (per ``head_types``) ``targets``;
    ``need_offsets`` carries its ``extras["edge_offset"]`` ``[e, 3]`` (each
    edge's periodic image, zero where it has none) with its edges. Returns a
    ``GraphBatch`` whose leading axes concatenate the per-part arrays (part
    ``p`` owns rows ``[p*NL, (p+1)*NL)`` etc.) — sharding every leaf on axis 0
    over a ``num_parts``-sized mesh axis gives each device exactly its shard.

    Per-shard layout: rows ``[0, NL-1)`` local nodes (dummy at ``NL-1``);
    edges are owned by the receiver's shard; remapped sender ids >= NL
    reference the halo region filled by ``halo_extend`` at run time. The
    local graph id 0 is the real graph (``n_node[0]`` = GLOBAL real node
    count, see ``HydraBase.__call__``), id 1 absorbs padding.
    """
    x = np.asarray(sample.x, dtype=np.float32)
    pos = (
        np.asarray(sample.pos, dtype=np.float32)
        if getattr(sample, "pos", None) is not None
        else np.zeros((x.shape[0], 3), np.float32)
    )
    edge_index = np.asarray(sample.edge_index)
    edge_attr = getattr(sample, "edge_attr", None)
    if edge_attr is not None:
        edge_attr = np.asarray(edge_attr, dtype=np.float32)
    n = x.shape[0]
    e = edge_index.shape[1]
    P = int(num_parts)

    if order == "morton" and pos is not None:
        perm = _morton_order(pos)
    else:
        perm = np.arange(n)

    # contiguous chunks of the ordering -> parts
    part_sizes = [(n + P - 1 - p) // P for p in range(P)]  # near-even
    part_of_node = np.empty(n, dtype=np.int64)
    local_of_node = np.empty(n, dtype=np.int64)
    start = 0
    for p, sz in enumerate(part_sizes):
        ids = perm[start : start + sz]
        part_of_node[ids] = p
        local_of_node[ids] = np.arange(sz)
        start += sz

    def _round_up(v, m):
        return int(-(-v // m) * m)

    budgets = budgets or {}
    nl = max(_round_up(max(part_sizes) + 1, node_multiple), budgets.get("nl", 0))

    # edge ownership by receiver
    send_g, recv_g = edge_index[0], edge_index[1]
    e_part = part_of_node[recv_g]
    e_counts = np.bincount(e_part, minlength=P)
    el = max(
        _round_up(max(int(e_counts.max()), 1), edge_multiple),
        budgets.get("el", 0),
    )

    # local edge row of every global edge (receiver-owner layout; matches
    # the ascending-nonzero order of the edge build loop below)
    local_of_edge = np.empty(max(e, 1), dtype=np.int64)
    for p in range(P):
        eidx = np.nonzero(e_part == p)[0]
        local_of_edge[eidx] = np.arange(eidx.shape[0])

    # halo: for each (owner p -> consumer q) the unique remote NODES the
    # consumer needs — remote senders of its edges plus (DimeNet) remote
    # j/k nodes of its triplets (the 2-hop halo)
    node_req_q = [e_part]
    node_req_item = [send_g]
    trip = None
    if need_triplets:
        from hydragnn_tpu.models.dimenet import compute_triplets

        t_i, t_j, t_k, t_kj, t_ji = compute_triplets(edge_index, n)
        t_part = e_part[t_ji]  # triplet lives with its (j->i) edge
        node_req_q += [t_part, t_part]
        node_req_item += [t_j, t_k]
        trip = (t_i, t_j, t_k, t_kj, t_ji, t_part)

    node_halo = _HaloTable(
        np.concatenate(node_req_q),
        np.concatenate(node_req_item),
        part_of_node,
        local_of_node,
        P,
        halo_multiple,
        dummy=nl - 1,
        min_h=budgets.get("halo", 0),
    )
    halo = node_halo.h

    edge_halo = None
    if need_triplets:
        # remote (k->j) edges whose STATE the consumer gathers (x_kj)
        edge_halo = _HaloTable(
            trip[5], trip[3], e_part, local_of_edge, P, halo_multiple, dummy=0,
            min_h=budgets.get("halo_edges", 0),
        )

    # ---- per-part arrays -------------------------------------------------
    F = x.shape[1]
    xs = np.zeros((P, nl, F), np.float32)
    ps = np.zeros((P, nl, 3), np.float32)
    node_graph = np.full((P, nl), 1, np.int32)
    node_mask = np.zeros((P, nl), bool)
    n_node = np.zeros((P, 2), np.int32)
    n_edge = np.zeros((P, 2), np.int32)
    graph_mask = np.zeros((P, 2), bool)
    senders = np.full((P, el), nl - 1, np.int32)
    receivers = np.full((P, el), nl - 1, np.int32)
    edge_mask = np.zeros((P, el), bool)
    e_attr = (
        np.zeros((P, el, edge_attr.shape[1]), np.float32)
        if edge_attr is not None
        else None
    )
    e_off = np.zeros((P, el, 3), np.float32) if need_offsets else None
    offset = (getattr(sample, "extras", None) or {}).get("edge_offset")
    # padded slots point at the dummy row so halo_reduce's scatter-add and
    # halo_extend's sends never touch a real node
    halo_send = node_halo.send
    nig = np.zeros((P, nl), np.int32)  # node_index_in_graph (global position)

    for p in range(P):
        ids = np.nonzero(part_of_node == p)[0]
        order_ids = ids[np.argsort(local_of_node[ids])]
        sz = order_ids.shape[0]
        xs[p, :sz] = x[order_ids]
        ps[p, :sz] = pos[order_ids]
        node_graph[p, :sz] = 0
        node_mask[p, :sz] = True
        nig[p, :sz] = order_ids
        n_node[p, 0] = n  # GLOBAL count: local pool sums psum to the true mean
        n_node[p, 1] = nl - sz
        graph_mask[p, 0] = True

    for p in range(P):
        eidx = np.nonzero(e_part == p)[0]
        k = eidx.shape[0]
        senders[p, :k] = node_halo.extended_ids(
            np.full(k, p, np.int64), send_g[eidx], base=nl
        )
        receivers[p, :k] = local_of_node[recv_g[eidx]].astype(np.int32)
        edge_mask[p, :k] = True
        n_edge[p, 0] = k
        n_edge[p, 1] = el - k
        if e_attr is not None:
            e_attr[p, :k] = edge_attr[eidx]
        if e_off is not None and offset is not None:
            e_off[p, :k] = offset[eidx]

    # ---- triplet arrays (DimeNet), fully vectorized ---------------------
    trip_extras = {}
    if trip is not None:
        t_i, t_j, t_k, t_kj, t_ji, t_part = trip
        t_counts = np.bincount(t_part, minlength=P)
        tl = max(_round_up(max(int(t_counts.max()), 1), 8), budgets.get("tl", 0))
        tr_i = np.full((P, tl), nl - 1, np.int32)
        tr_j = np.full((P, tl), nl - 1, np.int32)
        tr_k = np.full((P, tl), nl - 1, np.int32)
        tr_kj = np.zeros((P, tl), np.int32)
        tr_ji = np.zeros((P, tl), np.int32)
        tr_mask = np.zeros((P, tl), bool)
        # dense row within each part: rank of each triplet in a stable
        # part-ordered sort
        order_t = np.argsort(t_part, kind="stable")
        starts = np.concatenate([[0], np.cumsum(t_counts)[:-1]])
        rows = np.arange(order_t.shape[0]) - starts[t_part[order_t]]
        qs = t_part[order_t]
        tr_i[qs, rows] = local_of_node[t_i[order_t]].astype(np.int32)
        tr_j[qs, rows] = node_halo.extended_ids(qs, t_j[order_t], base=nl)
        tr_k[qs, rows] = node_halo.extended_ids(qs, t_k[order_t], base=nl)
        tr_kj[qs, rows] = edge_halo.extended_ids(qs, t_kj[order_t], base=el)
        tr_ji[qs, rows] = local_of_edge[t_ji[order_t]].astype(np.int32)
        tr_mask[qs, rows] = True
        trip_extras = {
            "trip_i": tr_i,
            "trip_j": tr_j,
            "trip_k": tr_k,
            "trip_kj": tr_kj,
            "trip_ji": tr_ji,
            "trip_mask": tr_mask,
            "halo_send_edges": edge_halo.send.reshape(P * P, edge_halo.h),
        }

    # ---- dense neighbor lists (scatter-free aggregation) -----------------
    # Built against each shard's EXTENDED node table (local rows + halo
    # region), so the conv's dense path gathers halo senders exactly like
    # the segment path does; gradients reach halo rows through the custom
    # VJP's reverse lists and flow back to owners via halo_extend's AD.
    nbr_extras = {}
    if need_neighbors:
        from hydragnn_tpu.ops.dense_agg import (
            build_neighbor_lists,
            max_degree,
        )

        ext_n = nl + P * halo
        k_in = budgets.get("k_in", 1)
        k_out = budgets.get("k_out", 1)
        for p in range(P):
            ki, ko = max_degree(senders[p], receivers[p], edge_mask[p])
            k_in, k_out = max(k_in, ki), max(k_out, ko)
        stacked = None
        for p in range(P):
            lists = build_neighbor_lists(
                senders[p], receivers[p], edge_mask[p], ext_n, k_in, k_out
            )
            if stacked is None:
                stacked = {
                    k: np.zeros((P,) + v.shape, v.dtype)
                    for k, v in lists.items()
                }
            for k, v in lists.items():
                stacked[k][p] = v
        nbr_extras = stacked

    # ---- targets ---------------------------------------------------------
    targets = []
    for ih, (t, d) in enumerate(zip(head_types, head_dims)):
        tgt = np.asarray(sample.targets[ih], np.float32)
        if t == "graph":
            arr = np.zeros((P, 2, d), np.float32)
            arr[:, 0] = tgt.reshape(-1)
        else:
            arr = np.zeros((P, nl, d), np.float32)
            for p in range(P):
                ids = np.nonzero(part_of_node == p)[0]
                order_ids = ids[np.argsort(local_of_node[ids])]
                arr[p, : order_ids.shape[0]] = tgt[order_ids].reshape(-1, d)
        targets.append(arr)

    def flat(a):
        return a.reshape((-1,) + a.shape[2:])

    batch = GraphBatch(
        x=flat(xs),
        pos=flat(ps),
        senders=flat(senders),
        receivers=flat(receivers),
        edge_attr=flat(e_attr) if e_attr is not None else None,
        node_graph=flat(node_graph),
        n_node=flat(n_node),
        n_edge=flat(n_edge),
        node_mask=flat(node_mask),
        edge_mask=flat(edge_mask),
        graph_mask=flat(graph_mask),
        targets=tuple(flat(t) for t in targets),
        extras={
            "halo_send": halo_send.reshape(P * P, halo),
            "node_index_in_graph": flat(nig),
            # triplet index tables are [P, TL] -> flattened like every other
            # leaf; halo_send_edges is already [P*P, HE]
            **{
                k: (v if k == "halo_send_edges" else flat(v))
                for k, v in trip_extras.items()
            },
            **{k: flat(v) for k, v in nbr_extras.items()},
            **({"edge_offset": flat(e_off)} if e_off is not None else {}),
        },
    )
    info = PartitionInfo(
        P, nl, el, halo, perm, part_of_node, local_of_node, n,
        halo_edges=edge_halo.h if edge_halo is not None else 0,
        tl=trip_extras["trip_i"].shape[1] if trip_extras else 0,
        k_in=nbr_extras["nbr_idx"].shape[2] if nbr_extras else 0,
        k_out=nbr_extras["rev_idx"].shape[2] if nbr_extras else 0,
    )
    return batch, info


# --------------------------------------------------------------------------
# shard_map step builders
# --------------------------------------------------------------------------


def _batch_spec(batch, axis):
    from jax.sharding import PartitionSpec as P

    return jax.tree_util.tree_map(lambda _: P(axis), batch)


def _constrain_partitioned(batch, mesh, axis):
    """Pin the partitioned batch's placement INSIDE the jitted program:
    ``with_sharding_constraint`` on every leading-axis-stacked leaf — the
    node table (``x``/``pos``), the edge features/indices, and the halo
    send tables — so XLA places the shard_map's all_to_all/psum
    collectives against the declared layout instead of replicating first
    and resharding at the shard_map boundary. On the 2-D mesh the
    partition axis is ``model``; unmentioned axes (``data``) replicate."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P(axis))
    return jax.tree_util.tree_map(
        lambda a: jax.lax.with_sharding_constraint(a, sharding), batch
    )


def _put_global(a, sharding):
    """Place an array (present in full on every process) under a global
    sharding. device_put cannot target non-addressable devices, so on
    multi-host each process contributes its addressable shards via
    make_array_from_callback."""
    if jax.process_count() == 1:
        return jax.device_put(jnp.asarray(a), sharding)
    a = np.asarray(a)
    return jax.make_array_from_callback(a.shape, sharding, lambda idx: a[idx])


def put_partitioned_batch(batch: GraphBatch, mesh, axis: str = GRAPH_AXIS) -> GraphBatch:
    """Device placement: every leaf sharded on axis 0 so each device holds
    exactly its shard's rows (multi-host safe)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P(axis))
    return jax.tree_util.tree_map(lambda a: _put_global(a, sharding), batch)


def put_partitioned_state(state, mesh):
    """Replicate the train state onto the mesh with the SAME sharding the
    partitioned step's outputs carry (``NamedSharding(mesh, P())``).

    Skipping this costs one full extra XLA compile: the first step returns
    P()-annotated arrays, and feeding those back into a jit that was traced
    for differently-annotated inputs is a sharding-signature cache miss
    (measured ~5 s duplicate compile on v5e). Multi-host safe (values are
    identical on every process, e.g. seeded init).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P())
    if jax.process_count() == 1:
        return jax.device_put(state, sharding)
    return jax.tree_util.tree_map(
        lambda a: _put_global(jax.device_get(a), sharding), state
    )


def make_partitioned_apply(model, mesh, axis: str = GRAPH_AXIS):
    """Jitted partitioned forward: (variables, batch) -> per-shard outputs.

    Graph-head rows come back replicated-identical on every shard; node-head
    rows are per-shard (un-partition with ``PartitionInfo.gather_nodes``).
    """
    from jax.sharding import PartitionSpec as P

    def fwd(variables, batch):
        batch = _constrain_partitioned(batch, mesh, axis)

        def shard_fn(variables, batch):
            return model.apply(variables, batch, train=False)

        return jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(P(), _batch_spec(batch, axis)),
            out_specs=P(axis),
            check_vma=False,
        )(variables, batch)

    return jax.jit(fwd)


def make_partitioned_train_step(model, tx, mesh, axis: str = GRAPH_AXIS):
    """One fused XLA program: partitioned forward + psum'd loss + backward
    (all_to_all transposes inserted by AD) + grad psum + optimizer update.

    The differentiated objective is the per-shard share ``loss / P`` — with
    ``check_vma=False`` every collective transposes to its true adjoint, so
    ``psum`` of the per-shard grads reconstructs the exact global gradient
    (asserted against the single-device model in
    ``tests/test_graph_partition.py``).
    """
    from jax.sharding import PartitionSpec as P

    axis_size = int(mesh.shape[axis])

    def step(state, batch, rng):
        batch = _constrain_partitioned(batch, mesh, axis)

        def shard_fn(params, batch_stats, opt_state, step_no, batch, rng):
            # decorrelate dropout masks across shards (rng enters replicated)
            rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))

            def loss_fn(p):
                variables = {"params": p}
                if batch_stats:
                    variables["batch_stats"] = batch_stats
                    outputs, mut = model.apply(
                        variables,
                        batch,
                        train=True,
                        mutable=["batch_stats"],
                        rngs={"dropout": rng},
                    )
                    new_bs = mut["batch_stats"]
                else:
                    outputs = model.apply(
                        variables, batch, train=True, rngs={"dropout": rng}
                    )
                    new_bs = batch_stats
                tot, tasks = model.loss(outputs, batch)
                return tot / axis_size, (tuple(tasks), new_bs, tot)

            (_, (tasks, new_bs, tot)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params)
            grads = jax.lax.psum(grads, axis)
            updates, new_opt = tx.update(grads, opt_state, params)
            import optax

            new_params = optax.apply_updates(params, updates)
            metrics = {
                "loss": tot,
                "tasks": jnp.stack(tasks) if tasks else jnp.zeros((0,)),
            }
            return new_params, new_bs, new_opt, step_no + 1, metrics

        new_params, new_bs, new_opt, step_no, metrics = jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(
                P(),
                P(),
                P(),
                P(),
                _batch_spec(batch, axis),
                P(),
            ),
            out_specs=(P(), P(), P(), P(), P()),
            check_vma=False,
        )(state.params, state.batch_stats, state.opt_state, state.step, batch, rng)
        return (
            state.replace(
                params=new_params,
                batch_stats=new_bs,
                opt_state=new_opt,
                step=step_no,
            ),
            metrics,
        )

    return jax.jit(step, donate_argnums=(0,))


def make_partitioned_eval_step(model, mesh, axis: str = GRAPH_AXIS):
    from jax.sharding import PartitionSpec as P

    def eval_step(params, batch_stats, batch):
        batch = _constrain_partitioned(batch, mesh, axis)

        def shard_fn(params, batch_stats, batch):
            variables = {"params": params}
            if batch_stats:
                variables["batch_stats"] = batch_stats
            outputs = model.apply(variables, batch, train=False)
            tot, tasks = model.loss(outputs, batch)
            return {
                "loss": tot,
                "tasks": jnp.stack(tasks) if tasks else jnp.zeros((0,)),
                "outputs": outputs,
            }

        return jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(P(), P(), _batch_spec(batch, axis)),
            out_specs={
                "loss": P(),
                "tasks": P(),
                "outputs": jax.tree_util.tree_map(
                    lambda _: P(axis), tuple(range(model.num_heads))
                ),
            },
            check_vma=False,
        )(params, batch_stats, batch)

    return jax.jit(eval_step)
