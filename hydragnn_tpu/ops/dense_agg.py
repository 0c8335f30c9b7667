"""Dense neighbor-list aggregation — scatter-free message passing.

XLA's scatter on TPU is the hot cost of segment-reduction message passing
at MXU-scale widths (measured on v5e: a single packed segment scatter at
E=70k, D=513 costs ~3-6 ms while the step's matmuls cost ~1 ms — the
whole PNA train step is scatter-bound). This module removes scatters from
BOTH directions of the conv:

- forward: neighbors are materialized host-side as fixed-width per-receiver
  lists (``nbr_idx [N, K]`` + mask), so every aggregation (sum/mean/min/
  max/std) is a masked reduction over the K axis — pure vectorized VPU
  work, no scatter;
- backward: the VJP of the neighbor gather is normally a scatter-add; we
  give it a custom VJP that reads the cotangent through the REVERSE
  neighbor list (sender-side slots, also precomputed host-side), so the
  backward pass is a gather + masked reduction too.

Two calls move rows between nodes and slots, each the other's transpose:
:func:`gather_neighbors` (sender rows to their receivers' slots) and
:func:`aggregate_to_senders` (slot values summed at the sender each
names). Where the operands allow it (a bf16 table, a TPU, a batch whose
collate states its locality) both are block-local one-hot products on the
MXU (``ops/local_gather.py``), f32 columns going through beside the bf16
table as three exact bf16 pieces each; otherwise XLA's gathers through
the forward and reverse lists.

Numerics are identical to the segment path (same masking, same empty-
segment fill); see ``tests/test_dense_agg.py`` for the parity proof.
The lists live in ``batch.extras`` and are built by the loader when the
architecture opts in (``dense_aggregation: true``).

Host side, a list is a SLOT per edge plus one assembly. An edge's slot in
its receiver's (sender's) list is its rank among the edges with that
receiver (sender), in edge-row order (:func:`edge_slots`) — a property of
the graph alone, because collation lays a sample's edges down contiguously
and in the sample's own order. So the loader computes slots once per
SAMPLE and caches them (``data/loaders.py:_sample_neighbor_slots``), and
per batch only concatenates them and runs :func:`assemble_neighbor_lists`:
direct indexed writes, no sort. Callers that hold only an edge list
(:func:`build_neighbor_lists`) compute slots over those edges and run the
same assembly — one implementation, identical arrays.
"""

import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

_BIG = 1e9
# every gather-and-reduce below, forward and backward, carries this name in
# a device trace (``jax.named_scope``: a name, no run-time cost)
_scope = jax.named_scope("agg_dense")


def max_degree(senders, receivers, edge_mask=None) -> Tuple[int, int]:
    """(max in-degree, max out-degree) over REAL edges — the K widths a
    layout needs for dense lists."""
    senders = np.asarray(senders)
    receivers = np.asarray(receivers)
    if edge_mask is not None:
        senders = senders[np.asarray(edge_mask)]
        receivers = receivers[np.asarray(edge_mask)]
    if senders.size == 0:
        return 1, 1
    k_in = int(np.bincount(receivers).max())
    k_out = int(np.bincount(senders).max())
    return max(k_in, 1), max(k_out, 1)


def _rank_in_group(owner_ids: np.ndarray) -> np.ndarray:
    """Rank of every row among the rows with its owner, in row order — the
    ONE slot-assignment rule (neighbour lists and DimeNet's triplet
    groupings both place row ``r`` at ``lists[owner[r], rank[r]]``)."""
    order = np.argsort(owner_ids, kind="stable")
    o_sorted = owner_ids[order]
    rank = np.empty(owner_ids.shape[0], np.intp)
    rank[order] = np.arange(o_sorted.shape[0]) - np.searchsorted(
        o_sorted, o_sorted, side="left"
    )
    return rank


def _check_slots(slots: np.ndarray, k: int, label: str):
    if slots.size and int(slots.max()) >= k:
        raise ValueError(
            f"group size exceeds layout {label}={k}; recompute the layout"
        )


def edge_slots(senders, receivers) -> np.ndarray:
    """``[2, E]`` slots of a graph's (all real) edges: row 0 the rank of
    each edge among the edges with its RECEIVER, row 1 among those with its
    SENDER, in edge-row order. Stored at the smallest unsigned dtype that
    holds them (uint8 up to degree 256: two bytes an edge). What the
    loader caches per sample; its row maxima + 1 (widened first: a uint8
    wraps) are the sample's (max in-degree, max out-degree)."""
    slots = np.stack(
        [
            _rank_in_group(np.asarray(receivers)),
            _rank_in_group(np.asarray(senders)),
        ]
    )
    widest = int(slots.max()) if slots.size else 0
    return slots.astype(np.min_scalar_type(widest))


def assemble_neighbor_lists(
    senders: np.ndarray,
    receivers: np.ndarray,
    slots: np.ndarray,
    num_nodes: int,
    num_edge_rows: int,
    k_in: int,
    k_out: int,
    with_slot_tables: bool = False,
    rows: Optional[np.ndarray] = None,
    slot=None,
):
    """The extras dict of :func:`build_neighbor_lists` from known per-edge
    ``slots [2, E]`` (:func:`edge_slots`), by direct indexed writes into
    zero-initialised arrays — no sort. ``senders``/``receivers``/``slots``
    cover the REAL edges only; ``rows`` are their rows in the edge table of
    ``num_edge_rows`` rows (None: the first ``E`` rows, the collate
    contract). Padded slots hold index 0, mask False. ``slot``
    (``graph/slots.py``) gives the lists to fill, zeroed again, and the
    scratch the ``E``-row temporaries are written into (sized at
    ``num_edge_rows``, which bounds ``E``); without it all are allocated."""
    from hydragnn_tpu.graph.slots import filled

    _check_slots(slots[0], k_in, "k_in")
    _check_slots(slots[1], k_out, "k_out")
    e = senders.shape[0]
    # a slot's scratch is sized once, at the bound; fresh scratch at E
    cap = e if slot is None else num_edge_rows
    if rows is None:
        if slot is None:
            rows = np.arange(e, dtype=np.int32)
        else:
            rows = slot.array(
                "rows", (cap,), np.int32,
                make=lambda: np.arange(cap, dtype=np.int32),
            )[:e]
    # flat [N*K_in] / [N*K_out] dense slot of every real edge
    flat_in = filled(slot, "flat_in", (cap,), np.intp, None)[:e]
    flat_out = filled(slot, "flat_out", (cap,), np.intp, None)[:e]
    np.multiply(receivers, k_in, out=flat_in, dtype=np.intp)
    np.add(flat_in, slots[0], out=flat_in, dtype=np.intp)
    np.multiply(senders, k_out, out=flat_out, dtype=np.intp)
    np.add(flat_out, slots[1], out=flat_out, dtype=np.intp)
    nbr_idx = filled(slot, "nbr_idx", (num_nodes, k_in), np.int32)
    nbr_edge = filled(slot, "nbr_edge", (num_nodes, k_in), np.int32)
    nbr_mask = filled(slot, "nbr_mask", (num_nodes, k_in), bool)
    rev_idx = filled(slot, "rev_idx", (num_nodes, k_out), np.int32)
    rev_mask = filled(slot, "rev_mask", (num_nodes, k_out), bool)
    nbr_idx.reshape(-1)[flat_in] = senders
    nbr_edge.reshape(-1)[flat_in] = rows
    nbr_mask.reshape(-1)[flat_in] = True
    rev_idx.reshape(-1)[flat_out] = flat_in
    rev_mask.reshape(-1)[flat_out] = True
    out = {
        "nbr_idx": nbr_idx,
        "nbr_edge": nbr_edge,
        "nbr_mask": nbr_mask,
        "rev_idx": rev_idx,
        "rev_mask": rev_mask,
    }
    if with_slot_tables:
        # out_slot is the inverse permutation of out_edge — the bmm-triplet
        # path routes per-(sender, out-slot) results back onto the edge
        # table with it
        out_edge = filled(slot, "out_edge", (num_nodes, k_out), np.int32)
        out_edge.reshape(-1)[flat_out] = rows
        edge_slot = filled(slot, "edge_slot", (num_edge_rows,), np.int32)
        out_slot = filled(slot, "out_slot", (num_edge_rows,), np.int32)
        edge_slot[rows] = flat_in
        out_slot[rows] = flat_out
        out.update(out_edge=out_edge, edge_slot=edge_slot, out_slot=out_slot)
    return out


def build_neighbor_lists(
    senders: np.ndarray,
    receivers: np.ndarray,
    edge_mask: Optional[np.ndarray],
    num_nodes: int,
    k_in: int,
    k_out: int,
    with_slot_tables: bool = False,
):
    """Host-side (numpy) conversion of an edge list into dense lists.

    Returns extras dict:
      ``nbr_idx   [N, K_in]``  sender node of each incoming-edge slot
      ``nbr_edge  [N, K_in]``  edge-list row of that slot (for edge_attr)
      ``nbr_mask  [N, K_in]``  slot validity
      ``rev_idx   [N, K_out]`` flat (receiver*K_in + slot) position of each
                               outgoing edge — the backward-gather index
      ``rev_mask  [N, K_out]``
    ``with_slot_tables`` (DimeNet's bmm-triplet path only — they are wire
    overhead for every other model) adds:
      ``out_edge  [N, K_out]`` edge-list row of each outgoing-edge slot
      ``edge_slot [E]``        flat (receiver*K_in + slot) of each edge
      ``out_slot  [E]``        flat (sender*K_out + slot) of each edge
    (the out-slot validity mask is ``rev_mask`` — same grouping).
    Real edges only (``edge_mask`` False rows are padding and excluded).

    The entry for callers that hold only an edge list (a whole batch, a
    partition shard): slots come from :func:`edge_slots` over these edges,
    the arrays from :func:`assemble_neighbor_lists`. The loader shares that
    assembly but takes its slots from the per-sample cache
    (``data/loaders.py:collate_for_layout``), so it sorts nothing per
    batch; the two routes give identical arrays.
    """
    senders = np.asarray(senders)
    receivers = np.asarray(receivers)
    num_edge_rows = senders.shape[0]
    rows = None
    if edge_mask is not None:
        rows = np.flatnonzero(np.asarray(edge_mask, bool)).astype(np.int32)
        senders, receivers = senders[rows], receivers[rows]
    return assemble_neighbor_lists(
        senders,
        receivers,
        edge_slots(senders, receivers),
        num_nodes,
        num_edge_rows,
        k_in,
        k_out,
        with_slot_tables=with_slot_tables,
        rows=rows,
    )


def _backend() -> str:
    """The platform the program is traced for (one name, so a test that
    runs the kernels in the interpreter can say ``tpu`` here)."""
    return jax.default_backend()


def _halo(x, exact, nbr_idx, nbr_mask, nbr_reach):
    """``window_halo`` for the table (operand) ``x`` with the f32 columns
    ``exact`` beside it as pieces, behind these lists: the one question
    both neighbour products ask, answered from the operands alone."""
    from hydragnn_tpu.ops.local_gather import lane_width, window_halo

    if nbr_mask is None or nbr_reach is None:
        return None
    widths = (x.shape[-1],) + (() if exact is None else (3 * exact.shape[-1],))
    return window_halo(
        x.dtype, nbr_reach.shape[-1], nbr_idx.shape[1], lane_width(*widths),
        _backend(),
    )


def _report(kind, n, k, width, dtype, h):
    from hydragnn_tpu.ops.agg_policy import emit_choice

    impl = "xla" if h is None else "onehot"
    emit_choice(
        f"{kind}/n{n}/k{k}/d{width}/{jnp.dtype(dtype).name}", impl,
        "operands", gather=impl, **({} if h is None else {"h": h}),
    )


def _one_table(x, exact):
    """``x`` and the f32 columns ``exact`` as ONE table for XLA's gather
    (f32 as soon as there are such columns)."""
    return x if exact is None else jnp.concatenate([x, exact], axis=-1)


def _apart(out, x):
    """A result of :func:`_one_table`'s table apart again: ``x``'s columns
    at its dtype, the rest (f32) or None."""
    d = x.shape[-1]
    if out.shape[-1] == d:
        return out.astype(x.dtype), None
    return out[..., :d].astype(x.dtype), out[..., d:]


def gather_neighbors(
    x, nbr_idx, rev_idx, rev_mask, nbr_mask=None, nbr_reach=None, exact=None
):
    """``x[nbr_idx]`` ([N, D] -> [N, K, D]) whose backward pass is no
    scatter-add. The ONE neighbour gather of the dense path, in one of
    two implementations chosen at trace time from the operands alone
    (``ops/local_gather.py window_halo``: table dtype, backend, what the
    collate states about locality, the window's size):

    - ``xla``: the indexed read, backward a gather through the reverse
      list. What every caller gets that states no locality (``nbr_reach``
      None: lists built from a bare edge list), bit for bit as before;
    - ``onehot``: block-local one-hot products on the MXU in both
      directions, for a batch whose collate states (``extras["nbr_reach"]``,
      ``data/loaders.py collate_for_layout``) that every sender lies
      within ``nbr_reach.shape[-1]`` rows of its receiver. Real slots
      equal the indexed read bit for bit; padded slots read zero instead
      of row 0 (every consumer masks them with ``nbr_mask``).

    ``exact``: f32 columns ``[N, C]`` (EGNN's positions) to gather through
    the same lists without giving up a bit: the result is then ``(x rows,
    exact rows [N, K, C] f32)``. Behind a bf16 ``x`` they go through the
    product as three bf16 pieces each (``local_gather.split_f32``), a
    second table of the same call, their cotangents likewise; under
    ``xla`` they are further columns of one (f32) table, as EGNN always
    gathered them. A table that IS f32 is not split: it keeps ``xla``.

    The choice is reported as an ``agg_choice`` event (``gather``, ``h``).
    """
    (n, d), k_in = x.shape, nbr_idx.shape[1]
    h = _halo(x, exact, nbr_idx, nbr_mask, nbr_reach)
    if h is None:
        table = _one_table(x, exact)
        _report("gather", n, k_in, table.shape[-1], table.dtype, None)
        rows = _apart(_gather_xla(table, nbr_idx, rev_idx, rev_mask), x)
    else:
        pieces = 0 if exact is None else 3 * exact.shape[-1]
        _report("gather", n, k_in, d + pieces, x.dtype, h)
        rows = _gather_onehot(h, x, exact, nbr_idx, nbr_mask)
    return rows[0] if exact is None else rows


def neighbor_rows(x, extras, exact=None):
    """:func:`gather_neighbors` through a dense-list batch's ``extras``,
    with everything they state (the convs' one call)."""
    return gather_neighbors(
        x, extras["nbr_idx"], extras["rev_idx"], extras["rev_mask"],
        extras["nbr_mask"], extras.get("nbr_reach"), exact,
    )


@jax.custom_vjp
@_scope
def _gather_xla(x, nbr_idx, rev_idx, rev_mask):
    # host-built lists: padded slots hold index 0 (always in range);
    # every consumer masks the gathered rows with nbr_mask before
    # accumulating, so the raw gather is the masking contract's input
    # numlint: disable=unmasked-gather-id
    return x[nbr_idx]


@_scope
def _gather_fwd(x, nbr_idx, rev_idx, rev_mask):
    # numlint: disable=unmasked-gather-id — mirrors the primal above
    return x[nbr_idx], (x.shape, nbr_idx.shape, rev_idx, rev_mask)


@_scope
def _gather_bwd(res, g):
    (n, d), (_, k_in), rev_idx, rev_mask = res
    flat = g.reshape(n * k_in, d)
    contrib = flat[rev_idx]  # [N, K_out, D]
    # K_out-axis accumulation in f32 (a bf16 cotangent would otherwise
    # sum at bf16); the upcast is a no-op on the f32 path
    gm = jnp.where(rev_mask[..., None], contrib, 0.0).astype(jnp.float32)
    gx = gm.sum(axis=1).astype(g.dtype)
    return gx, None, None, None


_gather_xla.defvjp(_gather_fwd, _gather_bwd)


# The two products, each the other's transpose: what one does forward the
# other does to the cotangents. f32 columns go through as a second bf16
# table of pieces; the kernels work slot-major ([K, N, D]: whole tiles per
# slot), and the transposes are layout choices XLA folds into the fusions
# beside them. Each is ONE ``jax.jit``-wrapped body: the calls of a step
# program that agree in shape (EGNN: seven layers, and a gather's backward
# with a sender sum's forward) share one traced and lowered function, so
# set-up pays for pieces, padding and kernel once per shape, not per site.


def _stated_mesh():
    """The abstract mesh that is current, stated: a no-op for the program,
    but JAX runs a backward rule under a stated (empty) mesh and a forward
    rule under none, and a jit body's trace cache tells the two apart. With
    this around both, a gather's forward and a sender sum's backward are
    ONE traced body and ONE lowered kernel (lowering a kernel is most of
    what set-up pays for the products: PERF.md section 6, PR 29)."""
    return jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh())


def _tables(x, exact):
    from hydragnn_tpu.ops.local_gather import split_f32

    return (x,) if exact is None else (x, split_f32(exact))


@functools.partial(jax.jit, static_argnums=0)
def _rows_product(h, x, exact, nbr_idx):
    """``(x[nbr_idx], exact[nbr_idx])``: [N, K, D] at ``x.dtype`` and
    [N, K, C] f32 (None without ``exact``)."""
    from hydragnn_tpu.ops.local_gather import gather_product, join_f32

    rows = gather_product(_tables(x, exact), nbr_idx, h)
    rows = [r.transpose(1, 0, 2) for r in rows]
    return rows[0], None if exact is None else join_f32(rows[1])


@functools.partial(jax.jit, static_argnums=0)
def _sums_product(h, g, exact, nbr_idx, nbr_mask):
    """Sums of the real slots of ``g [N, K, D]`` and ``exact [N, K, C]``
    at the sender each names: [N, D] at ``g.dtype`` and [N, C] f32 (the
    pieces leave the kernel in f32, its accumulator as it is)."""
    from hydragnn_tpu.ops.local_gather import join_f32, scatter_product

    sums = scatter_product(
        tuple(t.transpose(1, 0, 2) for t in _tables(g, exact)),
        nbr_idx, nbr_mask, h,
        out_dtype=(None,) if exact is None else (None, jnp.float32),
    )
    return sums[0], None if exact is None else join_f32(sums[1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
@_scope
def _gather_onehot(h, x, exact, nbr_idx, nbr_mask):
    with _stated_mesh():
        return _rows_product(h, x, exact, nbr_idx)


@_scope
def _gather_onehot_fwd(h, x, exact, nbr_idx, nbr_mask):
    return _gather_onehot(h, x, exact, nbr_idx, nbr_mask), (nbr_idx, nbr_mask)


@_scope
def _gather_onehot_bwd(h, res, g):
    with _stated_mesh():
        return (*_sums_product(h, *g, *res), None, None)


_gather_onehot.defvjp(_gather_onehot_fwd, _gather_onehot_bwd)


@jax.custom_vjp
@_scope
def group_sum(values, lists, lists_mask, owner_ids, valid):
    """Generic scatter-free segment sum for SINGLE-OWNER groupings.

    ``values [T, D]`` where every valid row belongs to exactly one group
    (``owner_ids [T]``, ``valid [T]`` row validity); ``lists [G, K]``
    enumerates each group's member rows with ``lists_mask`` validity.
    Forward is a gather + masked K-axis sum (= ``segment_sum(values,
    owner_ids, G)`` over valid rows, without the scatter); backward is the
    exact dual — a gather ``g[owner_ids]`` masked by ``valid`` (padded
    rows share owner slot 0, so an unmasked backward would corrupt real
    rows' gradients). Covers DimeNet's triplet->edge and edge->node
    aggregations (and any other one-owner grouping) with precomputed
    host-side lists.
    """
    member = values[lists]  # [G, K, D]
    # masked K-axis sum accumulates in f32, result back at the input
    # dtype (PNA fused-stats convention; no-op on the f32 path)
    hm = jnp.where(lists_mask[..., None], member, 0.0).astype(jnp.float32)
    return hm.sum(axis=1).astype(values.dtype)


@_scope
def _group_sum_fwd(values, lists, lists_mask, owner_ids, valid):
    return group_sum(values, lists, lists_mask, owner_ids, valid), (
        owner_ids,
        valid,
    )


@_scope
def _group_sum_bwd(res, g):
    owner_ids, valid = res
    gv = jnp.where(valid[:, None], g[owner_ids], 0.0)
    return gv, None, None, None, None


group_sum.defvjp(_group_sum_fwd, _group_sum_bwd)


@jax.custom_vjp
@_scope
def gather_rows_to_slots(table, lists, lists_mask, slot_of_row, row_valid):
    """``table[lists]`` ([R, D] -> [G, K, D]) for a SINGLE-OWNER grouping
    (every valid table row appears in exactly one list slot). Backward is
    the inverse permutation ``g.reshape(G*K, D)[slot_of_row]`` — a pure
    gather, no scatter-add in either direction."""
    return jnp.where(lists_mask[..., None], table[lists], 0.0)


@_scope
def _grs_fwd(table, lists, lists_mask, slot_of_row, row_valid):
    return (
        gather_rows_to_slots(table, lists, lists_mask, slot_of_row, row_valid),
        (table.shape, lists.shape, slot_of_row, row_valid),
    )


@_scope
def _grs_bwd(res, g):
    (r, d), (grp, k), slot_of_row, row_valid = res
    gt = g.reshape(grp * k, d)[slot_of_row]
    return jnp.where(row_valid[:, None], gt, 0.0), None, None, None, None


gather_rows_to_slots.defvjp(_grs_fwd, _grs_bwd)


@jax.custom_vjp
@_scope
def slots_to_rows(slots, slot_of_row, row_valid, lists, lists_mask):
    """Inverse of :func:`gather_rows_to_slots`: route per-slot values
    ``slots [G, K, D]`` back onto their owning rows -> ``[R, D]``.
    Backward gathers the row cotangent through ``lists`` — the exact dual,
    scatter-free both directions."""
    g, k, d = slots.shape
    out = slots.reshape(g * k, d)[slot_of_row]
    return jnp.where(row_valid[:, None], out, 0.0)


@_scope
def _str_fwd(slots, slot_of_row, row_valid, lists, lists_mask):
    return (
        slots_to_rows(slots, slot_of_row, row_valid, lists, lists_mask),
        (lists, lists_mask),
    )


@_scope
def _str_bwd(res, g):
    lists, lists_mask = res
    gs = jnp.where(lists_mask[..., None], g[lists], 0.0)
    return gs, None, None, None, None


slots_to_rows.defvjp(_str_fwd, _str_bwd)


def build_group_lists(
    owner_ids, valid_mask, num_groups: int, k: int, label: str = "k"
):
    """Host-side (numpy): invert a single-owner mapping into fixed-width
    member lists. Returns (lists [G, k] int32, mask [G, k] bool).
    ``label`` names the budget in overflow errors (k_in/k_out/kt)."""
    owner_ids = np.asarray(owner_ids, np.int64)
    rows = np.arange(owner_ids.shape[0])
    if valid_mask is not None:
        keep = np.asarray(valid_mask, bool)
        owner_ids, rows = owner_ids[keep], rows[keep]
    lists = np.zeros((num_groups, k), np.int32)
    mask = np.zeros((num_groups, k), bool)
    slot = _rank_in_group(owner_ids)
    _check_slots(slot, k, label)
    lists[owner_ids, slot] = rows
    mask[owner_ids, slot] = True
    return lists, mask


def aggregate_to_senders(
    h, nbr_idx, nbr_mask, rev_idx, rev_mask, nbr_reach=None, exact=None
):
    """Sum dense per-edge values ``h [N, K_in, D]`` (keyed by receiver x
    slot) onto their SENDER nodes -> ``[N, D]`` at ``h.dtype``, f32
    accumulation, scatter-free in both directions. The transpose of
    :func:`gather_neighbors`, and chosen like it, at trace time, from the
    operands alone (``window_halo``: operand dtype, backend, the collate's
    stated ``nbr_reach``, the window's size):

    - ``xla``: each sender reads its outgoing slots through the reverse
      list; backward a gather through the forward list. Every caller that
      states no locality or hands an f32 operand (SchNet's ``trans``,
      partition shards, the CPU), bit for bit as before;
    - ``onehot``: forward ``local_gather.scatter_product`` on the
      slot-major operand, backward ``gather_product`` of the cotangent
      masked by ``nbr_mask``.

    ``exact``: f32 values ``[N, K_in, C]`` (EGNN's translations) to sum
    alongside without giving up a bit of their addends: the result is then
    ``(sums of h, sums of exact [N, C] f32)``. Behind a bf16 ``h`` they
    go through the product as three bf16 pieces each, a second operand of
    the same call (a 0/1 product of a piece is exact, the kernel's f32
    accumulator leaves as it is), their cotangents likewise; under ``xla``
    they are further columns of one (f32) operand.

    Reported as an ``agg_choice`` event (``scatter/...``, ``gather``, ``h``).
    """
    n, k_in, d = h.shape
    halo = _halo(h, exact, nbr_idx, nbr_mask, nbr_reach)
    if halo is None:
        operand = _one_table(h, exact)
        _report("scatter", n, k_in, operand.shape[-1], operand.dtype, None)
        sums = _apart(
            _sender_sum_xla(operand, nbr_idx, nbr_mask, rev_idx, rev_mask), h
        )
    else:
        pieces = 0 if exact is None else 3 * exact.shape[-1]
        _report("scatter", n, k_in, d + pieces, h.dtype, halo)
        sums = _sender_sum_onehot(halo, h, exact, nbr_idx, nbr_mask)
    return sums[0] if exact is None else sums


def sender_sums(h, extras, exact=None):
    """:func:`aggregate_to_senders` through a dense-list batch's
    ``extras``, with everything they state (the convs' one call)."""
    return aggregate_to_senders(
        h, extras["nbr_idx"], extras["nbr_mask"], extras["rev_idx"],
        extras["rev_mask"], extras.get("nbr_reach"), exact,
    )


@jax.custom_vjp
@_scope
def _sender_sum_xla(h, nbr_idx, nbr_mask, rev_idx, rev_mask):
    n, k_in, d = h.shape
    flat = h.reshape(n * k_in, d)
    contrib = flat[rev_idx]  # [N, K_out, D]
    # masked K_out-axis sum accumulates in f32 (bf16 dense path), cast
    # back to the message dtype — no-op when h is already f32
    hm = jnp.where(rev_mask[..., None], contrib, 0.0).astype(jnp.float32)
    return hm.sum(axis=1).astype(h.dtype)


@_scope
def _sender_sum_xla_fwd(h, nbr_idx, nbr_mask, rev_idx, rev_mask):
    return (
        _sender_sum_xla(h, nbr_idx, nbr_mask, rev_idx, rev_mask),
        (nbr_idx, nbr_mask),
    )


@_scope
def _sender_sum_xla_bwd(res, g):
    nbr_idx, nbr_mask = res
    gh = g[nbr_idx]  # [N, K_in, D]
    gh = jnp.where(nbr_mask[..., None], gh, 0.0)
    return gh, None, None, None, None


_sender_sum_xla.defvjp(_sender_sum_xla_fwd, _sender_sum_xla_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
@_scope
def _sender_sum_onehot(halo, h, exact, nbr_idx, nbr_mask):
    with _stated_mesh():
        return _sums_product(halo, h, exact, nbr_idx, nbr_mask)


@_scope
def _sender_sum_onehot_fwd(halo, h, exact, nbr_idx, nbr_mask):
    return (
        _sender_sum_onehot(halo, h, exact, nbr_idx, nbr_mask),
        (nbr_idx, nbr_mask),
    )


@_scope
def _sender_sum_onehot_bwd(halo, res, g):
    nbr_idx, nbr_mask = res
    with _stated_mesh():
        rows = _rows_product(halo, *g, nbr_idx)
    # a padded slot's row (zero, or row 0) is no cotangent of its value
    return (
        *(
            None if r is None else jnp.where(nbr_mask[..., None], r, 0)
            for r in rows
        ),
        None, None,
    )


_sender_sum_onehot.defvjp(_sender_sum_onehot_fwd, _sender_sum_onehot_bwd)


@_scope
def dense_moments(h, nbr_mask):
    """(mean, std, deg, has) over the K axis of masked messages
    ``h [N, K, D]`` — PNA's count/mean/std statistics without a scatter.
    Matches segment_moments semantics: empty receivers -> mean/std of 0."""
    m = nbr_mask[..., None]
    # statistics accumulate in f32 regardless of the message dtype and
    # come back at h.dtype — the dense twin of the fused-kernel f32
    # stats path (models/pna.py casts the same way)
    hm = jnp.where(m, h, 0.0).astype(jnp.float32)
    cnt = nbr_mask.sum(axis=1).astype(jnp.float32)[:, None]
    has = cnt > 0
    deg = jnp.maximum(cnt, 1.0)
    mean = hm.sum(axis=1) / deg
    sq = (hm * hm).sum(axis=1) / deg
    std = jnp.sqrt(jnp.maximum(sq - mean * mean, 0.0) + 1e-5)
    return (
        mean.astype(h.dtype), std.astype(h.dtype),
        deg.astype(h.dtype), has,
    )


@_scope
def dense_minmax(h, nbr_mask, has, fill=0.0):
    """(min, max) over the K axis; empty receivers -> ``fill`` (segment
    fill semantics so padded nodes stay finite)."""
    m = nbr_mask[..., None]
    mx = jnp.where(m, h, -_BIG).max(axis=1)
    mn = jnp.where(m, h, _BIG).min(axis=1)
    mx = jnp.where(has, mx, fill)
    mn = jnp.where(has, mn, fill)
    return mn, mx


@_scope
def dense_sum(h, nbr_mask):
    # masked K-axis sum in f32, result at the message dtype (no-op for
    # f32 inputs; the guard the bf16 dense path needs)
    hm = jnp.where(nbr_mask[..., None], h, 0.0).astype(jnp.float32)
    return hm.sum(axis=1).astype(h.dtype)


def attach_neighbor_lists(batch):
    """Batch -> batch with dense-list extras attached (the one canonical
    attach operation; the loader, benches and tests all route through
    here). Host-side; keys match what the conv's dense path reads."""
    k_in, k_out = max_degree(batch.senders, batch.receivers, batch.edge_mask)
    extras = build_neighbor_lists(
        np.asarray(batch.senders),
        np.asarray(batch.receivers),
        np.asarray(batch.edge_mask),
        int(batch.x.shape[-2]),
        k_in,
        k_out,
        # DimeNet batches (triplet extras present) get the bmm-path slot
        # tables; other models never read them
        with_slot_tables="trip_ji" in (batch.extras or {}),
    )
    merged = dict(batch.extras or {})
    merged.update({k: jnp.asarray(v) for k, v in extras.items()})
    return batch.replace(extras=merged)
