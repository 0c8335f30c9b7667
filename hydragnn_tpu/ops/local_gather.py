"""Block-local neighbour gathers as one-hot products on the MXU.

XLA's gather on a TPU pays for every index, whatever the row's width
(v5e, PNA h256: 7.5 ns for a row of one bf16, 8.5 ns for a row of 256;
PERF.md section 5). A collated batch is block-diagonal: a graph's nodes
are contiguous and every neighbour of a node lies in its own graph, so
the sender of any slot of row ``i`` lies within ``reach`` rows of ``i``
(``reach`` = the layout's largest graph). A gather whose sources lie in
a window of a few hundred rows is a product with a 0/1 matrix over that
window:

- forward, per block ``b`` of ``BLOCK`` receivers: ``out[:, block] =
  onehot(nbr_idx[block, :] - (b - h) * BLOCK) @ x[blocks b - h .. b + h]``,
  ONE product for the block's K slots (stacked along its rows), summed
  over the ``2h + 1`` blocks of the window inside the MXU;
- backward, per block of ``BLOCK`` senders: ``gx[block] = sum_o onehot^T
  @ g[:, block b + o]``, one product per block of the window over its K x
  ``BLOCK`` (slot, receiver) rows, the 0/1 matrix masked by ``nbr_mask``,
  accumulated in f32 and cast once; where the whole list's window does
  not fit VMEM, over ``nk`` chunks of the K slots in turn (below).

One product per block, not one per slot: the kernels' bodies stay a few
dozen operations, so tracing and lowering them (set-up time, paid by
every step program of every run) costs a tenth of a second a pair; with
K x (2h + 1) products unrolled it cost half a second, at the same speed
on the chip (PERF.md section 6, PR 27).

The 0/1 matrices are built in VMEM from an iota compare and never reach
HBM. For a bf16 table the forward is exact (one non-zero term per
output, f32 accumulation); padded slots read zero where the indexed read
returns row 0 (every consumer masks them). One difference no mask
hides: a product multiplies, so a NON-FINITE table row reaches every
receiver of its window as NaN, where the indexed read confines it to
the rows that name it.

Both kernels work slot-major (``[K, N, D]``): every block they read or
write is a whole ``[BLOCK, D]`` tile. The caller transposes; XLA makes
that a layout choice of the neighbouring fusions.

The pair serves two callers in ``ops/dense_agg.py``, each the other's
transpose (PR 29):

- ``gather_neighbors`` (every dense conv): forward :func:`gather_product`,
  backward :func:`scatter_product`;
- ``aggregate_to_senders`` (EGNN's sum of per-slot messages at their
  SENDER): forward :func:`scatter_product` on the slot-major operand,
  backward :func:`gather_product` of the cotangent. With E_GCL's f32
  columns beside 128 messages, 16 slots a row, a call costs 3.5-4.4 ns an
  index at h = 1 and 5.4-6.9 at h = 2, pieces and padding included,
  against 23-38 (forward) and 6.7-8.7 (backward) for the reverse- and
  forward-list gathers it replaces (v5e, PERF.md section 6, PR 29).

``dense_agg`` wraps each product (pieces, padding, kernel) in ONE
``jax.jit`` body, and the kernel calls over lane-padded tables are jit
bodies of their own, so the calls of a step program that agree in shape
(EGNN: seven layers, and a gather's backward with a sender sum's forward)
share one traced and lowered function: 28 call sites, 5 lowered kernels.

f32 columns go through beside a bf16 table as three bf16 pieces each
(:func:`split_f32`, :func:`join_f32`): 3 x 8 significand bits hold an
f32's 24, a 0/1 product of each piece is exact, so a gathered position
equals the indexed read bit for bit and a summed piece column is an f32
sum. The pieces are a second table of the same call (both products take
a tuple of tables: ONE 0/1 matrix a block, a product a table, each table
in its own lane tiles), so 128 message columns are read and written where
they lie. The callers hand such columns over (``exact=`` of the two
functions above); a table that IS f32 keeps XLA's gather.

:func:`window_halo` is the rule that selects the product; everything it
reads is a property of the operands.

VMEM: the backward holds its window's ``2h + 1`` blocks of K x ``BLOCK``
cotangent rows, double-buffered, so many-slot lists at h = 2 need more
than the 32 MiB scoped limit every call once had (SchNet's 50 slots over
256 columns: 36 MiB, by the v5e compiler). :func:`_vmem_bytes` counts what
one grid step of each kernel holds, as that compiler allocates it; each
call asks for its own count with room and never for less than those 32
MiB (a call that fitted there compiles as it did), and the rule refuses a
window whose limit would pass ``_VMEM_BLOCK_BUDGET``: one count for the
rule and the kernel, so the two cannot disagree.

Where the backward's whole list would pass the budget (CGCNN's 47-50
slots over 512 columns: 46-74 MiB), it sums the slots in chunks:
:func:`slot_chunks` is the fewest chunks of ``Kc = ceil(K / nk)`` slots
whose one grid step fits, from ``(K, h, lanes)`` alone, and
the rule, the call and the ``agg_choice`` event's ``chunks`` all take it
from there. The chunks are a second, inner grid axis (``"arbitrary"``):
each step holds the window's ``[Kc, BLOCK, D]`` cotangent blocks and its
``[1, Kc BLOCK]`` sender lanes (block-major, so a chunk's lanes are
contiguous); the f32 accumulator is zeroed at the first chunk and cast
and written at the last. The 0/1 work is the whole list's, in pieces.
Where ``Kc`` does not divide K the last chunk's block reaches past the
array's end, and that chunk reads its real slots alone (a static slice:
whatever the buffer holds there, a NaN, reaches no product). At ``nk =
1`` the call is the one-axis grid it always was, the same traced kernel
and limit. The forward is never chunked: its count then sets the rule's
edge (60 slots at h = 2 over 512 columns).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp

BLOCK = 128  # receivers (senders) per grid step: the MXU's edge
_LANES = 128
_MIB = 1024 * 1024
# The most scoped VMEM one kernel call may be given. A v5e core has 128 MiB
# (``pltpu.get_tpu_info()`` on the chip), and the chip's compiler took every
# limit from 36 MiB to that for the widest call of any benchmark cell
# (SchNet's backward at K = 50, h = 2, 256 columns; refused at 32). That
# call's limit is 41.7 MiB (39.7 by :func:`_vmem_bytes` and the headroom);
# 48 MiB holds it and lists of up to 58 slots at its window (PERF.md
# section 6).
_VMEM_BLOCK_BUDGET = 48 * _MIB
# The limit every call had before it followed the call's own reckoning: a
# call that reckons less keeps it, so its program compiles as it did
_VMEM_LIMIT_FLOOR = 32 * _MIB
# room over the reckoning for what of the compiler's own it does not name
_VMEM_HEADROOM = 2 * _MIB
# The crossover, read once on a v5e (benchmarks/gather_crossover.py; PERF.md
# section 6, PR 27): per index and per [128 x 128] tile of its window
# ((2h + 1) row blocks x ceil(D / 128) lane tiles) the product costs
# 0.19-0.26 ns forward and 0.35-0.41 ns backward; XLA's gather costs 6.8-8.2
# ns per index forward, whatever the width, and 17-33 ns backward (through
# a reverse list 2.3 x as wide as the forward one). At 20 tiles (h = 2 at
# D = 512), the widest window read, the product still led by 3.7 + 6.9 ns
# against 7 + 17: no reading fell on the far side, so this is the edge of
# what was measured and not the crossover itself (about 35 tiles forward by
# the per-tile rate). Wider windows keep XLA's gather.
MAX_WINDOW_TILES = 20


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def split_f32(x):
    """``[..., C]`` f32 -> ``[..., 3 C]`` bf16, ``[hi | mid | lo]``: each
    piece the top 8 significand bits of what the pieces before it left
    (bit masks, so no compiler may round for us), hence ``hi + mid + lo ==
    x`` exactly wherever ``lo`` is a normal bf16: ``|x| >= 2**-102``.
    Below that the low pieces fall under bf16's normal range (subnormal on
    the CPU, flushed to zero on the TPU) and the re-join is off by less
    than ``2**-126``; a negative zero re-joins as zero, a non-finite ``x``
    as NaN."""
    rest = x.astype(jnp.float32)
    pieces = []
    for _ in range(3):
        bits = jax.lax.bitcast_convert_type(rest, jnp.uint32)
        top = jax.lax.bitcast_convert_type(
            bits & jnp.uint32(0xFFFF0000), jnp.float32
        )
        # numlint: disable=precision-policy-bypass — exact: 16 low bits clear
        pieces.append(top.astype(jnp.bfloat16))
        rest = rest - top  # exact: the bits the mask dropped
    return jnp.concatenate(pieces, axis=-1)


def join_f32(pieces):
    """The inverse of :func:`split_f32`, in f32; also of per-piece SUMS
    (f32 columns out of :func:`scatter_product`), then an f32 sum."""
    c = pieces.shape[-1] // 3
    hi, mid, lo = (
        pieces[..., i * c : (i + 1) * c].astype(jnp.float32) for i in range(3)
    )
    return (hi + mid) + lo


def window_halo(
    dtype, reach: Optional[int], k_in: int, dim: int, backend: str
) -> Optional[int]:
    """``h`` when the neighbour gather of a ``[N, dim]`` table of
    ``dtype`` through ``[N, k_in]`` lists runs as the block-local product
    (its window is the blocks ``b - h .. b + h``), ``None`` when it keeps
    XLA's gather. Selected by, and only by:

    - ``reach``: what the batch's collate states about locality (every
      sender within ``reach`` rows of its receiver); ``None`` from callers
      that hold only an edge list (partition shards with halo rows, one
      giant graph);
    - ``dtype``: bf16 only. An f32 table would need ``HIGHEST`` (six
      passes) to come out exact;
    - ``backend``: compiled Pallas is the TPU's;
    - the window's size: at most ``MAX_WINDOW_TILES`` MXU tiles per index
      (as far as the product was read ahead), and the scoped VMEM limit
      either kernel would be given (:func:`_vmem_limit`, from the same
      count the kernel call takes it from) within ``_VMEM_BLOCK_BUDGET``.
    """
    if reach is None or backend != "tpu" or dtype != jnp.bfloat16:
        return None
    h = _cdiv(max(int(reach), 1) - 1, BLOCK)
    lanes = _cdiv(dim, _LANES)
    if (2 * h + 1) * lanes > MAX_WINDOW_TILES:
        return None
    if not all(map(_fits, _vmem_bytes(k_in, h, lanes))):
        return None
    return h


def slot_chunks(k_in: int, h: int, dim: int) -> int:
    """How many chunks of its ``k_in`` slots the backward kernel sums a
    block's lists in, for a window of ``2h + 1`` blocks over tables
    ``dim`` columns wide (:func:`lane_width`): the fewest whose one
    chunk's count fits the budget, 1 wherever the whole list does. The
    one answer for the rule, the kernel call and the ``agg_choice``
    event's ``chunks``."""
    lanes = _cdiv(dim, _LANES)
    nk = 1
    while nk < k_in and not _fits(_bwd_bytes(_cdiv(k_in, nk), h, lanes)):
        nk += 1
    return nk


def _vmem_bytes(k_in: int, h: int, lanes: int):
    """(forward, backward): scoped VMEM one grid step of each kernel holds
    for lists of ``k_in`` slots, a window of ``2h + 1`` blocks and tables
    of ``lanes`` lane tiles side by side, as the v5e compiler allocates it:
    every block a ``BlockSpec`` names twice (Pallas double-buffers them),
    the scratch, and the kernels' large values. The backward's is that of
    ONE chunk of :func:`slot_chunks`' (the whole list wherever it fits).
    Over K 4-100, h 1-3 and 1-4 lane tiles it is 1.07-1.7 x what the
    compiler asked for the backward, 1.2-3 x for the forward (PERF.md
    section 6)."""
    w, d = 2 * h + 1, lanes * _LANES
    slab = k_in * BLOCK * d * 2  # one [K, BLOCK, D] bf16 block
    onehot = k_in * BLOCK * BLOCK  # elements of the 0/1 matrix per block
    forward = (
        2 * BLOCK * _cdiv(k_in, _LANES) * _LANES * 4  # the lists' block
        + 2 * w * BLOCK * d * 2  # the window's blocks
        + 2 * slab  # the result block
        + w * onehot * 3  # the [K BLOCK, w BLOCK] 0/1 matrix and its compare
        + 2 * slab  # the product, in f32
    )
    chunk = _cdiv(k_in, slot_chunks(k_in, h, d))
    return forward, _bwd_bytes(chunk, h, lanes)


def _bwd_bytes(kc: int, h: int, lanes: int) -> int:
    """What one grid step of the backward holds for a chunk of ``kc``
    slots (see :func:`_vmem_bytes`)."""
    w, d = 2 * h + 1, lanes * _LANES
    slab = kc * BLOCK * d * 2  # one [Kc, BLOCK, D] bf16 block
    return (
        2 * w * kc * BLOCK * 4  # the senders' [1, Kc BLOCK] windows
        + 2 * w * slab  # the cotangents' window
        + 3 * BLOCK * d * 4  # the result block (f32 at most), the accumulator
        + kc * BLOCK * BLOCK * 6  # the [BLOCK, Kc BLOCK] 0/1 matrix, the int32 iota
        + slab  # a ragged block's cotangents, masked
    )


def _vmem_limit(need: int) -> int:
    """The scoped VMEM limit of a call that reckons ``need`` bytes."""
    return max(_VMEM_LIMIT_FLOOR, need + _VMEM_HEADROOM)


def _fits(need: int) -> bool:
    """Whether that limit stays within ``_VMEM_BLOCK_BUDGET`` (the floor
    lies under the budget, so only the count and its headroom can pass
    it)."""
    return need + _VMEM_HEADROOM <= _VMEM_BLOCK_BUDGET


def _compiler_params(need: int, chunked: bool = False):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=(
            ("parallel", "arbitrary") if chunked else ("parallel",)
        ),
        vmem_limit_bytes=_vmem_limit(need),
    )


def _window_specs(block, axis: int, h: int, last: int, chunk_axis=None):
    """One BlockSpec per block ``b - h .. b + h`` of the window along
    ``axis``, each held inside the array (what a block beyond either end
    fetches instead is never used: see the kernels); along ``chunk_axis``,
    where given, the block of the grid's second index."""
    from jax.experimental import pallas as pl

    def spec(offset):
        def index(b, *chunk):
            at = [0] * len(block)
            at[axis] = jnp.clip(b + offset, 0, last)
            if chunk_axis is not None:
                at[chunk_axis] = chunk[0]
            return tuple(at)

        return pl.BlockSpec(block, index)

    return [spec(o) for o in range(-h, h + 1)]


def _fwd_kernel(h, idx_ref, *refs):
    from jax.experimental import pallas as pl

    w = 2 * h + 1
    parts = len(refs) // (w + 1)
    x_refs, out_refs = refs[: parts * w], refs[parts * w :]
    windows = [
        jnp.concatenate([r[...] for r in x_refs[p * w : (p + 1) * w]], axis=0)
        for p in range(parts)
    ]  # [w B, D] each
    # the table row each window row stands for. A block beyond either end
    # of the table was fetched clipped, but its numbers match no index
    rows = (pl.program_id(0) - h) * BLOCK + jax.lax.broadcasted_iota(
        jnp.int32, (BLOCK, w * BLOCK), 1
    )
    idx = idx_ref[...]  # [BLOCK, K]
    onehot = jnp.concatenate(
        [
            (idx[:, k : k + 1] == rows).astype(windows[0].dtype)
            for k in range(idx.shape[1])
        ],
        axis=0,
    )  # [K B, w B]: one product for every slot, summed over the window
    for window, out_ref in zip(windows, out_refs):
        out = jnp.dot(onehot, window, preferred_element_type=jnp.float32)
        out_ref[...] = out.astype(out_ref.dtype).reshape(out_ref.shape)


def _bwd_kernel(h, n_rows, k_in, *refs):
    from jax.experimental import pallas as pl

    w = 2 * h + 1
    parts = (len(refs) - w) // (w + 2)
    idx_refs, g_refs = refs[:w], refs[w : w + parts * w]
    out_refs = refs[w + parts * w : w + parts * w + parts]
    acc_refs = refs[w + parts * w + parts :]
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    tail = n_rows % BLOCK  # real rows of a ragged last block
    kc = g_refs[0].shape[0]  # slots a chunk (all K where the list is one)
    sub = jax.lax.broadcasted_iota(jnp.int32, (BLOCK, kc * BLOCK), 0)

    def zero():
        for acc_ref in acc_refs:
            acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate(o, ragged, slots):
        # the last chunk, where Kc does not divide K, reads its real slots
        # alone: the rest of its block lies past the array's end, where
        # whatever the buffer held (a NaN) would spread over the block
        gs = []
        for p in range(parts):
            g_ref = g_refs[p * w + o + h]  # [Kc, BLOCK receivers, D]
            g = g_ref[...] if slots == kc else g_ref[:slots]
            if ragged:
                # rows past the array's end hold whatever the buffer held:
                # a product would spread a NaN there over the block
                row = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)
                g = jnp.where(row < tail, g, 0)
            gs.append(g)
        # [1, slots BLOCK]: lanes = (slot, receiver)
        if slots == kc:
            senders, rows = idx_refs[o + h][...] - b * BLOCK, sub
        else:  # an iota of its own: the compiler slices none along lanes
            senders = idx_refs[o + h][:, : slots * BLOCK] - b * BLOCK
            rows = jax.lax.broadcasted_iota(
                jnp.int32, (BLOCK, slots * BLOCK), 0
            )
        onehot_t = (senders == rows).astype(gs[0].dtype)
        for g, acc_ref in zip(gs, acc_refs):
            acc_ref[...] += jnp.dot(
                onehot_t, g.reshape(slots * BLOCK, g.shape[2]),
                preferred_element_type=jnp.float32,
            )

    def window(slots):
        for o in range(-h, h + 1):
            blk = b + o
            inside = (blk >= 0) & (blk < nb)
            if tail:
                pl.when(inside & (blk < nb - 1))(
                    functools.partial(accumulate, o, False, slots)
                )
                pl.when(blk == nb - 1)(
                    functools.partial(accumulate, o, True, slots)
                )
            else:
                pl.when(inside)(functools.partial(accumulate, o, False, slots))

    def write():
        for out_ref, acc_ref in zip(out_refs, acc_refs):
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    nk = _cdiv(k_in, kc)
    if nk == 1:
        zero()
        window(kc)
        write()
        return
    # the chunks are the grid's inner axis: the accumulator holds a block
    # of senders over them, zeroed at the first and written at the last
    c = pl.program_id(1)
    last = k_in - (nk - 1) * kc  # real slots of the last chunk
    pl.when(c == 0)(zero)
    if last == kc:
        window(kc)
    else:
        pl.when(c < nk - 1)(functools.partial(window, kc))
        pl.when(c == nk - 1)(functools.partial(window, last))
    pl.when(c == nk - 1)(write)


def _pad_lanes(a):
    """Last axis up to a multiple of 128 lanes (PNA's first layer gathers
    a table of ONE feature)."""
    pad = -a.shape[-1] % _LANES
    if pad:
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])
    return a


def lane_width(*dims: int) -> int:
    """Lanes that tables of these widths take side by side, each padded
    to whole tiles of 128: the ``dim`` :func:`window_halo` counts."""
    return sum(_cdiv(d, _LANES) for d in dims) * _LANES


def gather_product(x, nbr_idx, h: int, interpret: bool = False):
    """``x[nbr_idx]`` as ``[K, N, D]`` (slot-major), for lists whose
    senders lie within ``h`` blocks of their receivers. ``x``: one table,
    or a tuple of tables of one dtype gathered through the same lists
    (ONE 0/1 matrix a block, a product a table; a tuple comes back): each
    keeps its own lane tiles, so a table of whole tiles is read where it
    lies and its rows are written where they are used, with no copy to
    join or part them."""
    given = x if isinstance(x, tuple) else (x,)
    out = _gather_call(
        tuple(_pad_lanes(p) for p in given), nbr_idx, h, interpret
    )
    out = tuple(o[..., : p.shape[1]] for o, p in zip(out, given))
    return out if isinstance(x, tuple) else out[0]


# The kernel calls take lane-padded tables and are jit bodies of their own:
# calls that differ only in what the padding hides (9 pieces or 12) share
# ONE lowered kernel, and lowering a kernel is what set-up pays most for
# (0.05-0.12 s each in the sandbox; PERF.md section 6, PR 29).


@functools.partial(jax.jit, static_argnums=(2, 3))
def _gather_call(given, nbr_idx, h, interpret):
    from jax.experimental import pallas as pl

    n = given[0].shape[0]
    k = nbr_idx.shape[1]
    nb = _cdiv(n, BLOCK)
    w = 2 * h + 1
    # whole blocks of the tables and of the lists: no product meets a row
    # that was never written (a table is small beside its result)
    rows = nb * BLOCK - n
    idx = jnp.pad(nbr_idx, ((0, rows), (0, 0)))
    parts = [jnp.pad(p, ((0, rows), (0, 0))) for p in given]
    need, _ = _vmem_bytes(k, h, sum(p.shape[1] for p in parts) // _LANES)
    # numlint: disable=pallas-vmem-unbounded — gated by window_halo above
    return pl.pallas_call(
        functools.partial(_fwd_kernel, h),
        grid=(nb,),
        in_specs=[pl.BlockSpec((BLOCK, k), lambda b: (b, 0))]
        + [
            spec for p in parts
            for spec in _window_specs((BLOCK, p.shape[1]), 0, h, nb - 1)
        ],
        out_specs=[
            pl.BlockSpec((k, BLOCK, p.shape[1]), lambda b: (0, b, 0))
            for p in parts
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, n, p.shape[1]), p.dtype) for p in parts
        ],
        compiler_params=_compiler_params(need),
        interpret=interpret,
        name="gather_neighbors_onehot",
    )(idx, *(p for p in parts for _ in range(w)))


def scatter_product(
    g, nbr_idx, nbr_mask, h: int, interpret: bool = False, out_dtype=None
):
    """The transpose of :func:`gather_product`: ``gx[s] = sum of g[k, r]
    over the real slots (r, k) that name sender s``, from a slot-major
    ``g [K, N, D]`` (or a tuple of such, as there); f32 accumulation, one
    cast to ``out_dtype`` (one, or one per part; None: the part's own;
    f32 hands the accumulator over as it is)."""
    given = g if isinstance(g, tuple) else (g,)
    if not isinstance(out_dtype, tuple):
        out_dtype = (out_dtype,) * len(given)
    parts = tuple(_pad_lanes(p) for p in given)
    nk = slot_chunks(given[0].shape[0], h, sum(p.shape[2] for p in parts))
    sums = _scatter_call(
        parts, nbr_idx, nbr_mask, h, nk, interpret,
        tuple(jnp.dtype(d or p.dtype) for d, p in zip(out_dtype, given)),
    )
    sums = tuple(s[:, : p.shape[2]] for s, p in zip(sums, given))
    return sums if isinstance(g, tuple) else sums[0]


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _scatter_call(parts, nbr_idx, nbr_mask, h, nk, interpret, out_dtypes):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k, n, _ = parts[0].shape
    nb = _cdiv(n, BLOCK)
    w = 2 * h + 1
    kc = _cdiv(k, nk)  # slots a chunk
    # the mask folded into the lists (-1 names no sender), whole blocks,
    # then block-major: one row of K x BLOCK (slot, receiver) per block,
    # whole chunks of it
    senders = jnp.pad(
        jnp.where(nbr_mask, nbr_idx, -1),
        ((0, nb * BLOCK - n), (0, nk * kc - k)), constant_values=-1,
    )
    senders = senders.reshape(nb, BLOCK, nk * kc).transpose(0, 2, 1)
    senders = senders.reshape(nb, 1, nk * kc * BLOCK)
    need = _bwd_bytes(kc, h, sum(p.shape[2] for p in parts) // _LANES)
    chunked = nk > 1
    # numlint: disable=pallas-vmem-unbounded — gated by window_halo above
    return pl.pallas_call(
        functools.partial(_bwd_kernel, h, n, k),
        grid=(nb, nk) if chunked else (nb,),
        in_specs=_window_specs(
            (None, 1, kc * BLOCK), 0, h, nb - 1, 2 if chunked else None
        )
        + [
            spec for p in parts
            for spec in _window_specs(
                (kc, BLOCK, p.shape[2]), 1, h, nb - 1, 0 if chunked else None
            )
        ],
        out_specs=[
            pl.BlockSpec((BLOCK, p.shape[2]), lambda b, *_: (b, 0))
            for p in parts
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, p.shape[2]), d)
            for p, d in zip(parts, out_dtypes)
        ],
        scratch_shapes=[
            pltpu.VMEM((BLOCK, p.shape[2]), jnp.float32) for p in parts
        ],
        compiler_params=_compiler_params(need, chunked),
        interpret=interpret,
        name="gather_neighbors_onehot_bwd",
    )(*([senders] * w), *(p for p in parts for _ in range(w)))
