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
  accumulated in f32 and cast once.

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
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp

BLOCK = 128  # receivers (senders) per grid step: the MXU's edge
_LANES = 128
# VMEM one grid step's blocks and 0/1 matrix may take (the scoped limit is
# raised to fit; a v5e core has 128 MiB)
_VMEM_BLOCK_BUDGET = 24 * 1024 * 1024
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
      (as far as the product was read ahead), and blocks and 0/1 matrix
      within the VMEM budget.
    """
    if reach is None or backend != "tpu" or dtype != jnp.bfloat16:
        return None
    h = _cdiv(max(int(reach), 1) - 1, BLOCK)
    w, lanes = 2 * h + 1, _cdiv(dim, _LANES)
    if w * lanes > MAX_WINDOW_TILES:
        return None
    slab = k_in * BLOCK * lanes * _LANES * 2  # one [k_in, BLOCK, D] block
    # backward: 2h+1 blocks of cotangents, double-buffered. Forward: the
    # result block (double-buffered), its f32 product, and the [k_in
    # BLOCK, w BLOCK] 0/1 matrix
    backward = 2 * w * slab
    forward = 4 * slab + k_in * BLOCK * w * BLOCK * 2
    if max(backward, forward) > _VMEM_BLOCK_BUDGET:
        return None
    return h


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel",),
        vmem_limit_bytes=_VMEM_BLOCK_BUDGET + 8 * 1024 * 1024,
    )


def _window_specs(block, axis: int, h: int, last: int):
    """One BlockSpec per block ``b - h .. b + h`` of the window along
    ``axis``, each held inside the array (what a block beyond either end
    fetches instead is never used: see the kernels)."""
    from jax.experimental import pallas as pl

    def spec(offset):
        def index(b):
            at = [0] * len(block)
            at[axis] = jnp.clip(b + offset, 0, last)
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


def _bwd_kernel(h, n_rows, *refs):
    from jax.experimental import pallas as pl

    w = 2 * h + 1
    parts = (len(refs) - w) // (w + 2)
    idx_refs, g_refs = refs[:w], refs[w : w + parts * w]
    out_refs = refs[w + parts * w : w + parts * w + parts]
    acc_refs = refs[w + parts * w + parts :]
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    tail = n_rows % BLOCK  # real rows of a ragged last block
    k_in = g_refs[0].shape[0]
    sub = jax.lax.broadcasted_iota(jnp.int32, (BLOCK, k_in * BLOCK), 0)
    for acc_ref in acc_refs:
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate(o, ragged):
        gs = []
        for p in range(parts):
            g = g_refs[p * w + o + h][...]  # [K, BLOCK receivers, D]
            if ragged:
                # rows past the array's end hold whatever the buffer held:
                # a product would spread a NaN there over the block
                row = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)
                g = jnp.where(row < tail, g, 0)
            gs.append(g)
        # [1, K BLOCK]: lanes = (slot, receiver)
        senders = idx_refs[o + h][...] - b * BLOCK
        onehot_t = (senders == sub).astype(gs[0].dtype)
        for g, acc_ref in zip(gs, acc_refs):
            acc_ref[...] += jnp.dot(
                onehot_t, g.reshape(k_in * BLOCK, g.shape[2]),
                preferred_element_type=jnp.float32,
            )

    for o in range(-h, h + 1):
        blk = b + o
        inside = (blk >= 0) & (blk < nb)
        if tail:
            pl.when(inside & (blk < nb - 1))(
                functools.partial(accumulate, o, False)
            )
            pl.when(blk == nb - 1)(functools.partial(accumulate, o, True))
        else:
            pl.when(inside)(functools.partial(accumulate, o, False))
    for out_ref, acc_ref in zip(out_refs, acc_refs):
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


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
        compiler_params=_compiler_params(),
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
    sums = _scatter_call(
        tuple(_pad_lanes(p) for p in given), nbr_idx, nbr_mask, h, interpret,
        tuple(jnp.dtype(d or p.dtype) for d, p in zip(out_dtype, given)),
    )
    sums = tuple(s[:, : p.shape[2]] for s, p in zip(sums, given))
    return sums if isinstance(g, tuple) else sums[0]


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _scatter_call(parts, nbr_idx, nbr_mask, h, interpret, out_dtypes):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k, n, _ = parts[0].shape
    nb = _cdiv(n, BLOCK)
    w = 2 * h + 1
    # the mask folded into the lists (-1 names no sender), whole blocks,
    # then block-major: one row of K x BLOCK (slot, receiver) per block
    senders = jnp.pad(
        jnp.where(nbr_mask, nbr_idx, -1),
        ((0, nb * BLOCK - n), (0, 0)), constant_values=-1,
    )
    senders = senders.reshape(nb, BLOCK, k).transpose(0, 2, 1)
    senders = senders.reshape(nb, 1, k * BLOCK)
    # numlint: disable=pallas-vmem-unbounded — gated by window_halo above
    return pl.pallas_call(
        functools.partial(_bwd_kernel, h, n),
        grid=(nb,),
        in_specs=_window_specs((None, 1, k * BLOCK), 0, h, nb - 1)
        + [
            spec for p in parts
            for spec in _window_specs((k, BLOCK, p.shape[2]), 1, h, nb - 1)
        ],
        out_specs=[
            pl.BlockSpec((BLOCK, p.shape[2]), lambda b: (b, 0)) for p in parts
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, p.shape[2]), d)
            for p, d in zip(parts, out_dtypes)
        ],
        scratch_shapes=[
            pltpu.VMEM((BLOCK, p.shape[2]), jnp.float32) for p in parts
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="gather_neighbors_onehot_bwd",
    )(*([senders] * w), *(p for p in parts for _ in range(w)))
