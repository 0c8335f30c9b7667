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

:func:`window_halo` is the rule that selects the product; everything it
reads is a property of the operands. ``ops/dense_agg.py
gather_neighbors`` is the one caller.
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

    x_refs, out_ref = refs[:-1], refs[-1]
    w = 2 * h + 1
    window = jnp.concatenate([r[...] for r in x_refs], axis=0)  # [w B, D]
    # the table row each window row stands for. A block beyond either end
    # of the table was fetched clipped, but its numbers match no index
    rows = (pl.program_id(0) - h) * BLOCK + jax.lax.broadcasted_iota(
        jnp.int32, (BLOCK, w * BLOCK), 1
    )
    idx = idx_ref[...]  # [BLOCK, K]
    onehot = jnp.concatenate(
        [
            (idx[:, k : k + 1] == rows).astype(window.dtype)
            for k in range(idx.shape[1])
        ],
        axis=0,
    )  # [K B, w B]: one product for every slot, summed over the window
    out = jnp.dot(onehot, window, preferred_element_type=jnp.float32)
    out_ref[...] = out.astype(out_ref.dtype).reshape(out_ref.shape)


def _bwd_kernel(h, n_rows, *refs):
    from jax.experimental import pallas as pl

    w = 2 * h + 1
    idx_refs, g_refs = refs[:w], refs[w : 2 * w]
    out_ref, acc_ref = refs[2 * w], refs[2 * w + 1]
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    tail = n_rows % BLOCK  # real rows of a ragged last block
    k_in = g_refs[0].shape[0]
    sub = jax.lax.broadcasted_iota(jnp.int32, (BLOCK, k_in * BLOCK), 0)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate(o, ragged):
        g = g_refs[o + h][...]  # [K, BLOCK receivers, D]
        if ragged:
            # rows past the array's end hold whatever the buffer held:
            # a product would spread a NaN there over the block
            row = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)
            g = jnp.where(row < tail, g, 0)
        # [1, K BLOCK]: lanes = (slot, receiver)
        senders = idx_refs[o + h][...] - b * BLOCK
        onehot_t = (senders == sub).astype(g.dtype)
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
    out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _pad_lanes(a):
    """Last axis up to a multiple of 128 lanes (PNA's first layer gathers
    a table of ONE feature)."""
    pad = -a.shape[-1] % _LANES
    if pad:
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])
    return a


def gather_product(x, nbr_idx, h: int, interpret: bool = False):
    """``x[nbr_idx]`` as ``[K, N, D]`` (slot-major), for lists whose
    senders lie within ``h`` blocks of their receivers."""
    from jax.experimental import pallas as pl

    n, d = x.shape
    k = nbr_idx.shape[1]
    nb = _cdiv(n, BLOCK)
    # whole blocks of the table and of the lists: no product meets a row
    # that was never written (the table is small beside the result)
    rows = nb * BLOCK - n
    xp = jnp.pad(_pad_lanes(x), ((0, rows), (0, 0)))
    idx = jnp.pad(nbr_idx, ((0, rows), (0, 0)))
    dp = xp.shape[1]
    # numlint: disable=pallas-vmem-unbounded — gated by window_halo above
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, h),
        grid=(nb,),
        in_specs=[pl.BlockSpec((BLOCK, k), lambda b: (b, 0))]
        + _window_specs((BLOCK, dp), 0, h, nb - 1),
        out_specs=pl.BlockSpec((k, BLOCK, dp), lambda b: (0, b, 0)),
        out_shape=jax.ShapeDtypeStruct((k, n, dp), x.dtype),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="gather_neighbors_onehot",
    )(idx, *([xp] * (2 * h + 1)))
    return out[..., :d]


def scatter_product(g, nbr_idx, nbr_mask, h: int, interpret: bool = False):
    """The transpose of :func:`gather_product`: ``gx[s] = sum of g[k, r]
    over the real slots (r, k) that name sender s``, from a slot-major
    cotangent ``g [K, N, D]``; f32 accumulation, one cast."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k, n, d = g.shape
    nb = _cdiv(n, BLOCK)
    gp = _pad_lanes(g)
    dp = gp.shape[2]
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
    gx = pl.pallas_call(
        functools.partial(_bwd_kernel, h, n),
        grid=(nb,),
        in_specs=_window_specs((None, 1, k * BLOCK), 0, h, nb - 1)
        + _window_specs((k, BLOCK, dp), 1, h, nb - 1),
        out_specs=pl.BlockSpec((BLOCK, dp), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((n, dp), g.dtype),
        scratch_shapes=[pltpu.VMEM((BLOCK, dp), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="gather_neighbors_onehot_bwd",
    )(*([senders] * w), *([gp] * w))
    return gx[:, :d]
