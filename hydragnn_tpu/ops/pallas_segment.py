"""Pallas TPU kernels for the message-passing aggregation hot path.

The reference's aggregation runs on torch_scatter CUDA kernels
(SURVEY.md §2.4). On TPU, XLA lowers ``jax.ops.segment_*`` to scatter-adds,
which serialize on duplicate indices and re-read the ``[E, D]`` message
array once per requested statistic — PNA wants mean, std AND the degree
count, i.e. three passes over HBM.

These kernels make aggregation MXU work instead of scatter work: the output
``[N, D]`` accumulator lives in VMEM across the whole grid; each step loads
one block of edges and accumulates ``onehot(receivers)^T @ messages`` — a
dense matmul the systolic array eats — so the messages are read from HBM
exactly ONCE. ``segment_moments`` produces sum, count and sum-of-squares in
that single pass (mean/std/degree all derive from it).

Enablement: ``HYDRAGNN_PALLAS=1`` opts in (with the VMEM-budget guard
below), ``0``/unset keeps the XLA path. The one end-to-end comparison on a
v5e (2026-07-30, PNA multihead, ~4.6k nodes / ~18k edges / dim 64, before
this tree) was a dead heat, 4.44 ms/step against XLA scatter's 4.45: the
moments kernel replaces only one of the remaining scatter passes, and XLA
fuses its scatter with the surrounding elementwise work — a fusion the
opaque pallas_call boundary forfeits — so the default stays OFF. Not
measured on the current tree (ROADMAP D1). Gradients are provided via
custom VJPs (gather-based, XLA-fused).
"""

import functools
import os

import jax
import jax.numpy as jnp

_EDGE_BLOCK = 256
_VMEM_ACC_BUDGET = 6 * 1024 * 1024  # bytes of VMEM we allow the accumulators


def pallas_segments_enabled(num_segments: int, dim: int, n_outputs: int = 1):
    """Decide kernel vs XLA fallback for a [num_segments, dim] accumulation.

    On via ``HYDRAGNN_PALLAS=1`` or the autotuner's family force
    ``HYDRAGNN_AGG=fused`` (``ops/autotune.py``): forcing the fused
    message-passing family also turns on the one-hot segment kernels at
    the sites the fused ops don't cover, so an A/B flips the whole tree.

    Budget covers everything the kernel keeps resident in VMEM: the
    accumulators AND the per-block ``[_EDGE_BLOCK, num_segments]`` one-hot
    indicator (at 16k+ segments the indicator alone exceeds the 16 MB VMEM
    scoped limit — observed as a compile-time VMEM OOM on the giant-graph
    partition config before this guard included it)."""
    from hydragnn_tpu.ops.autotune import emit_choice, env_force

    if os.getenv("HYDRAGNN_PALLAS", "0") != "1" and env_force() != "fused":
        return False
    acc_bytes = n_outputs * num_segments * max(dim, 1) * 4
    onehot_bytes = _EDGE_BLOCK * num_segments * 4
    fits = acc_bytes + onehot_bytes <= _VMEM_ACC_BUDGET
    # the kernels were asked for: report whether this site got one or the
    # guard sent it to XLA (the agg_choice contract of ops/autotune.py)
    emit_choice(
        f"onehot/n{num_segments}/d{dim}x{n_outputs}",
        "fused" if fits else "segment",
        "env" if fits else "guard",
    )
    return fits


@functools.lru_cache(maxsize=None)
def on_tpu() -> bool:
    """Decided ONCE from the platform of the devices in use. No fallback:
    on a TPU a requested kernel compiles or the run fails."""
    return jax.devices()[0].platform == "tpu"


def _interpret(requested: bool) -> bool:
    """Compiled pallas is TPU-only; other platforms run the interpreter (so
    HYDRAGNN_PALLAS=1 is testable on CPU)."""
    return requested or not on_tpu()


def _pad_edges(data, segment_ids, block):
    """Pad the edge axis to a block multiple; padded ids point past the last
    segment so their one-hot row is all zeros (no contribution).

    ids are returned as ``[E, 1]`` — 1-D i32 operands get XLA's T(1024)
    layout, which Mosaic cannot block at the edge-block size; the 2-D shape
    tiles conventionally (verified on v5e)."""
    e = data.shape[0]
    pad = (-e) % block
    if pad:
        data = jnp.pad(data, ((0, pad), (0, 0)))
        segment_ids = jnp.pad(
            segment_ids, (0, pad), constant_values=jnp.iinfo(jnp.int32).max
        )
    return data, segment_ids.reshape(-1, 1)


def _onehot(ids_block, num_segments):
    """[E_blk, N] float32 indicator from [E_blk, 1] ids; out-of-range ids
    give a zero row."""
    cols = jax.lax.broadcasted_iota(jnp.int32, (ids_block.shape[0], num_segments), 1)
    return (ids_block == cols).astype(jnp.float32)


# ---------------------------------------------------------------------------
# segment_sum
# ---------------------------------------------------------------------------

def _sum_kernel(ids_ref, data_ref, out_ref):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    onehot = _onehot(ids_ref[:], out_ref.shape[0])
    out_ref[:] += jax.lax.dot_general(
        onehot, data_ref[:],
        dimension_numbers=(((0,), (0,)), ((), ())),  # onehot^T @ data
        preferred_element_type=jnp.float32,
    )


def _segment_sum_fwd_impl(data, segment_ids, num_segments, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    interpret = _interpret(interpret)
    data = data.astype(jnp.float32)
    data, ids = _pad_edges(data, segment_ids.astype(jnp.int32), _EDGE_BLOCK)
    e_pad, dim = data.shape
    grid = e_pad // _EDGE_BLOCK
    return pl.pallas_call(
        _sum_kernel,
        out_shape=jax.ShapeDtypeStruct((num_segments, dim), jnp.float32),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((_EDGE_BLOCK, 1), lambda i: (i, 0)),
            pl.BlockSpec((_EDGE_BLOCK, dim), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((num_segments, dim), lambda i: (0, 0)),
        interpret=interpret,
    )(ids, data)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def segment_sum_onehot(data, segment_ids, num_segments, interpret=False):
    """Pallas segment-sum: ``out[n] = sum_{e: ids[e]==n} data[e]``.

    ``data`` must be 2-D ``[E, D]``. Same contract as
    ``jax.ops.segment_sum`` with static ``num_segments``.
    """
    return _segment_sum_fwd_impl(data, segment_ids, num_segments, interpret)


def _segment_sum_fwd(data, segment_ids, num_segments, interpret):
    out = _segment_sum_fwd_impl(data, segment_ids, num_segments, interpret)
    return out, (segment_ids, data.shape[0])


def _segment_sum_bwd(num_segments, interpret, res, g):
    segment_ids, _ = res
    # d/d_data = g gathered at each edge's segment. Out-of-range ids (the
    # kernels' padded-edge contract: they contribute nothing forward) must
    # get exactly ZERO gradient — a bare g[ids] would clamp-gather the
    # last segment's cotangent onto them.
    valid = (segment_ids >= 0) & (segment_ids < num_segments)
    safe = jnp.clip(segment_ids, 0, num_segments - 1)
    return jnp.where(valid[:, None], g[safe], 0.0), None


segment_sum_onehot.defvjp(_segment_sum_fwd, _segment_sum_bwd)


# ---------------------------------------------------------------------------
# segment_moments: sum / count / sum-of-squares in ONE pass
# ---------------------------------------------------------------------------

def _moments_kernel(ids_ref, data_ref, sum_ref, cnt_ref, sq_ref):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _():
        sum_ref[:] = jnp.zeros_like(sum_ref)
        cnt_ref[:] = jnp.zeros_like(cnt_ref)
        sq_ref[:] = jnp.zeros_like(sq_ref)

    data = data_ref[:]
    onehot = _onehot(ids_ref[:], sum_ref.shape[0])
    tdot = functools.partial(
        jax.lax.dot_general,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    sum_ref[:] += tdot(onehot, data)
    sq_ref[:] += tdot(onehot, data * data)
    cnt_ref[:] += jnp.sum(onehot, axis=0, keepdims=True).T


def _moments_impl(data, segment_ids, num_segments, interpret=False):
    from jax.experimental import pallas as pl

    interpret = _interpret(interpret)
    data = data.astype(jnp.float32)
    data, ids = _pad_edges(data, segment_ids.astype(jnp.int32), _EDGE_BLOCK)
    e_pad, dim = data.shape
    grid = e_pad // _EDGE_BLOCK
    return pl.pallas_call(
        _moments_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((num_segments, dim), jnp.float32),
            jax.ShapeDtypeStruct((num_segments, 1), jnp.float32),
            jax.ShapeDtypeStruct((num_segments, dim), jnp.float32),
        ),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((_EDGE_BLOCK, 1), lambda i: (i, 0)),
            pl.BlockSpec((_EDGE_BLOCK, dim), lambda i: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((num_segments, dim), lambda i: (0, 0)),
            pl.BlockSpec((num_segments, 1), lambda i: (0, 0)),
            pl.BlockSpec((num_segments, dim), lambda i: (0, 0)),
        ),
        interpret=interpret,
    )(ids, data)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def segment_moments(data, segment_ids, num_segments, interpret=False):
    """(sum, count, sum_of_squares) per segment in one pass over the edges.

    mean = sum / max(count, 1); var = sq/count - mean^2 — the PNA aggregator
    statistics (``models/PNAStack.py:28-34`` in the reference) from a single
    HBM read of the messages.
    """
    return _moments_impl(data, segment_ids, num_segments, interpret)


def _moments_fwd(data, segment_ids, num_segments, interpret):
    out = _moments_impl(data, segment_ids, num_segments, interpret)
    return out, (data, segment_ids)


def _moments_bwd(num_segments, interpret, res, grads):
    data, segment_ids = res
    g_sum, _g_cnt, g_sq = grads  # count is piecewise constant: no gradient
    # same padded-edge contract as _segment_sum_bwd: out-of-range ids
    # contributed nothing forward, so they get zero gradient back
    valid = (segment_ids >= 0) & (segment_ids < num_segments)
    safe = jnp.clip(segment_ids, 0, num_segments - 1)
    d_data = g_sum[safe] + 2.0 * data * g_sq[safe]
    return jnp.where(valid[:, None], d_data, 0.0), None


segment_moments.defvjp(_moments_fwd, _moments_bwd)
