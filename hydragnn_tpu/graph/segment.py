"""Segment reductions — the substrate of message passing on TPU.

The reference's conv stacks lean on torch_scatter/torch_sparse CUDA kernels
(SURVEY.md §2.4). On TPU the idiomatic equivalent is ``jax.ops.segment_sum``
and friends: XLA lowers them to sorted-scatter programs it can fuse with the
surrounding elementwise work, keeping everything in registers/VMEM instead of
bouncing through HBM.

All ops take static ``num_segments`` (XLA needs static output shapes) and are
safe under padding: padded edges must carry zeroed data or be masked by the
caller; padded segments simply produce the reduction identity.
"""

import jax
import jax.numpy as jnp

_BIG = 1e9  # sentinel for min/max identities; float32-safe
# every op of a segment reduction (and of its transpose) carries this name
# in a device trace; a reduction built from another nests the name
_scope = jax.named_scope("agg_segment")


@_scope
def segment_sum(data, segment_ids, num_segments):
    # scatter-adds in sub-f32 dtypes are pathologically slow on TPU (measured
    # 14x on v5e under bf16 mixed precision) AND lose accumulation precision;
    # run the reduction in f32, hand back the caller's dtype
    in_dtype = data.dtype
    if in_dtype in (jnp.bfloat16, jnp.float16):
        data = data.astype(jnp.float32)
    out = jax.ops.segment_sum(data, segment_ids, num_segments=num_segments)
    return out.astype(in_dtype) if out.dtype != in_dtype else out


@_scope
def segment_count(segment_ids, num_segments, weights=None):
    """Number of elements per segment (in-degree when ids are edge receivers)."""
    ones = (
        jnp.ones(segment_ids.shape[0], dtype=jnp.float32)
        if weights is None
        else weights
    )
    return jax.ops.segment_sum(ones, segment_ids, num_segments=num_segments)


@_scope
def segment_mean(data, segment_ids, num_segments):
    total = segment_sum(data, segment_ids, num_segments)
    count = segment_count(segment_ids, num_segments)
    count = jnp.maximum(count, 1.0)
    return total / count.reshape((-1,) + (1,) * (data.ndim - 1))


@_scope
def segment_max(data, segment_ids, num_segments, fill=0.0, has=None):
    """Max per segment; empty segments get ``fill`` (reference semantics: padded
    nodes should see 0, not -inf, so downstream matmuls stay finite).

    ``has``: optional precomputed [num_segments]-ish non-empty mask — callers
    that already ran a counting scatter (PNA's fused moments pass) supply it
    to avoid a redundant segment_count scatter."""
    out = jax.ops.segment_max(data, segment_ids, num_segments=num_segments)
    if has is None:
        has = segment_count(segment_ids, num_segments) > 0
    has = has.reshape((-1,) + (1,) * (data.ndim - 1))
    return jnp.where(has, jnp.where(jnp.isfinite(out), out, fill), fill)


@_scope
def segment_min(data, segment_ids, num_segments, fill=0.0, has=None):
    out = jax.ops.segment_min(data, segment_ids, num_segments=num_segments)
    if has is None:
        has = segment_count(segment_ids, num_segments) > 0
    has = has.reshape((-1,) + (1,) * (data.ndim - 1))
    return jnp.where(has, jnp.where(jnp.isfinite(out), out, fill), fill)


@_scope
def segment_minmax_fused(data, segment_ids, num_segments, fill=0.0, has=None):
    """(min, max) per segment from ONE scatter pass.

    Packs ``[data, -data]`` on the feature axis so a single segment-max
    scatter yields both extremes (max of ``-data`` is ``-min``). At
    small-graph batch shapes the scatter PASS, not the flops, is the cost
    (measured ~0.5 ms/pass on v5e at E=18k, D=64) — PNA runs this instead
    of separate min/max scatters.
    """
    d = data.shape[1]
    packed = jnp.concatenate([data, -data], axis=-1)
    out = jax.ops.segment_max(packed, segment_ids, num_segments=num_segments)
    if has is None:
        has = segment_count(segment_ids, num_segments) > 0
    has = has.reshape((-1,) + (1,) * (data.ndim - 1))
    mx_raw = out[:, :d]
    mn_raw = -out[:, d:]
    mx = jnp.where(has, jnp.where(jnp.isfinite(mx_raw), mx_raw, fill), fill)
    mn = jnp.where(has, jnp.where(jnp.isfinite(mn_raw), mn_raw, fill), fill)
    return mn, mx


@_scope
def segment_std(data, segment_ids, num_segments, eps=1e-5):
    """Per-segment standard deviation, PNA-style: sqrt(relu(E[x^2]-E[x]^2)+eps).

    Matches PyG PNAConv's ``std`` aggregator numerics (reference uses it via
    ``models/PNAStack.py:28``) so degree-scaler statistics line up.
    """
    mean = segment_mean(data, segment_ids, num_segments)
    mean_sq = segment_mean(data * data, segment_ids, num_segments)
    var = jax.nn.relu(mean_sq - mean * mean)
    return jnp.sqrt(var + eps)


@_scope
def segment_moments_fused(data, segment_ids, num_segments, weights=None):
    """(sum, count, sum_of_squares) per segment from ONE scatter pass.

    Packs data / data^2 / count-weights on the feature axis so a single
    segment scatter produces all three statistics (scatter passes, not
    flops, are the hot cost at small-graph scale — measured on v5e,
    bench.py).
    ``weights``: optional [E] count weights (e.g. an edge mask).
    """
    d = data.shape[1]
    w = (
        jnp.ones((data.shape[0],), jnp.float32)
        if weights is None
        else weights.astype(jnp.float32)
    )
    packed = jnp.concatenate([data, data * data, w[:, None]], axis=-1)
    s = segment_sum(packed, segment_ids, num_segments)
    return s[:, :d], s[:, -1:], s[:, d : 2 * d]


@_scope
def segment_softmax_unnorm(logits, segment_ids, num_segments, mask=None):
    """Masked, max-shifted ``exp`` — the stable-softmax numerator terms.

    Shared prologue of :func:`segment_softmax` and fused-attention callers
    (GAT) that fold the normalizer into their aggregation scatter: returns
    ``exp(logits - segmax)`` with padded elements exactly zero, so
    ``segment_sum`` of the result is the softmax denominator.
    """
    if mask is not None:
        m = mask.reshape(mask.shape + (1,) * (logits.ndim - 1))
        logits = jnp.where(m, logits, -_BIG)
    seg_max = jax.ops.segment_max(logits, segment_ids, num_segments=num_segments)
    seg_max = jnp.where(jnp.isfinite(seg_max), seg_max, 0.0)
    unnorm = jnp.exp(logits - seg_max[segment_ids])
    if mask is not None:
        m = mask.reshape(mask.shape + (1,) * (logits.ndim - 1))
        unnorm = jnp.where(m, unnorm, 0.0)
    return unnorm


@_scope
def segment_softmax(logits, segment_ids, num_segments, mask=None):
    """Numerically-stable softmax within segments (GAT edge attention).

    ``mask`` (bool over elements) zeroes out padded edges so they contribute
    neither to the max nor the normalizer.
    """
    unnorm = segment_softmax_unnorm(logits, segment_ids, num_segments, mask)
    denom = segment_sum(unnorm, segment_ids, num_segments)
    denom = jnp.maximum(denom, 1e-16)
    return unnorm / denom[segment_ids]
