"""The segment path's substrate (``graph/segment.py``) and the three gather
helpers of ``models/common.py``, against plain NumPy loops.

Every public reduction under each layout a padded batch produces: all edges
real; a padded tail aimed at the padding segment (zeroed data, zero weights
or ``mask``); segments with no edge; bf16 input (f32 accumulation). Then
gradients against hand-written VJPs under the same layouts.
"""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.graph import segment as S
from hydragnn_tpu.models import common

E, N, D = 120, 32, 8
PAD = 17  # padded edges of the "padded" layout
LIVE = 20  # segments [LIVE, N) have no edge in the "empty" layout
LONG = 400  # edges of the bf16 layout's segment 0: past bf16's 256
F32 = dict(rtol=1e-5, atol=1e-6)


def _case(layout, seed=0):
    """``data [E, D]``, ``ids [E]``, ``mask [E]`` (real edges) of one layout.
    Padded edges point at segment ``N - 1`` and carry zeroed data. A segment
    that has an edge has two or more: the variance of one value is a
    difference of equal f32 numbers, and ``segment_std`` takes its root."""
    rng = np.random.default_rng(seed)
    data = rng.standard_normal((E, D)).astype(np.float32)
    mask = np.ones(E, bool)

    def twice_then_random(lo, hi, count):
        each = np.tile(np.arange(lo, hi), 2)
        return np.concatenate([each, rng.integers(lo, hi, count - each.size)])

    if layout == "real":  # no segment is empty
        ids = twice_then_random(0, N, E)
    elif layout == "padded":
        ids = twice_then_random(0, N - 1, E)
        ids[-PAD:] = N - 1
        mask[-PAD:] = False
        data[-PAD:] = 0.0
    elif layout == "empty":
        ids = twice_then_random(0, LIVE, E)
    else:
        assert layout == "bf16"
        # segment 0 takes LONG ones: a bf16 accumulator would stall at 256
        data = np.concatenate([np.ones((LONG, D), np.float32), data])
        ids = np.concatenate([np.zeros(LONG, int), twice_then_random(1, N, E)])
        mask = np.ones(E + LONG, bool)
        data = data.astype(jnp.bfloat16).astype(np.float32)
    return SimpleNamespace(
        layout=layout, data=data, ids=ids.astype(np.int32), mask=mask
    )


# ---- NumPy loop references --------------------------------------------------


def ref_sum(data, ids, n):
    out = np.zeros((n,) + data.shape[1:], np.float64)
    for e, s in enumerate(ids):
        out[s] += data[e]
    return out


def ref_count(ids, n, weights=None):
    out = np.zeros(n, np.float64)
    for e, s in enumerate(ids):
        out[s] += 1.0 if weights is None else weights[e]
    return out


def ref_extreme(data, ids, n, pick, fill=0.0, has=None):
    out = np.full((n,) + data.shape[1:], fill, np.float64)
    seen = np.zeros(n, bool)
    for e, s in enumerate(ids):
        out[s] = data[e] if not seen[s] else pick(out[s], data[e])
        seen[s] = True
    if has is not None:
        out[~np.asarray(has)] = fill
    return out


def ref_mean(data, ids, n):
    cnt = np.maximum(ref_count(ids, n), 1.0)
    return ref_sum(data, ids, n) / cnt[:, None]


def ref_std(data, ids, n, eps=1e-5):
    mean = ref_mean(data, ids, n)
    var = np.maximum(ref_mean(data * data, ids, n) - mean * mean, 0.0)
    return np.sqrt(var + eps)


def ref_softmax_unnorm(logits, ids, n, mask):
    out = np.zeros(logits.shape, np.float64)
    for s in range(n):
        rows = [e for e in range(len(ids)) if ids[e] == s and mask[e]]
        if rows:
            out[rows] = np.exp(logits[rows] - logits[rows].max(axis=0))
    return out


def ref_softmax(logits, ids, n, mask):
    un = ref_softmax_unnorm(logits, ids, n, mask)
    return un / np.maximum(ref_sum(un, ids, n), 1e-16)[ids]


# ---- forward: ten reductions x four layouts ---------------------------------
# each entry: (what the op returns for a case, the reference for that case);
# a "padded" case hands the op what a padded batch hands it


def _weights(c):
    return c.mask.astype(np.float32) if c.layout == "padded" else None


def _mask(c):
    return jnp.asarray(c.mask) if c.layout == "padded" else None


def _lift(c, x):
    """Logits from data: padded rows are NOT zeroed but raised by 50, so
    that only ``mask`` keeps them out."""
    return x + np.where(c.mask, 0.0, 50.0)[:, None].astype(x.dtype)


OPS = {
    "segment_sum": (
        lambda c, x: S.segment_sum(x, c.ids, N),
        lambda c: ref_sum(c.data, c.ids, N),
    ),
    "segment_count": (
        lambda c, x: S.segment_count(c.ids, N, weights=_weights(c)),
        lambda c: ref_count(c.ids, N, _weights(c)),
    ),
    "segment_mean": (
        lambda c, x: S.segment_mean(x, c.ids, N),
        lambda c: ref_mean(c.data, c.ids, N),
    ),
    "segment_max": (
        lambda c, x: S.segment_max(x, c.ids, N),
        lambda c: ref_extreme(c.data, c.ids, N, np.maximum),
    ),
    "segment_min": (
        lambda c, x: S.segment_min(x, c.ids, N),
        lambda c: ref_extreme(c.data, c.ids, N, np.minimum),
    ),
    "segment_minmax_fused": (
        lambda c, x: S.segment_minmax_fused(x, c.ids, N),
        lambda c: (
            ref_extreme(c.data, c.ids, N, np.minimum),
            ref_extreme(c.data, c.ids, N, np.maximum),
        ),
    ),
    "segment_std": (
        lambda c, x: S.segment_std(x, c.ids, N),
        lambda c: ref_std(c.data, c.ids, N),
    ),
    "segment_moments_fused": (
        lambda c, x: S.segment_moments_fused(x, c.ids, N, weights=_weights(c)),
        lambda c: (
            ref_sum(c.data, c.ids, N),
            ref_count(c.ids, N, _weights(c))[:, None],
            ref_sum(c.data * c.data, c.ids, N),
        ),
    ),
    "segment_softmax_unnorm": (
        lambda c, x: S.segment_softmax_unnorm(_lift(c, x), c.ids, N, _mask(c)),
        lambda c: ref_softmax_unnorm(_lift(c, c.data), c.ids, N, c.mask),
    ),
    "segment_softmax": (
        lambda c, x: S.segment_softmax(_lift(c, x), c.ids, N, _mask(c)),
        lambda c: ref_softmax(_lift(c, c.data), c.ids, N, c.mask),
    ),
}


def _tuple(out):
    return out if isinstance(out, tuple) else (out,)


def _assert_trees_close(got, want, power=1, **tol):
    got, want = _tuple(got), _tuple(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(g, np.float64) ** power, w**power, **tol
        )


def _power(op):
    """``segment_std`` is compared as a variance: E[x^2] - E[x]^2 is
    rounded absolutely, which the root of a small variance magnifies."""
    return 2 if op == "segment_std" else 1


@pytest.mark.parametrize("layout", ["real", "padded", "empty"])
@pytest.mark.parametrize("op", OPS)
def pytest_reduction_matches_numpy_loop(op, layout):
    c = _case(layout)
    run, ref = OPS[op]
    got = run(c, jnp.asarray(c.data))
    _assert_trees_close(got, ref(c), _power(op), **F32)
    first = np.asarray(_tuple(got)[0])
    if layout == "empty" and first.shape[0] == N:
        # the reduction identity, not +-inf and not the f32 sentinel
        tail = first[LIVE:]
        want = np.sqrt(1e-5) if op == "segment_std" else 0.0
        np.testing.assert_allclose(tail, want, rtol=1e-6)
    if layout == "padded" and op.startswith("segment_softmax"):
        assert np.all(first[-PAD:] == 0.0)  # masked edges weigh nothing


@pytest.mark.parametrize(
    "op", ["segment_max", "segment_min", "segment_minmax_fused"]
)
def pytest_extremes_honour_fill_and_has(op):
    """Empty segments read ``fill``; a caller's ``has`` (PNA hands over
    the non-empty mask of its moments pass) is taken at its word, also
    where padded edges did land on the segment."""
    c = _case("padded")
    has = ref_count(c.ids, N, c.mask.astype(np.float32)) > 0
    assert not has[N - 1] and has[: N - 1].all()
    got = getattr(S, op)(
        jnp.asarray(c.data), c.ids, N, fill=-3.0, has=jnp.asarray(has)
    )
    picks = {"segment_max": (np.maximum,), "segment_min": (np.minimum,),
             "segment_minmax_fused": (np.minimum, np.maximum)}[op]
    want = tuple(
        ref_extreme(c.data, c.ids, N, p, fill=-3.0, has=has) for p in picks
    )
    _assert_trees_close(got, want, **F32)
    for g in _tuple(got):
        assert np.all(np.asarray(g)[N - 1] == -3.0)


# results that come back in the input's dtype; the others divide by an f32
# count (mean, std) or pack f32 count weights beside the data (moments)
_KEEPS_DTYPE = {
    "segment_sum", "segment_max", "segment_min", "segment_minmax_fused",
    "segment_softmax_unnorm", "segment_softmax",
}


@pytest.mark.parametrize("op", [o for o in OPS if o != "segment_count"])
def pytest_bf16_input_accumulates_in_f32(op):
    c = _case("bf16")
    run, ref = OPS[op]
    got = _tuple(run(c, jnp.asarray(c.data, jnp.bfloat16)))
    if op in _KEEPS_DTYPE:
        assert all(g.dtype == jnp.bfloat16 for g in got)
    if op in ("segment_sum", "segment_max", "segment_min",
              "segment_minmax_fused"):
        # the f32 result, rounded once
        f32 = _tuple(run(c, jnp.asarray(c.data, jnp.float32)))
        for g, f in zip(got, f32):
            assert np.array_equal(
                np.asarray(g), np.asarray(f.astype(jnp.bfloat16))
            )
    # bf16 resolution (8 bits) against the f64 loop over the same rounded
    # inputs; an accumulator in bf16 would miss segment 0 by a third
    _assert_trees_close(got, ref(c), _power(op), rtol=2e-2, atol=2e-2)
    if op in ("segment_sum", "segment_moments_fused"):
        assert np.all(np.asarray(got[0], np.float64)[0] == LONG)
    if op == "segment_softmax":
        head = np.asarray(got[0], np.float64)[:LONG]
        np.testing.assert_allclose(head, 1.0 / LONG, rtol=2**-8)


# ---- gradients against hand-written VJPs ------------------------------------


def _cotangent(shape, seed=7):
    rng = np.random.default_rng(seed)
    return rng.standard_normal(shape).astype(np.float32)


def _vjp_sum(c, ct):
    return ct[c.ids]


def _vjp_mean(c, ct):
    return (ct / np.maximum(ref_count(c.ids, N), 1.0)[:, None])[c.ids]


def _vjp_softmax(c, ct):
    p = ref_softmax(_lift(c, c.data), c.ids, N, c.mask)
    return p * (ct - ref_sum(ct * p, c.ids, N)[c.ids])


GRADS = {
    # name: (f(case, x) -> array, shape of its output, VJP(case, ct) -> dx)
    "segment_sum": (
        lambda c, x: S.segment_sum(x, c.ids, N), (N, D), _vjp_sum,
    ),
    "segment_mean": (
        lambda c, x: S.segment_mean(x, c.ids, N), (N, D), _vjp_mean,
    ),
    "segment_moments_fused.sum": (
        lambda c, x: S.segment_moments_fused(x, c.ids, N, c.mask)[0],
        (N, D), _vjp_sum,
    ),
    "segment_moments_fused.sum_of_squares": (
        lambda c, x: S.segment_moments_fused(x, c.ids, N, c.mask)[2],
        (N, D), lambda c, ct: 2.0 * c.data * ct[c.ids],
    ),
    "segment_softmax": (
        lambda c, x: S.segment_softmax(
            _lift(c, x), c.ids, N, jnp.asarray(c.mask)
        ),
        (E, D), _vjp_softmax,
    ),
}


@pytest.mark.parametrize("layout", ["real", "padded", "empty"])
@pytest.mark.parametrize("op", GRADS)
def pytest_gradient_matches_reference_vjp(op, layout):
    c = _case(layout, seed=3)
    fn, out_shape, ref_vjp = GRADS[op]
    ct = _cotangent(out_shape)
    out, pull = jax.vjp(lambda x: fn(c, x), jnp.asarray(c.data))
    (dx,) = pull(jnp.asarray(ct))
    assert out.shape == out_shape
    np.testing.assert_allclose(
        np.asarray(dx, np.float64), ref_vjp(c, ct.astype(np.float64)), **F32
    )
    if op == "segment_softmax":
        assert np.all(np.asarray(dx)[~c.mask] == 0.0)


@pytest.mark.parametrize("layout", ["real", "padded", "empty"])
def pytest_moments_count_gradient_reaches_the_weights(layout):
    """The third output of the packed pass: d count / d weights."""
    c = _case(layout, seed=3)
    ct = _cotangent((N, 1))
    w = jnp.asarray(c.mask, jnp.float32)
    _, pull = jax.vjp(
        lambda w: S.segment_moments_fused(jnp.asarray(c.data), c.ids, N, w)[1],
        w,
    )
    np.testing.assert_allclose(pull(jnp.asarray(ct))[0], ct[c.ids, 0], **F32)


def pytest_moments_statistics_gradient_finite_on_empty_segments():
    """PNA's use of the three moments (mean, sqrt(var + eps)) over a padded
    tail AND an empty band: finite everywhere (the epsilon keeps d sqrt
    finite at var = 0) and equal to the three plain scatters' gradient."""
    c = _case("padded", seed=5)
    ids = np.where(c.mask, c.ids % LIVE, N - 1).astype(np.int32)

    def loss(x, sums):
        s, cnt, sq = sums(x)
        deg = jnp.maximum(cnt, 1.0)
        mean = s / deg
        var = jax.nn.relu(sq / deg - mean**2)
        return jnp.sum(mean**2) + jnp.sum(jnp.sqrt(var + 1e-5))

    def ours(x):
        return loss(x, lambda x: S.segment_moments_fused(x, ids, N, c.mask))

    def plain(x):
        return loss(x, lambda x: (
            jax.ops.segment_sum(x, ids, num_segments=N),
            jax.ops.segment_sum(
                jnp.asarray(c.mask, jnp.float32), ids, num_segments=N
            )[:, None],
            jax.ops.segment_sum(x * x, ids, num_segments=N),
        ))

    x = jnp.asarray(c.data)
    g = np.asarray(jax.grad(ours)(x))
    assert np.isfinite(g).all()
    np.testing.assert_allclose(g, jax.grad(plain)(x), rtol=1e-4, atol=1e-5)
    s, cnt, _ = S.segment_moments_fused(x, ids, N, c.mask)
    assert np.all(np.asarray(s)[LIVE:] == 0.0)
    assert np.all(np.asarray(cnt)[LIVE:] == 0.0)


# ---- the gather helpers of models/common.py, with a masked tail -------------


def _graph(seed=1):
    """A node table and an edge list whose last PAD edges are padding that
    points at REAL nodes with live values: only ``edge_mask`` stops them."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((N, D)).astype(np.float32)
    snd = rng.integers(0, N, E).astype(np.int32)
    rcv = rng.integers(0, LIVE, E).astype(np.int32)  # nodes >= LIVE: no edge
    mask = np.ones(E, bool)
    mask[-PAD:] = False
    w = rng.standard_normal((E, D)).astype(np.float32) * mask[:, None]
    return x, snd, rcv, mask, w


def _ref_gather_sum(x, snd, rcv, mask, w=None):
    out = np.zeros((N, D), np.float64)
    for e in range(E):
        if mask[e]:
            out[rcv[e]] += x[snd[e]] * (1.0 if w is None else w[e])
    return out


def _ref_gather_sum_vjp(ct, snd, rcv, mask, w=None, scale=None):
    dx = np.zeros((N, D), np.float64)
    for e in range(E):
        if mask[e]:
            g = ct[rcv[e]] * (1.0 if w is None else w[e])
            dx[snd[e]] += g if scale is None else g / scale[rcv[e]]
    return dx


def _helper(name, dtype=np.float32):
    """(table, helper applied to it, expected result, expected VJP), the
    expectations computed from the table and weights as ``dtype`` holds
    them."""
    x, snd, rcv, mask, w = (
        a.astype(dtype).astype(np.float32) if a.dtype == np.float32 else a
        for a in _graph()
    )
    deg = np.maximum(ref_count(rcv, N, mask.astype(np.float64)), 1.0)
    if name == "gather_segment_sum":
        fn = lambda x: common.gather_segment_sum(x, snd, rcv, N, mask)
        want = _ref_gather_sum(x, snd, rcv, mask)
        vjp = lambda ct: _ref_gather_sum_vjp(ct, snd, rcv, mask)
    elif name == "gather_segment_mean":
        fn = lambda x: common.gather_segment_mean(x, snd, rcv, N, mask)
        want = _ref_gather_sum(x, snd, rcv, mask) / deg[:, None]
        vjp = lambda ct: _ref_gather_sum_vjp(ct, snd, rcv, mask, scale=deg)
    else:
        assert name == "gather_weighted_segment_sum"
        fn = lambda x: common.gather_weighted_segment_sum(
            x, w.astype(x.dtype), snd, rcv, N
        )
        want = _ref_gather_sum(x, snd, rcv, mask, w)
        vjp = lambda ct: _ref_gather_sum_vjp(ct, snd, rcv, mask, w)
    return jnp.asarray(x, dtype), fn, want, vjp


HELPERS = [
    "gather_segment_sum", "gather_segment_mean", "gather_weighted_segment_sum"
]


@pytest.mark.parametrize("name", HELPERS)
def pytest_gather_helper_forward_ignores_masked_tail(name):
    x, fn, want, _ = _helper(name)
    out = fn(x)
    assert out.dtype == x.dtype
    np.testing.assert_allclose(np.asarray(out, np.float64), want, **F32)
    assert np.all(np.asarray(out)[LIVE:] == 0.0)


@pytest.mark.parametrize("name", HELPERS)
def pytest_gather_helper_gradient_ignores_masked_tail(name):
    x, fn, _, ref_vjp = _helper(name)
    ct = _cotangent((N, D))
    _, pull = jax.vjp(fn, x)
    np.testing.assert_allclose(
        np.asarray(pull(jnp.asarray(ct))[0], np.float64),
        ref_vjp(ct.astype(np.float64)), **F32
    )


@pytest.mark.parametrize("name", HELPERS)
def pytest_gather_helper_bf16_table_accumulates_in_f32(name):
    x, fn, want, _ = _helper(name, jnp.bfloat16)
    out = fn(x)
    if name != "gather_segment_mean":  # that one divides by an f32 degree
        assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float64), want, rtol=2e-2, atol=2e-2
    )
