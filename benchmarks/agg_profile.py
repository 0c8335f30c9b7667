"""Where does the dense PNA step's time go? Times the fused-algebra
aggregation op (gather + 4 masked K-axis statistics, fwd+grad) alone at
OC20 scale vs a matmul floor — each as ONE dispatch of a chained
lax.fori_loop (per-dispatch host cost otherwise swamps an op this small).
Sizes the Pallas fusion opportunity (round-3 verdict item 1)."""
import sys, os, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np, jax, jax.numpy as jnp
from benchmarks.model_bench import _arg

def timeloop(make_body, z0, iters=50):
    @jax.jit
    def run(z):
        return jax.lax.fori_loop(0, iters, make_body, z)
    out = jax.block_until_ready(run(z0))
    t0 = time.perf_counter()
    out = jax.block_until_ready(run(z0))
    return (time.perf_counter() - t0) / iters * 1e3

N, D, K = 5760, int(_arg("hidden", 256)), int(_arg("k", 16))
deg = 12
dtype = jnp.bfloat16 if _arg("bf16", True) else jnp.float32
rng = np.random.default_rng(0)
z0 = jnp.asarray(rng.standard_normal((N, D)), dtype)
base = (np.arange(N) // 90) * 90
idx = (base[:, None] + rng.integers(0, 90, (N, K))).astype(np.int32)
mask = np.zeros((N, K), bool); mask[:, :deg] = True
nbr_idx = jnp.asarray(idx); nbr_mask = jnp.asarray(mask)
from hydragnn_tpu.ops.dense_agg import (
    build_neighbor_lists, gather_neighbors, dense_moments, dense_minmax,
)
send = idx.ravel(); recv = np.repeat(np.arange(N), K)
ex = build_neighbor_lists(jnp.asarray(send), jnp.asarray(recv),
                          jnp.asarray(mask.ravel()), N, K, 2 * K)
rev_idx, rev_mask = ex["rev_idx"], ex["rev_mask"]
wmix = jnp.asarray(rng.standard_normal((4 * D, D)) / 32, dtype)

def agg(z):
    h = gather_neighbors(z, nbr_idx, rev_idx, rev_mask)
    h = jnp.where(nbr_mask[..., None], h, 0.0)
    mean, std, degv, has = dense_moments(h, nbr_mask)
    mn, mx = dense_minmax(h, nbr_mask, has)
    return jnp.concatenate([mean, std, mn, mx], axis=-1).astype(dtype)

def body_fwd(i, z):
    return 0.5 * z + 0.5 * (agg(z) @ wmix)  # carry keeps shape [N, D]

def body_bwd(i, z):
    g = jax.grad(lambda zz: (agg(zz).astype(jnp.float32) ** 2).sum())(z)
    return 0.5 * z + 0.5 * g.astype(dtype)

w1 = jnp.asarray(rng.standard_normal((D, 4 * D)) / 16, dtype)
def body_mm(i, z):
    return 0.5 * z + 0.5 * ((z @ w1) @ wmix)

print("agg fwd (+[4D,D] mix matmul) ms/iter:", round(timeloop(body_fwd, z0), 3))
print("agg fwd+bwd ms/iter:", round(timeloop(body_bwd, z0), 3))
print("matmul pair [N,D]@[D,4D]@[4D,D] ms/iter:", round(timeloop(body_mm, z0), 3))

# ---- windowed-gather prototype: neighbors of node block b live within
# +/-2 blocks (contiguous packed graphs <= 250 rows), so the gather is an
# overlapping-window one-hot batched matmul -- MXU work, no random access.
B = 128
NB = N // B
W = 5 * B
zpad_rows = 2 * B

def windowed_agg(z):
    zp = jnp.pad(z, ((zpad_rows, zpad_rows), (0, 0)))
    # [NB, W, D] overlapping windows (5x z bytes, streamed)
    win = jnp.stack([
        jax.lax.dynamic_slice_in_dim(zp, b * B, W, 0) for b in range(NB)
    ])
    idx_b = nbr_idx.reshape(NB, B * K)
    local = idx_b - (jnp.arange(NB) * B - zpad_rows)[:, None]
    onehot = (local[:, :, None] ==
              jnp.arange(W)[None, None, :]).astype(dtype)
    gathered = jnp.einsum("bkw,bwd->bkd", onehot, win,
                          preferred_element_type=jnp.float32)
    h = gathered.reshape(N, K, D)
    h = jnp.where(nbr_mask[..., None], h, 0.0)
    mean, std, degv, has = dense_moments(h, nbr_mask)
    mn, mx = dense_minmax(h, nbr_mask, has)
    return jnp.concatenate([mean, std, mn, mx], axis=-1).astype(dtype)

def body_wfwd(i, z):
    return 0.5 * z + 0.5 * (windowed_agg(z) @ wmix)

def body_wbwd(i, z):
    g = jax.grad(lambda zz: (windowed_agg(zz).astype(jnp.float32) ** 2).sum())(z)
    return 0.5 * z + 0.5 * g.astype(dtype)

windowed_jit = jax.jit(windowed_agg)
agg_jit = jax.jit(agg)
ok = np.allclose(np.asarray(windowed_jit(z0), np.float32),
                 np.asarray(agg_jit(z0), np.float32), atol=2e-2)
print("windowed == gather parity:", ok)
print("windowed fwd (+mix) ms/iter:", round(timeloop(body_wfwd, z0), 3))
print("windowed fwd+bwd ms/iter:", round(timeloop(body_wbwd, z0), 3))

