"""Microbenchmark: pallas one-hot aggregation vs XLA scatter segment ops.

Run on a real TPU to decide the ``HYDRAGNN_PALLAS`` default:

    python benchmarks/segment_bench.py [--edges=100000] [--nodes=5000] [--dim=64]

Prints per-path step times for (a) plain segment_sum and (b) the PNA
statistic set (mean+std+count), forward and forward+grad.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def _arg(flag, default):
    for a in sys.argv[1:]:
        if a.startswith(f"--{flag}="):
            return int(a.split("=", 1)[1])
    return default


def timeit(fn, *args, iters=50):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3  # ms


def main():
    e, n, d = _arg("edges", 100_000), _arg("nodes", 5_000), _arg("dim", 64)
    rng = np.random.default_rng(0)
    data = jnp.asarray(rng.standard_normal((e, d)), jnp.float32)
    ids = jnp.asarray(np.sort(rng.integers(0, n, e)), jnp.int32)

    from hydragnn_tpu.ops import segment_moments, segment_sum_onehot

    @jax.jit
    def xla_sum(x):
        return jax.ops.segment_sum(x, ids, num_segments=n)

    @jax.jit
    def pls_sum(x):
        return segment_sum_onehot(x, ids, n)

    @jax.jit
    def xla_stats(x):
        s = jax.ops.segment_sum(x, ids, num_segments=n)
        c = jax.ops.segment_sum(jnp.ones(e), ids, num_segments=n).reshape(-1, 1)
        sq = jax.ops.segment_sum(x * x, ids, num_segments=n)
        mean = s / jnp.maximum(c, 1.0)
        return mean, jnp.sqrt(jnp.maximum(sq / jnp.maximum(c, 1.0) - mean**2, 0) + 1e-5)

    @jax.jit
    def pls_stats(x):
        s, c, sq = segment_moments(x, ids, n)
        mean = s / jnp.maximum(c, 1.0)
        return mean, jnp.sqrt(jnp.maximum(sq / jnp.maximum(c, 1.0) - mean**2, 0) + 1e-5)

    grad_xla = jax.jit(jax.grad(lambda x: sum(jnp.sum(o**2) for o in xla_stats(x))))
    grad_pls = jax.jit(jax.grad(lambda x: sum(jnp.sum(o**2) for o in pls_stats(x))))

    print(f"E={e} N={n} D={d} backend={jax.default_backend()}")
    print(f"segment_sum      xla {timeit(xla_sum, data):8.3f} ms   "
          f"pallas {timeit(pls_sum, data):8.3f} ms")
    print(f"pna stats (fwd)  xla {timeit(xla_stats, data):8.3f} ms   "
          f"pallas {timeit(pls_stats, data):8.3f} ms")
    print(f"pna stats (grad) xla {timeit(grad_xla, data):8.3f} ms   "
          f"pallas {timeit(grad_pls, data):8.3f} ms")


if __name__ == "__main__":
    main()
