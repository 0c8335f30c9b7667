"""DimeNet post-bmm stage profile (round-4 verdict item 5).

Times the composed stages of the bmm-path DimeNet step separately at OC20
shape, hidden 128, so the step's top consumers are measured, not guessed:

  geometry   _dimenet_geometry_dense (rad/cbf transcendental chains)
  bmm        _bmm_triplet_aggregate (the round-4 rewrite)
  forward    full model.apply
  step       full jitted train step (fwd + loss + grad + AdamW)

Timing discipline: chained dispatches of the same program, one
``jax.block_until_ready`` at the end (see benchmarks/model_bench.py).

Usage: python benchmarks/dimenet_profile.py [--hidden=128] [--iters=30]
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.model_bench import _arch, _arg, _collate, make_graphs


def _time(fn, args, iters):
    out = jax.block_until_ready(fn(*args))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3  # ms


def main():
    global jax
    import jax
    import jax.numpy as jnp

    from hydragnn_tpu.models import create_model_config
    from hydragnn_tpu.models.dimenet import (
        _bmm_triplet_aggregate,
        _dimenet_geometry_dense,
    )
    from hydragnn_tpu.models.common import TorchLinear
    from hydragnn_tpu.ops.dense_agg import attach_neighbor_lists
    from hydragnn_tpu.train.trainer import Trainer
    from hydragnn_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    hidden = int(_arg("hidden", 128))
    iters = int(_arg("iters", 30))
    bf16 = bool(_arg("bf16", False))
    num_graphs, nodes, degree = 64, 90, 12

    samples = make_graphs(num_graphs, nodes, degree, seed=0)
    batch = _collate(samples, num_graphs, nodes, degree, with_triplets=True)
    batch = attach_neighbor_lists(batch)
    arch = _arch("DimeNet", hidden, 3, nodes)
    model = create_model_config(arch)
    trainer = Trainer(
        model,
        training_config={
            "Optimizer": {"type": "AdamW", "learning_rate": 1e-3},
            "mixed_precision": bf16,
        },
    )
    state = trainer.init_state(batch)
    dbatch = trainer.put_batch(batch)
    rng = jax.random.PRNGKey(0)

    S, R = arch["num_spherical"], arch["num_radial"]
    cutoff, env = arch["radius"], arch["envelope_exponent"]

    geo = jax.jit(
        lambda pos: _dimenet_geometry_dense(dbatch, pos, S, R, cutoff, env)
    )
    t_geo = _time(geo, (dbatch.pos,), iters)

    dist, rad, cbf = geo(dbatch.pos)
    int_emb, basis_emb = arch["int_emb_size"], arch["basis_emb_size"]

    class BmmOnly(__import__("flax").linen.Module):
        @__import__("flax").linen.compact
        def __call__(self, x_down, rad, cbf):
            l1 = TorchLinear(basis_emb, use_bias=False, name="sbf1")
            l2 = TorchLinear(int_emb, use_bias=False, name="sbf2")
            return _bmm_triplet_aggregate(
                x_down, rad, cbf, l1, l2, dbatch, S, R
            )

    x_down = jnp.zeros((dbatch.senders.shape[0], int_emb), jnp.float32)
    bmm = BmmOnly()
    bmm_vars = bmm.init(rng, x_down, rad, cbf)
    bmm_fn = jax.jit(lambda v, xd: bmm.apply(v, xd, rad, cbf))
    t_bmm = _time(bmm_fn, (bmm_vars, x_down), iters)

    fwd = jax.jit(lambda p, b: model.apply({"params": p}, b, train=False))
    t_fwd = _time(fwd, (state.params, dbatch), iters)

    # ``state`` is DONATED by the compiled step: thread the returned state,
    # never reuse the pre-warm one (its buffers are gone after the warm call).
    # Fixed key on purpose: the profile times one fixed program.
    s, m = trainer._train_step(state, dbatch, rng)  # jaxlint: disable=prng-key-reuse
    np.asarray(m["loss"])
    t0 = time.perf_counter()
    for _ in range(iters):
        s, m = trainer._train_step(s, dbatch, rng)  # jaxlint: disable=prng-key-reuse
    float(np.asarray(m["loss"]))
    t_step = (time.perf_counter() - t0) / iters * 1e3

    print(
        json.dumps(
            {
                "hidden": hidden,
                "precision": "bf16" if bf16 else "f32",
                "geometry_ms": round(t_geo, 2),
                "bmm_aggregate_ms": round(t_bmm, 2),
                "forward_ms": round(t_fwd, 2),
                "train_step_ms": round(t_step, 2),
                "graphs_per_sec": round(num_graphs / (t_step / 1e3), 1),
            }
        )
    )


if __name__ == "__main__":
    main()
