"""Bucketed-layout A/B on the streaming path (round-3 verdict item 3).

Trains PNA over an OC20-shaped synthetic size distribution (log-normal
20-250 atoms) fed by the streaming ``GraphLoader``, single max-sized
layout vs N size buckets. Reports fence-true epoch wall-clock,
graphs/sec, and the padding efficiency of each configuration.

Usage: ``python benchmarks/bucket_bench.py [--buckets=4] [--num=2048]
[--batch=32] [--hidden=128] [--epochs=3]``
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.model_bench import _arg, _arch  # noqa: E402


def _oc20_samples(num, seed=0, degree=12):
    from hydragnn_tpu.data.dataobj import GraphData

    rng = np.random.default_rng(seed)
    sizes = np.clip(
        np.round(np.exp(rng.normal(np.log(60.0), 0.55, num))), 20, 250
    ).astype(int)
    out = []
    for n in sizes:
        d = GraphData(
            x=rng.random((int(n), 1)).astype(np.float32),
            pos=(rng.random((int(n), 3)) * n ** (1 / 3)).astype(np.float32),
        )
        src = np.repeat(np.arange(n), degree // 2)
        dst = (src + rng.integers(1, n, src.shape[0])) % n
        d.edge_index = np.stack(
            [np.concatenate([src, dst]), np.concatenate([dst, src])]
        ).astype(np.int64)
        d.targets = [np.asarray([d.x.sum()], np.float32), d.x.copy()]
        d.target_types = ["graph", "node"]
        out.append(d)
    return out


def run(samples, batch_size, num_buckets, hidden, epochs, k_dispatch=1,
        contiguous=False):
    import jax

    from hydragnn_tpu.data.loaders import (
        GraphLoader,
        compute_layout,
        padding_efficiency,
    )
    from hydragnn_tpu.models import create_model_config
    from hydragnn_tpu.train.trainer import Trainer

    layout = compute_layout([samples], batch_size, num_buckets=num_buckets)
    eff = padding_efficiency([samples], layout, batch_size)
    loader = GraphLoader(
        samples, batch_size, layout, shuffle=True,
        contiguous_buckets=contiguous,
    )
    model = create_model_config(_arch("PNA", hidden, 3, 250))
    trainer = Trainer(
        model,
        training_config={
            "Optimizer": {"type": "AdamW", "learning_rate": 1e-3},
            "steps_per_dispatch": k_dispatch,
        },
    )
    state = trainer.init_state(next(iter(loader)))
    rng = jax.random.PRNGKey(0)
    # warm every bucket's compiled program before timing
    state, rng, loss, _ = trainer.train_epoch(state, loader, rng)
    t0 = time.perf_counter()
    for ep in range(epochs):
        loader.set_epoch(ep + 1)
        state, rng, loss, _ = trainer.train_epoch(state, loader, rng)
    assert np.isfinite(loss)
    dt = (time.perf_counter() - t0) / epochs
    return {
        "buckets": num_buckets,
        "steps_per_dispatch": k_dispatch,
        "contiguous": contiguous,
        "padding_efficiency": round(eff, 4),
        "epoch_sec": round(dt, 3),
        "graphs_per_sec": round(len(samples) / dt, 1),
        "loss": round(float(loss), 5),
    }


def run_device(samples, batch_size, num_buckets, hidden, iters=20):
    """DEVICE time per epoch: per distinct batch shape, enqueue ``iters``
    dispatches of the compiled step and block once (as
    ``benchmarks/model_bench.py`` does), then sum step-time x batch-count. Isolates compute from
    the host's loader/dispatch overheads."""
    import jax

    from hydragnn_tpu.data.loaders import (
        GraphLoader,
        compute_layout,
        padding_efficiency,
    )
    from hydragnn_tpu.models import create_model_config
    from hydragnn_tpu.train.trainer import Trainer

    layout = compute_layout([samples], batch_size, num_buckets=num_buckets)
    eff = padding_efficiency([samples], layout, batch_size)
    loader = GraphLoader(samples, batch_size, layout, shuffle=False)
    by_shape = {}
    for b in loader:
        by_shape.setdefault(b.x.shape, [0, b])
        by_shape[b.x.shape][0] += 1
    model = create_model_config(_arch("PNA", hidden, 3, 250))
    trainer = Trainer(
        model,
        training_config={"Optimizer": {"type": "AdamW", "learning_rate": 1e-3}},
    )
    first = next(iter(by_shape.values()))[1]
    state = trainer.init_state(first)
    rng = jax.random.PRNGKey(0)
    total = 0.0
    for shape, (count, batch) in by_shape.items():
        db = trainer.put_batch(batch)
        # deliberate fixed key: the bench times one fixed program per
        # shape; training statistics are irrelevant here
        state, m = trainer._train_step(state, db, rng)  # jaxlint: disable=prng-key-reuse
        jax.block_until_ready(m)
        t0 = time.perf_counter()
        for _ in range(iters):
            state, m = trainer._train_step(state, db, rng)  # jaxlint: disable=prng-key-reuse
        jax.block_until_ready(m)
        total += (time.perf_counter() - t0) / iters * count
    return {
        "mode": "device_epoch",
        "buckets": num_buckets,
        "padding_efficiency": round(eff, 4),
        "device_epoch_sec": round(total, 3),
        "graphs_per_sec_device": round(len(samples) / total, 1),
    }


def main():
    import json

    num = int(_arg("num", 2048))
    batch = int(_arg("batch", 32))
    hidden = int(_arg("hidden", 128))
    epochs = int(_arg("epochs", 3))
    buckets = int(_arg("buckets", 4))
    kd = int(_arg("k", 8))
    samples = _oc20_samples(num)
    if _arg("device", False):
        print(json.dumps(run_device(samples, batch, 1, hidden)))
        print(json.dumps(run_device(samples, batch, buckets, hidden)))
        return
    print(json.dumps(run(samples, batch, 1, hidden, epochs)))
    print(json.dumps(run(samples, batch, buckets, hidden, epochs)))
    print(json.dumps(run(samples, batch, 1, hidden, epochs, k_dispatch=kd)))
    print(json.dumps(run(samples, batch, buckets, hidden, epochs,
                         k_dispatch=kd, contiguous=True)))


if __name__ == "__main__":
    main()
