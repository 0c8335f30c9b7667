"""Streaming-path benchmarks.

Default mode — H2D/compute overlap A/B (round-3 verdict item 5): trains
PNA fed by the streaming ``GraphLoader`` (host->device transfer per
batch — the production path for datasets too big for HBM residency) with
the double-buffered device prefetch ON vs OFF, all else equal.
Fence-true: the epoch's accumulated-metric readback materializes host
bytes, so wall-clock includes every transfer and step.

``--mix`` mode — the shard-native streaming pipeline end to end
(``hydragnn_tpu/data/stream/``): a two-source weighted mix (QM9-shaped +
OC20-shaped) through WeightedMix -> auto-tuned BucketPlanner ->
StreamLoader, reporting ingestion-side numbers (graphs/sec, sample
bytes/sec, pipeline stall share, measured padding waste, peak window
residency) as a ``BENCH_*``-style JSON row so the perf trajectory covers
ingestion, not just steps.

Usage: ``python benchmarks/streaming_bench.py [--num=2048] [--batch=64]
[--hidden=128] [--epochs=3] [--depth=2] [--host_prefetch=2] [--mix]
[--out=FILE]``
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.bucket_bench import _oc20_samples  # noqa: E402
from benchmarks.model_bench import _arg, _arch  # noqa: E402


def run(samples, batch_size, hidden, epochs, depth, host_prefetch):
    import jax

    from hydragnn_tpu.data.loaders import GraphLoader, compute_layout
    from hydragnn_tpu.models import create_model_config
    from hydragnn_tpu.train.trainer import Trainer

    layout = compute_layout([samples], batch_size)
    loader = GraphLoader(
        samples, batch_size, layout, shuffle=True, prefetch=host_prefetch
    )
    model = create_model_config(_arch("PNA", hidden, 3, 250))
    trainer = Trainer(
        model,
        training_config={
            "Optimizer": {"type": "AdamW", "learning_rate": 1e-3},
            "device_prefetch": depth,
        },
    )
    state = trainer.init_state(next(iter(loader)))
    rng = jax.random.PRNGKey(0)
    # warmup epoch: compile + first-touch
    state, rng, loss, _ = trainer.train_epoch(state, loader, rng)
    t0 = time.perf_counter()
    for ep in range(epochs):
        loader.set_epoch(ep + 1)
        state, rng, loss, _ = trainer.train_epoch(state, loader, rng)
    assert np.isfinite(loss)
    dt = (time.perf_counter() - t0) / epochs
    return {
        "device_prefetch": depth,
        "host_prefetch": host_prefetch,
        "epoch_sec": round(dt, 3),
        "graphs_per_sec": round(len(samples) / dt, 1),
        "loss": round(float(loss), 5),
    }


def _qm9_shaped(num, seed=3):
    """Small molecules (the QM9 end of a GFM mix) with the same head
    schema as the OC20-shaped generator so the two sources mix."""
    from hydragnn_tpu.data.dataobj import GraphData

    rng = np.random.default_rng(seed)
    out = []
    for _ in range(num):
        n = int(rng.integers(4, 30))
        d = GraphData(
            x=rng.random((n, 1)).astype(np.float32),
            pos=rng.random((n, 3)).astype(np.float32),
        )
        src = np.arange(n)
        dst = (src + 1) % n
        d.edge_index = np.stack(
            [np.concatenate([src, dst]), np.concatenate([dst, src])]
        ).astype(np.int64)
        d.targets = [np.asarray([d.x.sum()], np.float32), d.x.copy()]
        d.target_types = ["graph", "node"]
        out.append(d)
    return out


def run_mix(num, batch_size, hidden, epochs, host_prefetch):
    """The shard-native streaming pipeline end to end: weighted
    two-source mix -> auto bucket plan -> StreamLoader -> train. Returns
    one BENCH-style row of ingestion-side numbers."""
    import jax

    from hydragnn_tpu.data.stream import (
        BucketPlanner,
        ListSource,
        StreamLoader,
        WeightedMix,
    )
    from hydragnn_tpu.models import create_model_config
    from hydragnn_tpu.train.trainer import Trainer

    src_small = ListSource(
        _qm9_shaped(num // 2), shard_size=64, name="qm9_shaped"
    )
    src_large = ListSource(
        _oc20_samples(num // 2), shard_size=64, name="oc20_shaped"
    )
    mix = WeightedMix(
        [src_small, src_large], [1.0, 1.0], seed=11, num_shards=1,
        shard_id=0, window=2,
    )
    planner = BucketPlanner(
        mix.sources, batch_size, num_buckets=4
    )
    layout = planner.plan(emit=False)
    loader = StreamLoader(
        mix, batch_size, layout, prefetch=host_prefetch
    )
    model = create_model_config(_arch("PNA", hidden, 3, 250))
    trainer = Trainer(
        model,
        training_config={
            "Optimizer": {"type": "AdamW", "learning_rate": 1e-3},
        },
    )
    state = trainer.init_state(loader.example_batch())
    rng = jax.random.PRNGKey(0)
    loader.set_epoch(0)
    state, rng, loss, _ = trainer.train_epoch(state, loader, rng)  # warmup
    t0 = time.perf_counter()
    graphs = 0
    stall_s = 0.0
    for ep in range(epochs):
        loader.set_epoch(ep + 1)
        state, rng, loss, _ = trainer.train_epoch(state, loader, rng)
        # _epoch_stats is replaced per epoch — accumulate, don't
        # extrapolate the last epoch across the run
        graphs += loader._epoch_stats["samples"]
        stall_s += loader._epoch_stats["stall_s"]
    dt = time.perf_counter() - t0
    assert np.isfinite(loss)
    real, padded = loader.epoch_padding_stats()
    res = mix.residency_stats()
    return {
        "mode": "mix",
        "sources": 2,
        "num_buckets": len(layout.layouts),
        "host_prefetch": host_prefetch,
        "epoch_sec": round(dt / epochs, 3),
        "graphs_per_sec": round(graphs / dt, 1),
        "stall_share": round(stall_s / dt, 4),
        "padding_waste": round(1.0 - real / padded, 4),
        "est_waste": round(planner.estimate_waste(layout), 4),
        "resident_bytes_peak": int(res["resident_bytes_peak"]),
        "open_shards_peak": int(res["open_shards_peak"]),
        "loss": round(float(loss), 5),
    }


def main():
    num = int(_arg("num", 2048))
    batch = int(_arg("batch", 64))
    hidden = int(_arg("hidden", 128))
    epochs = int(_arg("epochs", 3))
    depth = int(_arg("depth", 2))
    host_prefetch = int(_arg("host_prefetch", 2))
    if _arg("mix", False):
        row = run_mix(num, batch, hidden, epochs, host_prefetch)
        print(json.dumps(row), flush=True)
        out = _arg("out")
        if out and out is not True:
            # BENCH_*-style record: append-merge so rounds accumulate
            rows = []
            if os.path.exists(out):
                with open(out) as f:
                    rows = json.load(f)
            rows.append(row)
            with open(out, "w") as f:
                json.dump(rows, f, indent=1)
        return
    samples = _oc20_samples(num)
    rows = []
    # interleaved ABAB so slow drift of the host cancels instead of
    # landing on one arm
    for d in (0, depth, 0, depth):
        rows.append(run(samples, batch, hidden, epochs, d, host_prefetch))
        print(json.dumps(rows[-1]), flush=True)
    off = np.mean([r["graphs_per_sec"] for r in rows if not r["device_prefetch"]])
    on = np.mean([r["graphs_per_sec"] for r in rows if r["device_prefetch"]])
    print(json.dumps({"overlap_speedup": round(float(on / off), 3)}))


if __name__ == "__main__":
    main()
