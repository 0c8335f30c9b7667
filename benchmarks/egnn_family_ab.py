"""EGNN's row of the family rule, read on one TPU chip: the train step of
the benchmark's EGNN cell (its traffic, its rung, its seven layers) with
``Architecture.dense_aggregation`` true against false, at hidden 32, 64
and 128. The reading behind ``ops/agg_policy.py
DENSE_AUTO_MIN_HIDDEN["EGNN"]`` (PERF.md section 6, PR 29).

    python benchmarks/egnn_family_ab.py [--hidden 32 64 128] [--f32] [--out chiprun_out/egnn_family_ab.jsonl]

Per (hidden, family): the cell is built as ``perfbench/run.py`` builds it
(``perfbench/build.py``: seeded graphs -> the program's loaders ->
``update_config`` -> model, trainer, state), one batch of each bucket is
put on the device, and its ``train_step`` program is run back to back
(the device is the limit: the host only enqueues). A bucket's time is the
median of three such timings; the step is the buckets' mean weighted by
their batches in an epoch. Every line a JSON object on stdout.

Fails off a TPU: a CPU timing of either side says nothing.
"""

import argparse
import copy
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "perfbench")]

import numpy as np

import jax

CELL = "egnn_h128x7_train_mptrj"


def step_reading(hidden, dense, graphs, cell, config, mix, rung, iters,
                 f32=False):
    import build

    config = copy.deepcopy(config)
    arch = config["NeuralNetwork"]["Architecture"]
    arch.update(hidden_dim=hidden, dense_aggregation=dense)
    if f32:  # the cell's "auto" turns bf16 on from hidden 128
        config["NeuralNetwork"]["Training"]["mixed_precision"] = False
    work = tempfile.mkdtemp(prefix="egnn_ab_", dir=os.environ.get("TMPDIR"))
    os.chdir(work)
    paths = build.write_dataset(work, graphs, graphs[: mix["eval_graphs"]])
    cfg = build.hydragnn_config(config, mix, cell, paths, rung)
    cfg, loader, _, trainer, state, _, _ = build.build_program(cfg)
    per_bucket = {}
    for batch in loader:
        per_bucket.setdefault(batch.x.shape[0], []).append(batch)
    rng = jax.random.PRNGKey(0)
    line = {
        "hidden": hidden,
        "precision": "f32" if f32 else "auto",
        "family": "dense" if dense else "segment",
        "stated": cfg["NeuralNetwork"]["Architecture"]["dense_aggregation"],
        "buckets": {},
    }
    total, steps = 0.0, 0
    for rows, batches in sorted(per_bucket.items()):
        dev = trainer.put_batch(batches[0])
        t0 = time.perf_counter()
        rng, sub = jax.random.split(rng)
        state, metrics = trainer._train_step(state, dev, sub)
        jax.block_until_ready(metrics)
        first = time.perf_counter() - t0
        takes = []
        for _ in range(3):
            t0 = time.perf_counter()
            rng, *subs = jax.random.split(rng, iters + 1)
            for sub in subs:
                state, metrics = trainer._train_step(state, dev, sub)
            jax.block_until_ready(metrics)
            takes.append((time.perf_counter() - t0) / iters * 1e3)
        ms = float(np.median(takes))
        line["buckets"][f"n{rows}"] = {
            "batches": len(batches), "step_ms": round(ms, 3),
            "first_call_s": round(first, 2),
        }
        total += ms * len(batches)
        steps += len(batches)
    line["step_ms"] = round(total / steps, 3)
    return line


def main():
    import build
    import traffic_gen

    ap = argparse.ArgumentParser()
    ap.add_argument("--hidden", type=int, nargs="+", default=[32, 64, 128])
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--f32", action="store_true",
                    help="mixed_precision false in place of the cell's auto")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    out = args.out and os.path.abspath(args.out)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell, config, mix = build.load_cell(CELL, json.load(f))
    rung = build.batch_size_for(mix, cell["chips"])
    graphs = traffic_gen.make_graphs(mix, rung * mix["dataset_batches"], 0)
    lines = [{"device": dev.device_kind, "cell": CELL, "rung": rung}]
    print(json.dumps(lines[0]), flush=True)
    for hidden in args.hidden:
        pair = {}
        for dense in (False, True):
            line = step_reading(
                hidden, dense, graphs, cell, config, mix, rung, args.iters,
                f32=args.f32,
            )
            pair[dense] = line["step_ms"]
            lines.append(line)
            print(json.dumps(line), flush=True)
        verdict = {
            "hidden": hidden, "segment_over_dense": round(pair[False] / pair[True], 3),
            "dense_wins_by_over_10pct": bool(pair[True] < 0.9 * pair[False]),
        }
        lines.append(verdict)
        print(json.dumps(verdict), flush=True)
    if out:
        os.makedirs(os.path.dirname(out), exist_ok=True)
        with open(out, "w") as f:
            f.writelines(json.dumps(l) + "\n" for l in lines)


if __name__ == "__main__":
    main()
