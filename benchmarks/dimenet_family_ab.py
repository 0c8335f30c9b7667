"""DimeNet's two policy rows, read on one TPU chip: the train step of the
benchmark's DimeNet++ cell (its traffic, its rung, its four blocks) with
``Architecture.dense_aggregation`` true | false and
``Training.mixed_precision`` false | true: four readings, ms a step. The
reading behind ``ops/agg_policy.py DENSE_AUTO_MIN_HIDDEN["DimeNet"]`` and
``models/create.py BF16_AUTO_MIN_HIDDEN`` (PERF.md section 6, PR 30).
Distances, angles, the bases and the Bessel layer are f32 on both sides of
the precision pair.

    python benchmarks/dimenet_family_ab.py [--rung 64] [--out chiprun_out/dimenet_family_ab.jsonl]

Per (family, precision): the cell is built as ``perfbench/run.py`` builds it
(``perfbench/build.py``: seeded graphs -> the program's loaders ->
``update_config`` -> model, trainer, state), one batch of each bucket is put
on the device, and its ``train_step`` program is run back to back (the
device is the limit: the host only enqueues). A bucket's time is the median
of three such timings; the step is the buckets' mean weighted by their
batches in an epoch. A side the device cannot hold is a line with ``error``:
a reading too. Every line a JSON object on stdout.

Fails off a TPU: a CPU timing of either side says nothing.
"""

import argparse
import copy
import gc
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "perfbench")]

import numpy as np

import jax

CELL = "dimenetpp_h128x4_train_mptrj"


def step_reading(dense, bf16, graphs, cell, config, mix, rung, iters):
    import build
    from hydragnn_tpu.obs import runtime as obs

    config = copy.deepcopy(config)
    config["NeuralNetwork"]["Architecture"]["dense_aggregation"] = dense
    config["NeuralNetwork"]["Training"]["mixed_precision"] = bf16
    work = tempfile.mkdtemp(prefix="family_ab_", dir=os.environ.get("TMPDIR"))
    os.chdir(work)
    paths = build.write_dataset(work, graphs, graphs[: mix["eval_graphs"]])
    cfg = build.hydragnn_config(config, mix, cell, paths, rung)
    cfg, loader, _, trainer, state, _, _ = build.build_program(cfg)
    per_bucket = {}
    for batch in loader:
        per_bucket.setdefault(batch.x.shape[0], []).append(batch)
    rng = jax.random.PRNGKey(0)
    line = {
        "family": "dense" if dense else "segment",
        "precision": "bf16" if bf16 else "f32",
        "stated": cfg["NeuralNetwork"]["Architecture"]["dense_aggregation"],
        "buckets": {},
    }
    total, steps = 0.0, 0
    try:
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
                "loss": float(metrics["loss"]),
                "edges": int(batches[0].senders.shape[0]),
            }
            total += ms * len(batches)
            steps += len(batches)
            del dev
        line["step_ms"] = round(total / steps, 3)
    except Exception as e:  # the device cannot hold this side
        line["error"] = f"{type(e).__name__}: {e}"[:300]
    obs.deactivate(status="complete")
    del trainer, state, loader, per_bucket
    gc.collect()
    jax.clear_caches()
    return line


def main(cell_name=CELL):
    """The four readings of ``cell_name`` (``gat_family_ab.py`` reads its
    own cell through this)."""
    import build
    import traffic_gen

    ap = argparse.ArgumentParser()
    ap.add_argument("--rung", type=int, default=None,
                    help="another batch size than the cell's")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    out = args.out and os.path.abspath(args.out)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell, config, mix = build.load_cell(cell_name, json.load(f))
    rung = args.rung or build.batch_size_for(mix, cell["chips"])
    graphs = traffic_gen.make_graphs(mix, rung * mix["dataset_batches"], 0)
    lines = [{"device": dev.device_kind, "cell": cell_name, "rung": rung}]
    print(json.dumps(lines[0]), flush=True)
    for dense in (True, False):
        for bf16 in (False, True):
            line = step_reading(
                dense, bf16, graphs, cell, config, mix, rung, args.iters)
            lines.append(line)
            print(json.dumps(line), flush=True)
    if out:
        os.makedirs(os.path.dirname(out), exist_ok=True)
        with open(out, "w") as f:
            f.writelines(json.dumps(l) + "\n" for l in lines)


if __name__ == "__main__":
    main()
