#!/usr/bin/env python3
"""One clock: the program's spans in a profiler trace against the same spans
in the recorder's ring.

    python3 perfbench/span_clock.py --workload <cell> --seed <n> [--seconds 3]

Runs the cell once with ``--trace 1``'s path and the trace kept, then finds
the recorder's annotations in the trace by their ``id`` statistic and
compares each one's start (the trace counts from ``profile_start_time`` of
its ``Task Environment`` plane, ns since the epoch) with the ring's record
of that id, converted to the wall clock by the recorder's anchor pair. One
JSON line: per span name the matches and the median and largest difference
in us, and the run's per-layer metrics. Not part of a benchmark run.
"""

import argparse
import json
import os
import statistics
import sys

import run

NAMES = ("train", "dataload", "train_step", "epoch_readback", "collate",
         "put_group", "h2d")


def trace_starts(path):
    """{span id: (name, start in ns since the epoch)} of the host events
    that carry an ``id``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    origin, found = None, {}
    for plane in data.planes:
        if plane.name == "Task Environment":
            origin = dict(plane.stats).get("profile_start_time")
    if origin is None:
        raise SystemExit("the trace has no profile_start_time")
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in NAMES:
                    span_id = dict(ev.stats).get("id")
                    if span_id is not None:
                        found[int(span_id)] = (ev.name, int(origin) + int(ev.start_ns))
    return found


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=3.0)
    args = parser.parse_args()
    result = run.run_cell(args.workload, args.seed, args.seconds, True,
                          keep_trace=True)
    import trace_reduce

    from hydragnn_tpu.utils import tracer

    log = tracer.spans()
    wall0, perf0 = log.anchor
    ring = {s.id: s for s in log.records}
    found = trace_starts(trace_reduce.find_xplane(
        os.path.join(run.OUT, args.workload, "trace")))
    gaps = {}
    for span_id, (name, start) in found.items():
        span = ring.get(span_id)
        if span is not None and span.name == name:
            gaps.setdefault(name, []).append(
                (start - (wall0 + span.start_ns - perf0)) * 1e-3)
    report = {
        name: {"matched": len(v), "median_us": statistics.median(v),
               "largest_us": max(v, key=abs)}
        for name, v in sorted(gaps.items())
    }
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "correct": result["correct"], "annotations": len(found),
                      "one_clock": report, "metrics": result["metrics"]}),
          flush=True)
    return 0 if report else 1


if __name__ == "__main__":
    sys.exit(main())
