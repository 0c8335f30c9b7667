#!/usr/bin/env python3
"""Readings for the limits of ``correct``: several seeds of one cell in one
process (set-up is most of a run), each with the numbers compared and,
where asked, the same numbers for a control (the reference in the
program's place at a lower precision: ``fp8``, ``bf16``) or a planted fault
(``half_batch``). One JSON line a seed, also appended to ``--out``.

    python3 perfbench/calibrate.py --workload <cell> --seeds 11 12 13 \\
        --seconds 3 --control fp8 half_batch --out chiprun_out/cal.jsonl

Not part of a benchmark run: ``setup_s`` of a seed after the first is not a
process's set-up.
"""

import argparse
import json
import os
import sys
import traceback

import run


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--seconds", type=float, default=3.0)
    parser.add_argument("--trace", type=int, default=0)
    parser.add_argument("--control", nargs="*", default=[])
    parser.add_argument("--rung", type=int, help="another batch size than the cell's")
    parser.add_argument("--keep-trace", action="store_true",
                        help="leave the trace for trace_dump.py")
    parser.add_argument("--out")
    args = parser.parse_args()
    out = os.path.abspath(args.out) if args.out else None
    if out:
        os.makedirs(os.path.dirname(out), exist_ok=True)
    status = 0
    for seed in args.seeds:
        try:
            result = run.run_cell(args.workload, seed, args.seconds,
                                  bool(args.trace), control=tuple(args.control),
                                  rung=args.rung, keep_trace=args.keep_trace)
        except BaseException as e:  # a seed that dies is a reading too
            traceback.print_exc()
            result = {"error": f"{type(e).__name__}: {e}"[:500]}
            status = 1
        line = json.dumps({"workload": args.workload, "seed": seed, **result})
        print(line, flush=True)
        if out:
            with open(out, "a") as f:
                f.write(line + "\n")
    return status


if __name__ == "__main__":
    sys.exit(main())
