#!/usr/bin/env python3
"""Look at one trace by hand: planes, lines, event counts and the first
events of each line; optionally cut a small recorded trace (JSON) for the
self-checks.

    python3 perfbench/trace_dump.py <trace dir> [--record out.json --from-ms 0 --ms 50]
"""

import argparse
import json
import sys

import trace_reduce


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("trace_dir")
    parser.add_argument("--record")
    parser.add_argument("--from-ms", type=float, default=0.0)
    parser.add_argument("--ms", type=float, default=50.0)
    args = parser.parse_args()
    from jax.profiler import ProfileData

    path = trace_reduce.find_xplane(args.trace_dir)
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events))
            for ev in events[:6]:
                print("     ", repr(ev.name)[:100], ev.start_ns, ev.duration_ns)
    if args.record:
        keep = lambda n: n.startswith("perfbench.") or n in (  # noqa: E731
            "train", "dataload", "train_step")
        trace = trace_reduce.load_xplane(path, keep_host=keep)
        window = trace_reduce.span_window(trace, "perfbench.")
        lo = window[0] + int(args.from_ms * 1e6)
        hi = lo + int(args.ms * 1e6)
        cut = {
            plane: {
                line: [[ev[0][:80], ev[1] - lo, ev[2]] for ev in events
                       if lo <= ev[1] and ev[1] + ev[2] <= hi]
                for line, events in lines.items()
            }
            for plane, lines in trace.items()
        }
        cut["/host:CPU"].setdefault("window", []).append(
            ["perfbench.cut", 0, hi - lo])
        with open(args.record, "w") as f:
            json.dump({"trace": cut}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
