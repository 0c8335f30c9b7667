"""The least time the chips could take for the window's required work (the
larger of operations over peak and required bytes over the memory's rate)
over the device time the step programs took on the busiest device. Which of
the two bounds it is printed on stderr."""

import json
import sys


def read(run):
    trace, work = run["trace"], run["work"]
    if trace is None or work is None or trace["step_busy_s"] <= 0:
        return None
    chips = run["cell"]["chips"]
    by_flops = work["flops"] / (run["peaks"]["bf16_flops_per_s"] * chips)
    by_bytes = work["bytes"] / (run["peaks"]["hbm_bytes_per_s"] * chips)
    print(json.dumps({"roofline_bound": "flops" if by_flops >= by_bytes else "bytes",
                      "least_s_by_flops": by_flops, "least_s_by_bytes": by_bytes}),
          file=sys.stderr)
    return 100.0 * max(by_flops, by_bytes) / trace["step_busy_s"]
