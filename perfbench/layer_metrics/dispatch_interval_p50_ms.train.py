"""Median interval between the starts of consecutive executions of the step
programs on the device, per optimizer step, over the traced window."""

import statistics


def read(run):
    trace = run["trace"]
    if trace is None or not trace["intervals_s"]:
        return None
    return statistics.median(trace["intervals_s"]) * 1e3
