"""Device time of one optimizer step: the union of device-operation
intervals inside executions of the step programs, per optimizer step, on the
busiest device of the traced window."""


def read(run):
    trace = run["trace"]
    if trace is None or not trace["steps"]:
        return None
    return trace["step_busy_s"] / trace["steps"] * 1e3
