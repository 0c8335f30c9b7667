"""Share of the traced window in which no operation ran on the busiest
device."""


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    return 100.0 * (1.0 - trace["busiest_busy_s"] / trace["window_s"])
