"""The whole step's share of the chips' bf16 peak: operations the forward
and backward passes REQUIRE for the steps the trace holds
(``work/<model_type>.py``: real atoms and edges, no padding, no
recomputation) over the device time the step programs took on the busiest
device x chips x peak. Both factors come from the trace, so the host's
stalls do not move it (the rate, ``device_idle_pct.train`` and
``input_wait_pct.train`` carry those). While operations and not bytes bound
the work it equals ``step_roofline_pct.train``."""


def read(run):
    trace, work = run["trace"], run["work"]
    if trace is None or work is None or trace["step_busy_s"] <= 0:
        return None
    peak = run["peaks"]["bf16_flops_per_s"] * run["cell"]["chips"]
    return 100.0 * work["flops"] / (trace["step_busy_s"] * peak)
