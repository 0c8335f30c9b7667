"""Share of the window in which the epoch loop's thread is under no span
but a ``train`` root itself: the roots' self time plus whatever of the gaps
between them (the caller's bookkeeping) no span of the program names. A
span the loop thread opens between two roots (the epoch's ``batch_plan``)
counts as named."""

import span_window

import span_main


def read(run):
    win = span_main.boundary(run)
    if win is None:
        return None
    covered = span_window.covered_ns(
        win["loop_spans"], win["lo"], win["hi"]) * 1e-9
    return 100.0 * (1.0 - covered / win["seconds"])
