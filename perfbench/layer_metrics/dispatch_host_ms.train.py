"""Median ``train_step`` span of the window: the host's time to enqueue one
dispatch of a step program (the device runs it later)."""

import span_window


def read(run):
    win = span_window.window_spans(run)
    return win and span_window.median_ms(
        span_window.named(win, "train_step", win["loop"]))
