"""Median, per epoch of the window, of the time from ``train``'s start to
its first ``train_step``'s start: the pipeline refilling from empty (one
collate and one put, fully exposed) before the first dispatch."""

import statistics

import span_window


def read(run):
    win = span_window.window_spans(run)
    if win is None:
        return None
    steps = span_window.named(win, "train_step", win["loop"])
    first = {}
    for s in steps:
        first.setdefault(s.parent, s.start_ns)
    gaps = [first[r.id] - r.start_ns for r in win["roots"] if r.id in first]
    return statistics.median(gaps) * 1e-6 if gaps else None
