"""Median ``collate`` span of the window: sample fetch, ``collate_graphs``
and the layout's extras (neighbour lists, triplets) for one batch, on
whichever thread collates."""

import span_window


def read(run):
    win = span_window.window_spans(run)
    return win and span_window.median_ms(span_window.named(win, "collate"))
