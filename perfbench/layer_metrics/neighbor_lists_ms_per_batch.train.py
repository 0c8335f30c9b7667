"""Median ``neighbor_lists`` span of the window: ``build_neighbor_lists``
for one batch, a part of ``collate`` where the layout asks for dense
neighbour lists (the PNA cell; EGNN's layout builds none)."""

import span_window


def read(run):
    win = span_window.window_spans(run)
    return win and span_window.median_ms(span_window.named(win, "neighbor_lists"))
