"""Attention slots that hold neither an edge nor an atom's self-loop, over all
slots the softmax of the window's batches is computed over. Worked out from
counts the spans of every dense-list batch already carry: a ``collate`` span
(``nodes``, ``edges``, ``bucket`` = padded rows) and the ``neighbor_lists``
span under it (``k_in``). The softmax runs over ``bucket x (k_in + 1)``
slots a batch (the neighbour slots and the self-loop slot of every padded
row); real are ``edges + nodes`` (what it is over in the reference). None
where no collate of the window has a ``neighbor_lists`` child with the
counts (the edge-list family, a program without the recorder). Says what an
attending model computes over: listed for the GATv2 cell alone."""

import span_window


def read(run):
    win = span_window.window_spans(run)
    if win is None:
        return None
    k_in = {s.parent: s.attrs["k_in"]
            for s in span_window.named(win, "neighbor_lists")
            if s.attrs and "k_in" in s.attrs}
    real = slots = 0
    for s in span_window.named(win, "collate"):
        if s.id in k_in and s.attrs and "bucket" in s.attrs:
            slots += s.attrs["bucket"] * (k_in[s.id] + 1)
            real += s.attrs["edges"] + s.attrs["nodes"]
    if not slots:
        return None
    return 100.0 * (1.0 - real / slots)
