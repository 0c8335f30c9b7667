"""Neighbour slots that hold no edge, over all slots of the window's
dense-list batches: ``1 - edges / (bucket x k_in)``. Worked out from counts
the spans of every dense-list batch already carry: a ``collate`` span
(``edges``, ``bucket`` = padded rows) and the ``neighbor_lists`` span under
it (``k_in``). Every ``[N, K, *]`` pass of a dense-list edge phase (the
gathered table, the pre-activation, its BatchNorm, the gate) runs over
``bucket x k_in`` slots a batch; real are ``edges``. None where no collate of
the window has a ``neighbor_lists`` child with the counts (the edge-list
family, whose share is ``edge_padding_waste_pct.train``, or a program
without the recorder). Listed for the CGCNN cell alone."""

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
            slots += s.attrs["bucket"] * k_in[s.id]
            real += s.attrs["edges"]
    if not slots:
        return None
    return 100.0 * (1.0 - real / slots)
