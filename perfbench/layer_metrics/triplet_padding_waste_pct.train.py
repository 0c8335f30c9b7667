"""Triplet slots that hold no triplet, over all triplet slots the window's
collates laid out: the counts ``triplets`` and ``triplet_slots`` on the
``neighbor_lists`` spans of a dense-list DimeNet batch (``n_pad x k_out x
k_in`` grid slots a batch, which the step's per-layer products run over) or,
where the triplet tables run instead, on the ``triplets`` spans (rows of the
padded table). None where no span of the window carries the counts: another
model, or a program from before the counters."""

import span_window


def read(run):
    win = span_window.window_spans(run)
    if win is None:
        return None
    spans = [
        s for name in ("neighbor_lists", "triplets")
        for s in span_window.named(win, name)
        if s.attrs and "triplet_slots" in s.attrs
    ]
    slots = sum(s.attrs["triplet_slots"] for s in spans)
    if not slots:
        return None
    return 100.0 * (1.0 - sum(s.attrs["triplets"] for s in spans) / slots)
