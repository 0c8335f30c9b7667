"""Median ``epoch_readback`` span under the window's ``train`` roots: the
epoch's one blocking readback (``Trainer._acc_read``), which waits for
every dispatch still in flight."""

import span_window


def read(run):
    win = span_window.window_spans(run)
    if win is None:
        return None
    roots = {r.id for r in win["roots"]}
    return span_window.median_ms([
        s for s in span_window.named(win, "epoch_readback", win["loop"])
        if s.parent in roots
    ])
