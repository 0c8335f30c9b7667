"""Padded edge slots that hold no edge, over all edge slots collated in the
window (the counts on the ``collate`` spans): the edge twin of
``padding_waste_pct.train``. The segment path scatters over every slot."""

import span_window


def read(run):
    win = span_window.window_spans(run)
    if win is None:
        return None
    collates = span_window.named(win, "collate")
    slots = sum(s.attrs["e_pad"] for s in collates)
    if not slots:
        return None
    return 100.0 * (1.0 - sum(s.attrs["edges"] for s in collates) / slots)
