"""Host time of the transfer stage per batch: the window's ``put_group``
spans (``stack_batches``, ``compact``, ``h2d``: the host's side of the put)
summed, over the batches they carried."""

import span_window


def read(run):
    win = span_window.window_spans(run)
    if win is None:
        return None
    puts = span_window.named(win, "put_group")
    batches = sum(s.attrs["batches"] for s in puts)
    if not batches:
        return None
    return sum(map(span_window.seconds, puts)) / batches * 1e3
