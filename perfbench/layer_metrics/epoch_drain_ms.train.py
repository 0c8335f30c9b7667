"""Median ``drain`` span under the window's ``epoch_readback`` spans: the
stack of the epoch's accumulators and ``jax.block_until_ready`` on it
(``Trainer._acc_read``), the wait for the device's queue.
``epoch_readback_ms.train`` less this is the host's own part of the
readback (transfer, float64 summation)."""

import span_window

import span_main


def read(run):
    win = span_main.boundary(run)
    if win is None:
        return None
    readbacks = {s.id for e in win["epochs"] for s in e
                 if s.name == "epoch_readback"}
    return span_window.median_ms([
        s for s in span_window.named(win, "drain", win["loop"])
        if s.parent in readbacks
    ])
