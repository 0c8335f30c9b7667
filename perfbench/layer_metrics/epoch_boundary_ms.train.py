"""Median, over consecutive epochs of the window, from the END of an
epoch's last ``train_step`` span to the START of the next epoch's first:
the last ``acc_add``, ``settle``, ``epoch_readback`` (with its ``drain``),
whatever the caller does between the ``train`` roots, ``epoch_open`` and the
refill. The host's view of the interval the device sees at the boundary."""

import statistics

import span_main


def read(run):
    win = span_main.boundary(run)
    if win is None:
        return None
    steps = [[s for s in e if s.name == "train_step"] for e in win["epochs"]]
    gaps = [
        nxt[0].start_ns - max(s.end_ns for s in prev)
        for prev, nxt in zip(steps, steps[1:]) if prev and nxt
    ]
    return statistics.median(gaps) * 1e-6 if gaps else None
