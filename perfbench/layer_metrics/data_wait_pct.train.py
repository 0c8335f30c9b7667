"""Share of the window the epoch loop spent waiting for a batch, from inside
the program: the sum of the recorder's ``dataload`` spans over the span of
the window's ``train`` roots. The inside twin of ``input_wait_pct.train``
(the same seconds, as the goodput ledger was told them). Also prints where
the window's host time went (``span_window.summary``) on stderr."""

import json
import sys

import span_window


def read(run):
    win = span_window.window_spans(run)
    if win is None:
        return None
    print(json.dumps({"phase": "spans", **span_window.summary(win)}),
          file=sys.stderr)
    waits = span_window.named(win, "dataload", win["loop"])
    return 100.0 * sum(map(span_window.seconds, waits)) / win["seconds"]
