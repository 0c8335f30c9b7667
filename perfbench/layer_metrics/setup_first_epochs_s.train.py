"""Seconds of set-up under the ``train`` roots that closed before the
window: the warm epochs, one per shuffle of the cycle (tracing, lowering,
compile or cache load of every step program, and their steps). Moves
``setup_s``."""

import span_main


def read(run):
    parts = span_main.setup_parts(run)
    return None if parts is None else parts["first_epochs"]
