"""Seconds of set-up under the ``init_state`` span
(``train/driver.py _build_model_and_trainer``): the model, the trainer, the
example batch and the initial state, with whatever that traces and compiles.
Moves ``setup_s``."""

import span_main


def read(run):
    parts = span_main.setup_parts(run)
    return None if parts is None else parts["model_init"]
