"""Seconds of XLA backend compilation during set-up
(``hydragnn_tpu.obs.runtime.compile_seconds()`` when the window opens):
what the persistent cache did not hold. Moves ``setup_s``."""


def read(run):
    return run["compile_s"]
