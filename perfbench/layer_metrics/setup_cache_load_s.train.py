"""Seconds of set-up in ``compile`` spans with ``event``
``cache_retrieval_time_sec``: programs read from the persistent compile
cache. 0 on a cold start. jax 0.9.0 reports a cache hit's retrieval INSIDE
the ``backend_compile_duration`` it also sends, so ``compile_s.train`` less
this is what the backend really compiled. Moves ``setup_s``."""

import span_main


def read(run):
    setup = span_main.setup_spans(run)
    if setup is None:
        return None
    return span_main.total_s(span_main.compiles(
        setup, lambda e: e == span_main.CACHE_LOAD))
