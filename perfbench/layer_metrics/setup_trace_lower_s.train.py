"""Seconds of set-up covered by ``compile`` spans whose ``event`` is
neither ``backend_compile_duration`` nor ``cache_retrieval_time_sec``: of
what jax 0.9.0 sends, ``jaxpr_trace_duration`` (tracing, one event per
jit, an inner jit's inside its caller's) and
``jaxpr_to_mlir_module_duration`` (lowering). Traces nest, so this is the
time the spans COVER on each thread, not their sum. Cuts across
``setup_model_init_s.train`` and ``setup_first_epochs_s.train``; a span's
``parent`` says which. Moves ``setup_s``."""

import span_main


def read(run):
    setup = span_main.setup_spans(run)
    if setup is None:
        return None
    return span_main.covered_s(span_main.compiles(
        setup,
        lambda e: e not in (span_main.BACKEND_COMPILE, span_main.CACHE_LOAD),
    ))
