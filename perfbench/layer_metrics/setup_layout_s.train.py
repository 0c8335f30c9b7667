"""Seconds of set-up in the ``sample_stats``, ``compute_layout``,
``bucket_assignments`` and ``batch_plan`` spans, on any thread: the pass
over every sample that sizes the layout (and builds the dense lists' slot
tables), the buckets, and the packing of the warm epochs' plans. The first
two lie inside ``setup_loader_s.train``, the rest where the plan is first
asked for. Moves ``setup_s``."""

import span_main

NAMES = ("sample_stats", "compute_layout", "bucket_assignments", "batch_plan")


def read(run):
    setup = span_main.setup_spans(run)
    if setup is None:
        return None
    return span_main.total_s(span_main.named(setup, *NAMES))
