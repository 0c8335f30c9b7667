"""Seconds of set-up in the ``radius_graph`` spans (one a split:
``data/serialized.py load_serialized_data``, the per-sample loop over
``radius_graph_pbc`` / ``radius_graph``). Inside ``setup_loader_s.train``.
Moves ``setup_s``."""

import span_main


def read(run):
    setup = span_main.setup_spans(run)
    if setup is None:
        return None
    return span_main.total_s(span_main.named(setup, "radius_graph"))
