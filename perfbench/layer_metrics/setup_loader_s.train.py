"""Seconds of set-up under the ``load_datasets`` span
(``data/loaders.py dataset_loading_and_splitting``): the splits read, their
radius graphs, targets, the per-sample statistics and the layout, up to the
loaders. ``setup_graph_build_s.train`` and most of ``setup_layout_s.train``
lie inside it. Moves ``setup_s``."""

import span_main


def read(run):
    parts = span_main.setup_parts(run)
    return None if parts is None else parts["loader"]
