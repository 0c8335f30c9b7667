"""``setup_s`` less ``setup_loader_s.train``, ``setup_model_init_s.train``
and ``setup_first_epochs_s.train``: imports, the harness's own data
generation, dataset files and weights, and whatever of the program still
lies under none of the three spans (``load_datasets``, ``init_state``, the
warm ``train`` roots). The four add up to ``setup_s`` by construction.
Moves ``setup_s``."""

import span_main


def read(run):
    parts = span_main.setup_parts(run)
    if parts is None:
        return None
    return run["setup_s"] - sum(parts.values())
