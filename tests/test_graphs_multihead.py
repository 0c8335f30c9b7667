"""End-to-end accuracy, graph + node heads, every model but GAT (its
training is the suite's longest: ``test_graphs_multihead_gat.py``), and the
aggregation and loss modes on 300 graphs (``tests/e2e_train.py``). The
short mode cases ride behind the long training so that the file has five
cases and is handed out early (``conftest.py``, "CI tiers").
"""

import pytest

from e2e_train import (
    ALL_MODELS,
    FULL,
    unittest_train_model,
    unittest_train_model_300,
)


@pytest.mark.parametrize(
    "model_type", [m for m in ALL_MODELS if m != "GAT"] if FULL else ["PNA"]
)
def pytest_train_model_multihead(model_type):
    unittest_train_model(model_type, "ci_multihead.json", False)


@pytest.mark.parametrize("model_type", ["PNA", "DimeNet"])
def pytest_train_model_dense_aggregation(model_type):
    """Scatter-free dense neighbor-list aggregation (dense_aggregation:
    true) through the public API must hit the same accuracy ceilings as
    the segment path — it is the performance mode for MXU-scale configs
    (ops/dense_agg.py). DimeNet's dense mode is the bmm-triplet path
    (models/dimenet.py): no T axis, no host-side compute_triplets."""
    unittest_train_model_300(model_type, Architecture={"dense_aggregation": True})


@pytest.mark.parametrize("model_type", ["PNA"])
def pytest_train_model_nll_loss(model_type):
    """Uncertainty-weighted NLL multi-task loss (the mode the reference
    leaves unfinished): heads grow a log-variance channel, training through
    the public API still hits the reference accuracy ceilings."""
    unittest_train_model_300(model_type, Architecture={"ilossweights_nll": 1})


@pytest.mark.skipif(not FULL, reason="auto-dense e2e: FULL tier")
def pytest_train_model_auto_dense_no_flag():
    """At MXU widths the aggregation path is chosen AUTOMATICALLY (no
    dense_aggregation key anywhere): the measured-crossover policy must
    route this hidden-96 MFC run onto the dense path and still hit the
    reference ceilings through the public API."""
    unittest_train_model_300("MFC", Architecture={"hidden_dim": 96})
