"""Giant-graph (partition) mode through the PUBLIC training API.

``Architecture.partition_axis`` routes ``run_training`` to the partitioned
trainer: every sample becomes one graph sharded node-wise over all 8 virtual
devices. Numerics match the unpartitioned model exactly, so the SAME
accuracy ceilings as ``tests/test_graphs*.py`` (``tests/e2e_train.py``) must
hold.
"""

import pytest

from e2e_train import FULL, unittest_train_model

_OVERWRITE = {"NeuralNetwork": {"Architecture": {"partition_axis": "graph"}}}


def pytest_partitioned_run_training_pna():
    unittest_train_model(
        "PNA", "ci.json", False, overwrite_config=_OVERWRITE,
        num_samples_tot=300,
    )


@pytest.mark.skipif(not FULL, reason="HYDRAGNN_FULL_TEST=1 for the long matrix")
@pytest.mark.parametrize("model_type", ["EGNN", "DimeNet"])
def pytest_partitioned_run_training_hard_paths(model_type):
    """The two hardest partition paths through the public API: EGNN's
    sender-side equivariant aggregation (halo_reduce) and DimeNet's
    2-hop/edge-state halos (triplet tables)."""
    ci = "ci_equivariant.json" if model_type == "EGNN" else "ci.json"
    unittest_train_model(
        model_type, ci, False, overwrite_config=_OVERWRITE,
        num_samples_tot=300,
    )
