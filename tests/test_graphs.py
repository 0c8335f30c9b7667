"""End-to-end accuracy, single head (helper and strategy: ``tests/e2e_train.py``).

Default-tier e2e coverage over this file and its ``test_graphs_*.py``
siblings: every model trains to the accuracy ceilings at least once —
GIN+MFC (conv head) and PNA+SchNet+SAGE+DimeNet here, PNA and GAT
(multihead, a file each), CGCNN (lengths), EGNN (equivariant). Which case
lives in which file, and in what order, is the scheduler's business:
``conftest.py``, "CI tiers".
"""

import pytest

from e2e_train import ALL_MODELS, FULL, unittest_train_model


@pytest.mark.parametrize(
    "model_type",
    ["SAGE", "GIN", "GAT", "MFC", "PNA", "SchNet", "DimeNet", "EGNN"]
    if FULL
    else ["GIN", "MFC"],
)
def pytest_train_model_conv_head(model_type):
    unittest_train_model(model_type, "ci_conv_head.json", False)


@pytest.mark.parametrize(
    "model_type", ALL_MODELS if FULL else ["PNA", "SchNet", "SAGE", "DimeNet"]
)
def pytest_train_model(model_type):
    unittest_train_model(model_type, "ci.json", False)
