"""End-to-end accuracy where geometry enters the model: edge lengths as
features (vector and scalar outputs) and the equivariant models
(``tests/e2e_train.py``).
"""

import pytest

from e2e_train import FULL, unittest_train_model


@pytest.mark.parametrize("model_type", ["PNA"])
def pytest_train_model_vectoroutput(model_type):
    unittest_train_model(model_type, "ci_vectoroutput.json", True)


@pytest.mark.parametrize(
    "model_type",
    ["PNA", "CGCNN", "SchNet", "EGNN"] if FULL else ["PNA", "CGCNN"],
)
def pytest_train_model_lengths(model_type):
    unittest_train_model(model_type, "ci.json", True)


@pytest.mark.parametrize("model_type", ["EGNN", "SchNet"] if FULL else ["EGNN"])
def pytest_train_equivariant_model(model_type):
    unittest_train_model(model_type, "ci_equivariant.json", False)
