"""End-to-end accuracy, graph + node heads, GAT: the longest training of
the suite, in a file of its own so that a second worker runs it beside
``test_graphs_multihead.py``; and the dispatch modes on 300 graphs
(``tests/e2e_train.py``). The short and the skipped mode cases ride behind
it so that the file has five cases and is handed out early, and so that its
worker takes its next file only when this one is nearly done
(``conftest.py``, "CI tiers").
"""

import pytest

from e2e_train import FULL, unittest_train_model, unittest_train_model_300


@pytest.mark.parametrize("model_type", ["GAT"])
def pytest_train_model_multihead(model_type):
    unittest_train_model(model_type, "ci_multihead.json", False)


@pytest.mark.parametrize("model_type", ["PNA"])
def pytest_train_model_multistep_dispatch(model_type):
    """steps_per_dispatch (scan multi-step) through the public API must hit
    the same accuracy ceilings as the per-batch streaming path."""
    unittest_train_model_300(model_type, Training={"steps_per_dispatch": 4})


@pytest.mark.parametrize("model_type", ["PNA"])
def pytest_train_model_whole_training_dispatch(model_type):
    """Device-resident + chunked whole-training dispatch (fit_staged) must
    hit the same accuracy ceilings through the public run_training API."""
    unittest_train_model_300(
        model_type,
        Training={"device_resident_dataset": True, "fit_chunk_epochs": 10},
    )


@pytest.mark.skipif(not FULL, reason="cross-mode matrix: FULL tier")
@pytest.mark.parametrize(
    "training_overwrite",
    [
        {"device_resident_dataset": True, "fit_chunk_epochs": 10},
        {"steps_per_dispatch": 4},
    ],
    ids=["whole_training", "multistep"],
)
def pytest_train_model_dense_cross_modes(training_overwrite):
    """dense_aggregation composes with the whole-training and multi-step
    dispatch modes (the extras ride stage_batches/stack_batches): same
    reference ceilings through the public API."""
    unittest_train_model_300(
        "PNA",
        Architecture={"dense_aggregation": True},
        Training=training_overwrite,
    )
