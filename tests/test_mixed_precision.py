"""Mixed-precision (bf16 compute / f32 master) training accuracy.

No reference counterpart — HydraGNN trains pure f32. The bf16 path must
still clear the SAME accuracy ceilings as f32 training
(``tests/e2e_train.py`` / reference ``tests/test_graphs.py:139-156``),
otherwise it would be a perf knob that silently costs accuracy.
"""

from e2e_train import unittest_train_model


def pytest_mixed_precision_pna_multihead():
    unittest_train_model(
        "PNA",
        "ci_multihead.json",
        False,
        overwrite_config={
            "NeuralNetwork": {"Training": {"mixed_precision": True}}
        },
    )
