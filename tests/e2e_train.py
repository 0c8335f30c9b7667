"""End-to-end accuracy helper: one training through the full public API.

Mirrors the reference's core test strategy (``tests/test_graphs.py:25-189``):
train a model on the deterministic synthetic dataset via
``hydragnn_tpu.run_training``, reload + predict via ``run_prediction``, and
assert per-head RMSE and sample MAE against per-model ceilings.

Not collected by pytest (no ``test_`` prefix): the ONE home of the helper,
imported by bare name by ``test_graphs*.py``, ``test_mixed_precision.py``,
``test_partitioned_run_training.py`` and ``test_bucketed_layouts.py``
(``test_suite_layout.py`` guards that). How many trainings a file may hold,
and in which order: ``conftest.py``, "CI tiers".
"""

import atexit
import copy
import json
import os
import shutil
import tempfile

import numpy as np

import hydragnn_tpu
from hydragnn_tpu.utils.config import merge_config
from synthetic import deterministic_graph_data

ALL_MODELS = ["SAGE", "GIN", "GAT", "MFC", "PNA", "CGCNN", "SchNet", "DimeNet", "EGNN"]
# HYDRAGNN_FULL_TEST=1 widens the default one-run-per-feature-axis matrices
# to the reference's full 33-run matrix (tests/test_graphs.py:193-224)
FULL = int(os.getenv("HYDRAGNN_FULL_TEST", "0")) == 1

# same ceilings as the reference CI (tests/test_graphs.py:139-156)
THRESHOLDS = {
    "SAGE": [0.20, 0.20],
    "PNA": [0.20, 0.20],
    "MFC": [0.20, 0.20],
    "GIN": [0.25, 0.20],
    "GAT": [0.60, 0.70],
    "CGCNN": [0.50, 0.40],
    "SchNet": [0.20, 0.20],
    "DimeNet": [0.50, 0.50],
    "EGNN": [0.20, 0.20],
}

# one per process (each xdist worker builds the 300- and 500-graph sets it
# needs once), removed when the process ends
_WORKDIR = None


def _workdir():
    global _WORKDIR
    if _WORKDIR is None:
        _WORKDIR = tempfile.mkdtemp(prefix="hydragnn_tpu_ci_")
        atexit.register(shutil.rmtree, _WORKDIR, ignore_errors=True)
    return _WORKDIR


def unittest_train_model(
    model_type, ci_input, use_lengths, overwrite_config=None, num_samples_tot=500
):
    workdir = _workdir()
    os.environ["SERIALIZED_DATA_PATH"] = workdir
    cwd = os.getcwd()
    os.chdir(workdir)
    try:
        config_file = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "inputs", ci_input
        )
        with open(config_file, "r") as f:
            config = json.load(f)
        config["NeuralNetwork"]["Architecture"]["model_type"] = model_type
        if overwrite_config:
            config = merge_config(config, overwrite_config)
        if use_lengths:
            config["NeuralNetwork"]["Architecture"]["edge_features"] = ["lengths"]
        # MFC favors graph-level over node-level heads in the multihead CI run
        if model_type == "MFC" and ci_input == "ci_multihead.json":
            config["NeuralNetwork"]["Architecture"]["task_weights"][0] = 2

        perc_train = config["NeuralNetwork"]["Training"]["perc_train"]
        for name, rel in config["Dataset"]["path"].items():
            if name == "total":
                num = num_samples_tot
            elif name == "train":
                num = int(num_samples_tot * perc_train)
            else:
                num = int(num_samples_tot * (1 - perc_train) * 0.5)
            # key the cached dataset dir by its size: tests with different
            # num_samples_tot must not silently share (and therefore train
            # on whichever size generated first)
            data_path = os.path.join(workdir, f"{rel}_{num}")
            config["Dataset"]["path"][name] = data_path
            if not os.path.exists(data_path) or not os.listdir(data_path):
                deterministic_graph_data(data_path, number_configurations=num)

        hydragnn_tpu.run_training(copy.deepcopy(config))
        error, error_rmse_task, true_values, predicted_values = (
            hydragnn_tpu.run_prediction(copy.deepcopy(config))
        )

        thresholds = dict(THRESHOLDS)
        if use_lengths and "vector" not in ci_input:
            thresholds["CGCNN"] = [0.175, 0.175]
            thresholds["PNA"] = [0.10, 0.10]
        if use_lengths and "vector" in ci_input:
            thresholds["PNA"] = [0.2, 0.15]
        if ci_input == "ci_conv_head.json":
            thresholds["GIN"] = [0.25, 0.40]

        for ihead in range(len(true_values)):
            assert (
                error_rmse_task[ihead] < thresholds[model_type][0]
            ), f"head {ihead} RMSE {error_rmse_task[ihead]} for {model_type}"
            mae = float(
                np.abs(
                    np.asarray(true_values[ihead])
                    - np.asarray(predicted_values[ihead])
                ).mean()
            )
            assert (
                mae < thresholds[model_type][1]
            ), f"head {ihead} sample MAE {mae} for {model_type}"
        assert error < thresholds[model_type][0], f"total error {error}"
    finally:
        os.chdir(cwd)


def unittest_train_model_300(model_type, **sections):
    """``ci.json`` on 300 graphs with ``NeuralNetwork.<section>`` overrides:
    the mode cases (dispatch, aggregation, loss)."""
    unittest_train_model(
        model_type,
        "ci.json",
        False,
        overwrite_config={"NeuralNetwork": sections},
        num_samples_tot=300,
    )
