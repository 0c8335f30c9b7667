"""Config-schema guard tests (round-3 verdict item 9).

Mirrors the reference's ``tests/test_config.py:15-40`` (required sections
present in the shipped example configs) and adds negative tests pinning
``update_config``'s validation/error paths so key drift in
``hydragnn_tpu/utils/config.py`` is caught directly, not incidentally.
"""

import copy
import json
import os

import numpy as np
import pytest

from hydragnn_tpu.utils.config import (
    check_output_dim_consistent,
    merge_config,
    update_config,
    update_config_edge_dim,
    update_config_equivariance,
    update_config_NN_outputs,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_EXAMPLE_CONFIGS = [
    "lsms/lsms.json",
    "qm9/qm9.json",
    "md17/md17.json",
    "open_catalyst_2020/oc20.json",
    "mptrj/mptrj.json",
    "multidataset/gfm.json",
]


@pytest.mark.parametrize("config_file", _EXAMPLE_CONFIGS)
def pytest_example_config_schema(config_file):
    """Same contract as the reference test: every shipped example config
    carries the required categories and keys."""
    with open(os.path.join(_REPO, "examples", config_file)) as f:
        config = json.load(f)

    assert "NeuralNetwork" in config, "Missing required input category"
    for key in ("Architecture", "Variables_of_interest", "Training"):
        assert key in config["NeuralNetwork"], f"Missing NeuralNetwork.{key}"
    arch = config["NeuralNetwork"]["Architecture"]
    for key in ("model_type", "hidden_dim", "num_conv_layers", "output_heads",
                "task_weights"):
        assert key in arch, f"Missing Architecture.{key}"
    voi = config["NeuralNetwork"]["Variables_of_interest"]
    for key in ("input_node_features", "output_index", "type"):
        assert key in voi, f"Missing Variables_of_interest.{key}"
    training = config["NeuralNetwork"]["Training"]
    for key in ("batch_size", "num_epoch"):
        assert key in training, f"Missing Training.{key}"
    if "Dataset" in config:
        assert "name" in config["Dataset"], "Missing Dataset.name"
        # streaming-only Dataset sections (docs/data.md) name their
        # formats per source; `format` governs the raw->serialized path
        if "streaming" not in config["Dataset"]:
            assert "format" in config["Dataset"], "Missing Dataset.format"
        else:
            for src in config["Dataset"]["streaming"].get("sources", []):
                assert "train" in src, "streaming source missing train path"


class _Sample:
    def __init__(self, n=4, targets=None):
        self.num_nodes = n
        self.num_edges = 2 * n
        self.edge_index = np.stack(
            [np.arange(2 * n) % n, (np.arange(2 * n) + 1) % n]
        ).astype(np.int64)
        self.targets = targets or [np.ones((1,), np.float32),
                                   np.ones((n, 1), np.float32)]


class _Loader:
    def __init__(self, samples):
        self.dataset = samples


def _nn_config(node_head_type="mlp"):
    return {
        "Architecture": {
            "model_type": "GIN",
            "hidden_dim": 8,
            "num_conv_layers": 2,
            "output_heads": {
                "graph": {
                    "num_sharedlayers": 1,
                    "dim_sharedlayers": 8,
                    "num_headlayers": 1,
                    "dim_headlayers": [8],
                },
                "node": {
                    "num_headlayers": 1,
                    "dim_headlayers": [8],
                    "type": node_head_type,
                },
            },
            "task_weights": [1.0, 1.0],
        },
        "Training": {"batch_size": 2, "num_epoch": 1},
        "Variables_of_interest": {
            "input_node_features": [0],
            "output_index": [0, 0],
            "type": ["graph", "node"],
            "denormalize_output": False,
        },
    }


def pytest_update_config_derives_dims():
    samples = [_Sample(4), _Sample(4)]
    loaders = [_Loader(samples)] * 3
    config = update_config({"NeuralNetwork": _nn_config()}, *loaders)
    arch = config["NeuralNetwork"]["Architecture"]
    assert arch["output_dim"] == [1, 1]
    assert arch["output_type"] == ["graph", "node"]
    assert arch["num_nodes"] == 4
    assert arch["input_dim"] == 1
    assert arch["pna_deg"] is None  # GIN
    assert arch["equivariance"] is False
    assert arch["edge_dim"] is None
    assert config["NeuralNetwork"]["Training"]["loss_function_type"] == "mse"
    assert config["NeuralNetwork"]["Training"]["Optimizer"]["type"] == "AdamW"


def pytest_update_config_pna_degree_histogram():
    cfg = {"NeuralNetwork": _nn_config()}
    cfg["NeuralNetwork"]["Architecture"]["model_type"] = "PNA"
    loaders = [_Loader([_Sample(4)])] * 3
    config = update_config(copy.deepcopy(cfg), *loaders)
    arch = config["NeuralNetwork"]["Architecture"]
    # ring graph: every node has in-degree 2 -> histogram [0, 0, 4]
    assert arch["pna_deg"] == [0, 0, 4]
    assert arch["max_neighbours"] == 2


def pytest_auto_dense_aggregation_policy():
    """The measured-crossover policy (ops/agg_policy.py): scatter-heavy models
    pick the dense path at MXU widths with NO config flag; SchNet never
    does; an explicit flag and partition mode always win. (EGNN's row is
    ``tests/test_agg_policy.py``'s.)"""
    from hydragnn_tpu.ops.agg_policy import needs_dense_neighbors

    for m in ("PNA", "GAT", "MFC", "DimeNet"):
        assert needs_dense_neighbors({"model_type": m, "hidden_dim": 256})
        assert needs_dense_neighbors({"model_type": m, "hidden_dim": 96})
        assert not needs_dense_neighbors({"model_type": m, "hidden_dim": 64})
    for m in ("GIN", "SAGE"):
        assert needs_dense_neighbors({"model_type": m, "hidden_dim": 256})
        assert not needs_dense_neighbors({"model_type": m, "hidden_dim": 128})
    # SchNet: one fused scatter/layer — dense never wins. CGCNN runs
    # at input_dim width, so hidden_dim is not a crossover signal.
    for m in ("SchNet", "CGCNN"):
        assert not needs_dense_neighbors({"model_type": m, "hidden_dim": 512})
    # CGCNN's own rule keys on input_dim — its true conv width — and
    # INVERSELY: the dense frame's gather traffic grows with input width
    # while the scatter cost it removes stays flat (round-5 measured
    # crossover). Narrow inputs (the realistic case) go dense.
    assert needs_dense_neighbors(
        {"model_type": "CGCNN", "hidden_dim": 64, "input_dim": 4}
    )
    assert needs_dense_neighbors(
        {"model_type": "CGCNN", "hidden_dim": 512, "input_dim": 64}
    )
    assert not needs_dense_neighbors(
        {"model_type": "CGCNN", "hidden_dim": 64, "input_dim": 256}
    )
    # absent input_dim stays conservative (segment), whatever the hidden
    assert not needs_dense_neighbors({"model_type": "CGCNN", "hidden_dim": 512})
    # explicit override beats the policy in both directions
    assert not needs_dense_neighbors(
        {"model_type": "PNA", "hidden_dim": 256, "dense_aggregation": False}
    )
    assert needs_dense_neighbors(
        {"model_type": "EGNN", "hidden_dim": 64, "dense_aggregation": True}
    )
    # partition mode always builds its own per-shard lists
    assert not needs_dense_neighbors(
        {"model_type": "PNA", "hidden_dim": 256, "partition_axis": "data"}
    )


def pytest_update_config_records_auto_dense():
    """update_config writes the resolved AUTO decision into the arch so
    saved configs show which path ran."""
    cfg = {"NeuralNetwork": _nn_config()}
    cfg["NeuralNetwork"]["Architecture"]["model_type"] = "PNA"
    cfg["NeuralNetwork"]["Architecture"]["hidden_dim"] = 256
    loaders = [_Loader([_Sample(4)])] * 3
    config = update_config(copy.deepcopy(cfg), *loaders)
    assert config["NeuralNetwork"]["Architecture"]["dense_aggregation"] is True
    cfg["NeuralNetwork"]["Architecture"]["hidden_dim"] = 8
    config = update_config(copy.deepcopy(cfg), *loaders)
    assert config["NeuralNetwork"]["Architecture"]["dense_aggregation"] is False


def pytest_update_config_mfc_degree_bound():
    """MFC configs derive a dataset-wide static in-degree bound so the
    conv can slice dead banks from its one-hot degree matmul."""
    cfg = {"NeuralNetwork": _nn_config()}
    cfg["NeuralNetwork"]["Architecture"]["model_type"] = "MFC"
    cfg["NeuralNetwork"]["Architecture"]["max_neighbours"] = 50
    loaders = [_Loader([_Sample(4)])] * 3
    config = update_config(copy.deepcopy(cfg), *loaders)
    # ring graph: every node has in-degree exactly 2
    assert config["NeuralNetwork"]["Architecture"]["mfc_degree_bound"] == 2


def pytest_update_config_rejects_mlp_per_node_variable_size():
    """``mlp_per_node`` + variable graph size must raise
    (``config_utils.py:156-192`` analog)."""
    cfg = {"NeuralNetwork": _nn_config(node_head_type="mlp_per_node")}
    loaders = [_Loader([_Sample(4), _Sample(6)])] * 3
    with pytest.raises(ValueError, match="mlp_per_node"):
        update_config(cfg, *loaders)


def pytest_update_config_env_overrides_size_detection(monkeypatch):
    monkeypatch.setenv("HYDRAGNN_USE_VARIABLE_GRAPH_SIZE", "1")
    cfg = {"NeuralNetwork": _nn_config(node_head_type="mlp_per_node")}
    loaders = [_Loader([_Sample(4)])] * 3  # fixed size, but env says variable
    with pytest.raises(ValueError, match="mlp_per_node"):
        update_config(cfg, *loaders)


def pytest_update_config_unknown_output_type():
    nn = _nn_config()
    nn["Variables_of_interest"]["type"] = ["graph", "bogus"]
    with pytest.raises(ValueError, match="Unknown output type"):
        update_config_NN_outputs(nn, _Sample(4), False)


def pytest_equivariance_validation():
    assert update_config_equivariance({"model_type": "EGNN",
                                       "equivariance": True})["equivariance"]
    with pytest.raises(AssertionError, match="equivariance"):
        update_config_equivariance({"model_type": "GIN", "equivariance": True})
    # absent key defaults to False
    assert update_config_equivariance({"model_type": "GIN"})[
        "equivariance"] is False


def pytest_edge_dim_validation():
    arch = update_config_edge_dim({"model_type": "PNA",
                                   "edge_features": ["length"]})
    assert arch["edge_dim"] == 1
    with pytest.raises(AssertionError, match="[Ee]dge"):
        update_config_edge_dim({"model_type": "GIN",
                                "edge_features": ["length"]})
    # CGCNN requires constant width: edge_dim 0 when no features given
    assert update_config_edge_dim({"model_type": "CGCNN"})["edge_dim"] == 0
    assert update_config_edge_dim({"model_type": "GIN"})["edge_dim"] is None


def pytest_output_dim_consistency_check():
    config = {
        "Dataset": {
            "graph_features": {"dim": [1]},
            "node_features": {"dim": [1]},
        },
        "NeuralNetwork": {
            "Variables_of_interest": {
                "type": ["graph"],
                "output_index": [0],
            }
        },
    }
    check_output_dim_consistent(_Sample(4), config)  # consistent: no raise
    bad = copy.deepcopy(config)
    bad["Dataset"]["graph_features"]["dim"] = [7]
    with pytest.raises(AssertionError):
        check_output_dim_consistent(_Sample(4), bad)


def pytest_merge_config_deep():
    a = {"x": {"y": 1, "z": 2}, "w": 3}
    b = {"x": {"y": 10}, "v": 4}
    out = merge_config(a, b)
    assert out == {"x": {"y": 10, "z": 2}, "w": 3, "v": 4}
    assert a == {"x": {"y": 1, "z": 2}, "w": 3}  # inputs untouched
