"""Config system — schema defaulting and data-derived fields.

Parity with ``hydragnn/utils/config_utils.py:24-318``: same JSON section
names (Verbosity / Dataset / NeuralNetwork{Architecture, Variables_of_interest,
Training} / Visualization) so reference configs translate mechanically;
``update_config`` derives input/output dims from the first sample, the PNA
degree histogram, edge_dim/equivariance validation, and min-max
denormalization tables.
"""

import json
import os
import pickle
from copy import deepcopy

import numpy as np


def update_config(config, train_loader, val_loader, test_loader):
    env = os.getenv("HYDRAGNN_USE_VARIABLE_GRAPH_SIZE")
    if env is None:
        graph_size_variable = check_if_graph_size_variable(
            train_loader, val_loader, test_loader
        )
    else:
        graph_size_variable = bool(int(env))

    ds = config.get("Dataset", {})
    if "graph_features" in ds or "node_features" in ds:
        # a Dataset section without declared feature dims (e.g. one that
        # only carries the `streaming` spec) has nothing to cross-check
        check_output_dim_consistent(train_loader.dataset[0], config)

    config["NeuralNetwork"] = update_config_NN_outputs(
        config["NeuralNetwork"], train_loader.dataset[0], graph_size_variable
    )
    config = normalize_output_config(config)

    config["NeuralNetwork"]["Architecture"]["input_dim"] = len(
        config["NeuralNetwork"]["Variables_of_interest"]["input_node_features"]
    )

    arch = config["NeuralNetwork"]["Architecture"]
    from hydragnn_tpu.parallel.distributed import host_allreduce
    if arch["model_type"] == "PNA":
        deg = gather_deg(train_loader.dataset)
        arch["pna_deg"] = deg.tolist()
        arch["max_neighbours"] = len(deg) - 1
    else:
        arch["pna_deg"] = None
    if "dense_aggregation" not in arch and not arch.get("partition_axis"):
        # record the aggregation-family decision so the saved config and
        # downstream consumers see the value THE RUN ACTUALLY USES. The
        # rule has two steps: an explicit true/false in the input config
        # wins, else the width tables decide (ops/agg_policy.py). Partition
        # mode keeps its own explicit opt-in (per-shard lists change the
        # memory equation)
        from hydragnn_tpu.ops.agg_policy import (
            arch_for_auto_policy,
            needs_dense_neighbors,
        )

        arch["dense_aggregation"] = needs_dense_neighbors(
            arch_for_auto_policy(config["NeuralNetwork"])
        )
    if arch["model_type"] == "MFC":
        # dataset-wide max in-degree: a STATIC bound that lets the conv
        # slice dead banks out of its one-hot degree matmul (the reference
        # allocates and applies all max_neighbours+1 banks regardless —
        # MFCStack.py:22-51; parameter shapes here stay identical, only
        # the compute shrinks). Derived ONLY from plain in-memory splits
        # (store-backed datasets — graph_sizes/epoch_begin markers — would
        # pay an O(dataset) edge walk at startup, or store-transport
        # traffic for DistDataset); everywhere else the bound is cleared
        # to None, never trusted from a loaded config: a stale bound from
        # a smaller dataset would silently clamp higher-degree nodes to
        # the wrong bank. The walk-or-not decision is reduced across
        # hosts first (min) so no host is stranded in max_in_degree's
        # allreduce if dataset wrappers differ.
        cheap = all(
            not hasattr(ld.dataset, "epoch_begin")
            and not hasattr(ld.dataset, "graph_sizes")
            for ld in (train_loader, val_loader, test_loader)
        )
        all_cheap = bool(host_allreduce(np.asarray([int(cheap)]), op="min")[0])
        arch["mfc_degree_bound"] = (
            max_in_degree(
                ld.dataset for ld in (train_loader, val_loader, test_loader)
            )
            if all_cheap
            else None
        )

    for key in (
        "radius",
        "num_gaussians",
        "num_filters",
        "envelope_exponent",
        "num_after_skip",
        "num_before_skip",
        "basis_emb_size",
        "int_emb_size",
        "out_emb_size",
        "num_radial",
        "num_spherical",
    ):
        arch.setdefault(key, None)

    config["NeuralNetwork"]["Architecture"] = update_config_edge_dim(arch)
    config["NeuralNetwork"]["Architecture"] = update_config_equivariance(
        config["NeuralNetwork"]["Architecture"]
    )

    arch = config["NeuralNetwork"]["Architecture"]
    arch.setdefault("freeze_conv_layers", False)
    arch.setdefault("initial_bias", None)
    arch.setdefault("activation_function", "relu")
    arch.setdefault("SyncBatchNorm", False)

    training = config["NeuralNetwork"]["Training"]
    training.setdefault("loss_function_type", "mse")
    training.setdefault("conv_checkpointing", False)
    if "Optimizer" not in training:
        training["Optimizer"] = {"type": "AdamW", "learning_rate": 1e-3}
    return config


def update_config_equivariance(arch):
    equivariant_models = ["EGNN", "SchNet"]
    if arch.get("equivariance"):
        assert (
            arch["model_type"] in equivariant_models
        ), "E(3) equivariance can only be ensured for EGNN and SchNet."
    elif "equivariance" not in arch:
        arch["equivariance"] = False
    return arch


def update_config_edge_dim(arch):
    arch["edge_dim"] = None
    edge_models = ["PNA", "CGCNN", "SchNet", "EGNN"]
    if arch.get("edge_features"):
        assert (
            arch["model_type"] in edge_models
        ), "Edge features can only be used with EGNN, SchNet, PNA and CGCNN."
        arch["edge_dim"] = len(arch["edge_features"])
    elif arch["model_type"] == "CGCNN":
        arch["edge_dim"] = 0
    return arch


def check_if_graph_size_variable(train_loader, val_loader, test_loader) -> bool:
    sizes = set()
    for loader in (train_loader, val_loader, test_loader):
        for d in loader.dataset:
            sizes.add(d.num_nodes)
            if len(sizes) > 1:
                break
        if len(sizes) > 1:
            break
    variable = len(sizes) > 1
    from hydragnn_tpu.parallel.distributed import host_allreduce

    return bool(host_allreduce(np.asarray([int(variable)]), op="max")[0] > 0)


def check_output_dim_consistent(data, config):
    output_type = config["NeuralNetwork"]["Variables_of_interest"]["type"]
    output_index = config["NeuralNetwork"]["Variables_of_interest"]["output_index"]
    for ihead, (t, idx) in enumerate(zip(output_type, output_index)):
        dim = data.targets[ihead].shape[-1] if data.targets[ihead].ndim > 1 else data.targets[ihead].shape[0]
        if t == "graph":
            assert dim == config["Dataset"]["graph_features"]["dim"][idx]
        elif t == "node":
            assert dim == config["Dataset"]["node_features"]["dim"][idx]


def update_config_NN_outputs(nn_config, data, graph_size_variable: bool):
    """Derive head output dims from the first sample's targets
    (``config_utils.py:156-192``)."""
    output_type = nn_config["Variables_of_interest"]["type"]
    dims_list = []
    for ihead, t in enumerate(output_type):
        if t == "graph":
            dims_list.append(int(data.targets[ihead].shape[0]))
        elif t == "node":
            if (
                graph_size_variable
                and nn_config["Architecture"]["output_heads"]["node"]["type"]
                == "mlp_per_node"
            ):
                raise ValueError(
                    '"mlp_per_node" is not allowed for variable graph size'
                )
            dims_list.append(int(data.targets[ihead].shape[-1]))
        else:
            raise ValueError("Unknown output type", t)
    nn_config["Architecture"]["output_dim"] = dims_list
    nn_config["Architecture"]["output_type"] = list(output_type)
    nn_config["Architecture"]["num_nodes"] = int(data.num_nodes)
    return nn_config


def normalize_output_config(config):
    var_config = config["NeuralNetwork"]["Variables_of_interest"]
    if var_config.get("denormalize_output"):
        if (
            var_config.get("minmax_node_feature") is not None
            and var_config.get("minmax_graph_feature") is not None
        ):
            dataset_path = None
        elif list(config["Dataset"]["path"].values())[0].endswith(".pkl"):
            dataset_path = list(config["Dataset"]["path"].values())[0]
        else:
            base = os.environ.get("SERIALIZED_DATA_PATH", os.getcwd())
            if "total" in config["Dataset"]["path"]:
                dataset_path = (
                    f"{base}/serialized_dataset/{config['Dataset']['name']}.pkl"
                )
            else:
                dataset_path = (
                    f"{base}/serialized_dataset/"
                    f"{config['Dataset']['name']}_train.pkl"
                )
        var_config = update_config_minmax(dataset_path, var_config)
    else:
        var_config["denormalize_output"] = False
    config["NeuralNetwork"]["Variables_of_interest"] = var_config
    return config


def update_config_minmax(dataset_path, var_config):
    """Load denormalization tables (``config_utils.py:219-243``)."""
    if (
        "minmax_node_feature" not in var_config
        and "minmax_graph_feature" not in var_config
    ):
        with open(dataset_path, "rb") as f:
            node_minmax = pickle.load(f)
            graph_minmax = pickle.load(f)
    else:
        node_minmax = np.asarray(var_config["minmax_node_feature"])
        graph_minmax = np.asarray(var_config["minmax_graph_feature"])
    var_config["x_minmax"] = [
        node_minmax[:, i].tolist() for i in var_config["input_node_features"]
    ]
    var_config["y_minmax"] = []
    for t, idx in zip(var_config["type"], var_config["output_index"]):
        if t == "graph":
            var_config["y_minmax"].append(graph_minmax[:, idx].tolist())
        elif t == "node":
            var_config["y_minmax"].append(node_minmax[:, idx].tolist())
        else:
            raise ValueError("Unknown output type", t)
    return var_config


def _in_degree_counts(d) -> np.ndarray:
    """Per-node in-degree of one sample (shared by the PNA histogram and
    the MFC bound so the two derivations cannot drift)."""
    return np.bincount(d.edge_index[1], minlength=d.num_nodes)


def max_in_degree(datasets) -> int:
    """Dataset-wide max in-degree (all splits), reduced across hosts."""
    from hydragnn_tpu.parallel.distributed import host_allreduce

    m = 0
    for ds in datasets:
        for d in ds:
            if d.num_edges:
                m = max(m, int(_in_degree_counts(d).max()))
    return int(host_allreduce(np.asarray([m]), op="max")[0])


def gather_deg(dataset) -> np.ndarray:
    """In-degree histogram over the dataset for PNA scalers
    (``preprocess/utils.py:177-234``), reduced across hosts."""
    from hydragnn_tpu.parallel.distributed import host_allreduce

    max_deg = 0
    for d in dataset:
        if d.num_edges:
            max_deg = max(max_deg, int(_in_degree_counts(d).max()))
    max_deg = int(host_allreduce(np.asarray([max_deg]), op="max")[0])
    deg = np.zeros(max_deg + 1, dtype=np.int64)
    for d in dataset:
        deg += np.bincount(_in_degree_counts(d), minlength=max_deg + 1)
    return host_allreduce(deg, op="sum")


def get_log_name_config(config):
    """Run naming (``config_utils.py:246-279``)."""
    arch = config["NeuralNetwork"]["Architecture"]
    training = config["NeuralNetwork"]["Training"]
    name = config["Dataset"]["name"]
    cut = name.rfind("_") if name.rfind("_") > 0 else None
    return (
        f"{arch['model_type']}-r-{arch.get('radius')}"
        f"-ncl-{arch['num_conv_layers']}-hd-{arch['hidden_dim']}"
        f"-ne-{training['num_epoch']}"
        f"-lr-{training['Optimizer']['learning_rate']}"
        f"-bs-{training['batch_size']}"
        f"-data-{name[:cut]}"
        "-node_ft-"
        + "".join(
            str(x)
            for x in config["NeuralNetwork"]["Variables_of_interest"][
                "input_node_features"
            ]
        )
        + "-task_weights-"
        + "".join(f"{w}-" for w in arch["task_weights"])
    )


def save_config(config, log_name, path="./logs/"):
    from hydragnn_tpu.parallel.distributed import get_comm_size_and_rank

    _, rank = get_comm_size_and_rank()
    if rank == 0:
        fname = os.path.join(path, log_name, "config.json")
        os.makedirs(os.path.dirname(fname), exist_ok=True)
        with open(fname, "w") as f:
            json.dump(config, f, indent=4, default=str)


def merge_config(a: dict, b: dict) -> dict:
    """Deep merge b into a (``config_utils.py:310-318``)."""
    result = deepcopy(a)
    for k, v in b.items():
        if isinstance(result.get(k), dict) and isinstance(v, dict):
            result[k] = merge_config(result[k], v)
        else:
            result[k] = deepcopy(v)
    return result
