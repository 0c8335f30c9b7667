"""From a cell's files to the program's own objects.

Writes the seeded graphs in the serialized-pickle format the program's
loaders read, fills the ``Dataset`` section the way ``run_training`` expects
it, and builds loaders, model, trainer and state through the same calls as
``hydragnn_tpu/train/driver.py run_training_impl``. Nothing here decides a
precision, an aggregation path or a kernel: the program's policy does.
"""

import copy
import json
import os
import pickle
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(name, benchmark, files=HERE):
    """(cell, config file, traffic mix) for the cell ``name`` of the
    parsed ``BENCHMARK.json``; every file is found by the names there
    (traffic mixes and limits under ``files``)."""
    cell = next((w for w in benchmark["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in benchmark["configs"] if c["name"] == cell["config"])
    root = os.path.dirname(HERE)
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(files, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    return cell, config, mix


def batch_size_for(mix, chips):
    """The rung the sizing rule gave, per number of chips."""
    rung = mix["batch_size"].get(str(chips))
    if rung is None:
        raise SystemExit(f"traffic file has no batch_size for {chips} chip(s)")
    return int(rung)


def write_split(path, graphs):
    """One split in the serialized-dataset format (two min-max tables the
    loader skips, then the sample list). Node features are the model
    inputs followed by the per-atom target."""
    from hydragnn_tpu.data.dataobj import GraphData

    samples = [
        GraphData(
            x=np.concatenate([g["x_in"], g["y_node"]], 1),
            pos=g["pos"],
            y=g["y_graph"],
            supercell_size=None if g["cell"] is None else np.diag(g["cell"]),
        )
        for g in graphs
    ]
    with open(path, "wb") as f:
        pickle.dump(np.zeros((2, 2)), f)
        pickle.dump(np.zeros((2, 1)), f)
        pickle.dump(samples, f)


def write_dataset(out_dir, graphs, evals):
    """{split: path} of the three pickles the program's loaders want; the
    validation and test splits (never iterated here) share ``evals``."""
    paths = {s: os.path.join(out_dir, s + ".pkl")
             for s in ("train", "validate", "test")}
    write_split(paths["train"], graphs)
    write_split(paths["validate"], evals)
    write_split(paths["test"], evals)
    return paths


def hydragnn_config(config, mix, cell, paths, batch_size):
    """The dict ``run_training`` would be given: the configuration file's
    ``NeuralNetwork`` section, the traffic's feature layout as ``Dataset``,
    the rung as batch size, and the cell's mesh."""
    nn = copy.deepcopy(config["NeuralNetwork"])
    voi = nn["Variables_of_interest"]
    if len(voi["input_node_features"]) != mix["input_dim"]:
        raise SystemExit(
            "configuration reads %d input features, traffic makes %d"
            % (len(voi["input_node_features"]), mix["input_dim"])
        )
    training = nn["Training"]
    training["batch_size"] = batch_size
    training.update(mix.get("training", {}))
    if cell["chips"] > 1:
        training.setdefault("mesh_shape", [cell["chips"], 1])
    dims = [1] * mix["input_dim"] + [mix["node_target_dim"]]
    return {
        "Verbosity": {"level": 0},
        "Dataset": {
            "name": cell["name"],
            "format": "pickle",
            "compositional_stratified_splitting": False,
            "rotational_invariance": False,
            "path": dict(paths),
            "node_features": {
                "name": [f"in{i}" for i in range(mix["input_dim"])]
                + [voi["output_names"][1]],
                "dim": dims,
                "column_index": list(np.cumsum([0] + dims[:-1]).tolist()),
            },
            "graph_features": {
                "name": [voi["output_names"][0]],
                "dim": [1],
                "column_index": [0],
            },
        },
        "NeuralNetwork": nn,
        "Visualization": {"create_plots": False},
    }


def build_program(cfg):
    """Loaders, model, trainer and state, by the calls of
    ``run_training_impl`` (its checkpoint, scalar-writer and elastic parts
    left out: they are outside the measured window)."""
    from hydragnn_tpu.data.loaders import dataset_loading_and_splitting
    from hydragnn_tpu.obs import runtime as obs
    from hydragnn_tpu.parallel.distributed import setup_distributed
    from hydragnn_tpu.parallel.mesh import resolve_mesh
    from hydragnn_tpu.train.driver import _build_model_and_trainer
    from hydragnn_tpu.utils import tracer as tr
    from hydragnn_tpu.utils.compile_cache import enable_compile_cache
    from hydragnn_tpu.utils.config import get_log_name_config, update_config

    enable_compile_cache()
    setup_distributed()
    resolve_mesh(cfg["NeuralNetwork"]["Training"])
    tr.initialize()
    t = time.perf_counter()
    loaders = dataset_loading_and_splitting(cfg)
    cfg = update_config(cfg, *loaders)
    timings = {"load_s": time.perf_counter() - t}
    log_name = get_log_name_config(cfg)
    telemetry = obs.init_run_telemetry(cfg, log_name)
    if getattr(loaders[0], "plan_event", None):
        obs.emit("bucket_plan", **loaders[0].plan_event)
    t = time.perf_counter()
    model, trainer, state = _build_model_and_trainer(cfg, loaders[0], 0)
    timings["model_s"] = time.perf_counter() - t
    return cfg, loaders[0], model, trainer, state, telemetry, timings
