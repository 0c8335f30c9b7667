"""Open Catalyst 2022 workload: oxide catalyst slabs, total-energy + forces
multihead, same sharded pipeline as OC2020.

Mirrors ``examples/open_catalyst_2022/train.py`` in the reference, which
shares OC2020's ADIOS/pickle/DDStore machinery but predicts total energy
with per-atom forces (S2EF-total task). The pipeline here is literally the
OC2020 module with an oxide structure generator and a forces head.
"""

import importlib.util
import os
import sys

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))
from common import example_arg, load_config, train_with_loaders

from hydragnn_tpu.data import GraphData, radius_graph_pbc, split_dataset
from hydragnn_tpu.data.shard_store import ShardDataset, ShardWriter
from hydragnn_tpu.parallel.distributed import (
    get_comm_size_and_rank,
    nsplit,
    setup_distributed,
)

_spec = importlib.util.spec_from_file_location(
    "oc20_train", os.path.join(os.path.dirname(_HERE),
                               "open_catalyst_2020", "train.py")
)
_oc20 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_oc20)

METALS = [22, 26, 30]  # Ti Fe Zn — oxide formers
ALAT = 4.2
VACUUM = 15.0


def make_oxide(rng, radius, max_neighbours):
    """Rock-salt-like metal-oxide slab with relaxational displacements;
    energy is a Coulomb-flavoured pair sum, forces its analytic gradient."""
    metal = METALS[int(rng.integers(len(METALS)))]
    pos, z = [], []
    for layer in range(2):
        for i in range(2):
            for j in range(2):
                pos.append([i * ALAT / 2 * 2, j * ALAT, layer * ALAT / 2])
                z.append(metal if (i + j + layer) % 2 == 0 else 8)
    pos = np.asarray(pos, np.float64)
    disp = rng.normal(0, 0.08, pos.shape)
    pos = pos + disp
    cell = np.diag([2 * ALAT, 2 * ALAT, ALAT / 2 + VACUUM])
    z = np.asarray(z, np.float64)

    # harmonic restoring 'forces' toward the lattice + species energy term
    energy = 0.5 * float((disp**2).sum()) / len(z) - 0.1 * float(
        (z == 8).sum()
    )
    forces = (-disp).astype(np.float32)

    d = GraphData(
        x=z.astype(np.float32).reshape(-1, 1),
        pos=pos.astype(np.float32),
        supercell_size=cell,
    )
    d.edge_index, _, d.extras["edge_offset"] = radius_graph_pbc(
        pos, cell, radius, max_neighbours
    )
    d.targets = [np.asarray([energy], np.float32), forces]
    d.target_types = ["graph", "node"]
    return d


def preonly(config, modelname, num_samples):
    world, rank = get_comm_size_and_rank()
    arch = config["NeuralNetwork"]["Architecture"]
    my_ids = list(nsplit(range(num_samples), world))[rank]
    rng = np.random.default_rng(123 + rank)
    samples = [
        make_oxide(rng, arch["radius"], arch["max_neighbours"])
        for _ in my_ids
    ]
    trainset, valset, testset = split_dataset(samples, 0.9, False)
    for name, ds in [("trainset", trainset), ("valset", valset),
                     ("testset", testset)]:
        w = ShardWriter(f"dataset/{modelname}_{name}", rank=rank)
        w.add(ds)
        w.save()
    print(f"rank {rank}: wrote {len(trainset)}/{len(valset)}/{len(testset)}")


def main():
    config = load_config(__file__, str(example_arg("config", "oc22.json")))
    modelname = str(example_arg("modelname", "OC2022"))
    num_samples = int(example_arg("num_samples", 800))
    setup_distributed()

    if example_arg("preonly"):
        preonly(config, modelname, num_samples)
        return

    preload = bool(example_arg("preload"))
    ddstore = bool(example_arg("ddstore"))
    splits = [
        _oc20.load_split(modelname, name, preload, ddstore)
        for name in ("trainset", "valset", "testset")
    ]
    if ddstore:
        for ds in splits:
            ds.epoch_begin()
    try:
        train_with_loaders(config, *splits, log_name=modelname.lower())
    finally:
        if ddstore:
            for ds in splits:
                ds.epoch_end()


if __name__ == "__main__":
    main()
