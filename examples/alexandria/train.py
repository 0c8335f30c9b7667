"""Alexandria workload: periodic bulk crystals, formation energy (graph) +
magnetic moment (node) multihead.

Mirrors ``examples/alexandria`` in the reference (the Alexandria DFT
database of periodic structures). Offline: random rock-salt/CsCl-like
binary crystals with full 3D periodic radius graphs; formation energy is an
electronegativity-difference mixing rule and moments follow the magnetic
species' local environment.
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from common import example_arg, load_config, train_example

from hydragnn_tpu.data import GraphData, radius_graph_pbc
from hydragnn_tpu.utils.periodic_table import element

PAIRS = [(26, 8), (27, 8), (28, 8), (22, 7), (23, 7)]  # FeO CoO NiO TiN VN
MOMENTS = {26: 2.2, 27: 1.7, 28: 0.6, 22: 0.0, 23: 0.3}


def make_crystal(rng, radius, max_neighbours):
    """4x4x4 rock-salt sites: every cell dimension exceeds 2*radius so no
    pair is reachable through two periodic images (the PBC builder rejects
    such cells)."""
    za, zb = PAIRS[int(rng.integers(len(PAIRS)))]
    alat = 4.2 + 0.2 * rng.standard_normal()
    pos, z = [], []
    for i in range(4):
        for j in range(4):
            for k in range(4):
                pos.append([i * alat / 2, j * alat / 2, k * alat / 2])
                z.append(za if (i + j + k) % 2 == 0 else zb)
    # random antisite defects make the node head non-trivial
    z = np.asarray(z, np.float64)
    flips = rng.random(len(z)) < 0.1
    z[flips] = np.where(z[flips] == za, zb, za)
    pos = np.asarray(pos, np.float64) + rng.normal(0, 0.04, (len(z), 3))
    cell = np.diag([2 * alat, 2 * alat, 2 * alat])

    en_a = element(int(za)).en_pauling
    en_b = element(int(zb)).en_pauling
    frac_a = float((z == za).mean())
    energy = -abs(en_a - en_b) * 4 * frac_a * (1 - frac_a) - 0.5

    d = GraphData(
        x=z.astype(np.float32).reshape(-1, 1),
        pos=pos.astype(np.float32),
        supercell_size=cell,
    )
    d.edge_index, lengths, d.extras["edge_offset"] = radius_graph_pbc(
        pos, cell, radius, max_neighbours
    )
    # moment: species value damped by like-neighbor count
    like = np.zeros(len(z))
    for s, r in zip(*d.edge_index):
        like[r] += float(z[s] == z[r])
    moment = np.array([MOMENTS.get(int(zi), 0.0) for zi in z])
    moment = moment * (1.0 - 0.05 * like)
    d.targets = [np.asarray([energy], np.float32),
                 moment.astype(np.float32).reshape(-1, 1)]
    d.target_types = ["graph", "node"]
    return d


def main():
    config = load_config(__file__, "alexandria.json")
    arch = config["NeuralNetwork"]["Architecture"]
    num_samples = int(example_arg("num_samples", 600))
    rng = np.random.default_rng(11)
    dataset = [
        make_crystal(rng, arch["radius"], arch["max_neighbours"])
        for _ in range(num_samples)
    ]
    train_example(config, dataset, log_name="alexandria")


if __name__ == "__main__":
    main()
