"""Lattice sites of a periodic slab: ``layers`` sheets of a cubic lattice,
vacuum above, in-plane cell edges at least twice the cutoff (copied and
generalised from ``chip_smoke.py make_graphs``)."""

import math

import numpy as np


def sites(n, mix, rng):
    """(``[n, 3]`` sites, ``[3]`` orthorhombic cell)."""
    a, layers = mix["lattice_a"], mix["layers"]
    min_cells = int(math.ceil(2.0 * mix["radius"] / a))
    need = int(math.ceil(n / (layers * mix["occupancy"])))
    nx = max(min_cells, int(round(math.sqrt(need))))
    ny = max(min_cells, int(math.ceil(need / nx)))
    grid = np.stack(
        np.meshgrid(np.arange(nx), np.arange(ny), np.arange(layers),
                    indexing="ij"), -1,
    ).reshape(-1, 3)
    keep = np.sort(rng.permutation(len(grid))[:n])
    cell = np.array([nx * a, ny * a, layers * a + mix["vacuum"]])
    return grid[keep].astype(np.float64) * a, cell
