"""Lattice sites of a compact non-periodic cluster: the ``n`` sites of a
cubic lattice nearest a random centre."""

import math

import numpy as np


def sites(n, mix, rng):
    """(``[n, 3]`` sites, None: no cell)."""
    a = mix["lattice_a"]
    half = int(math.ceil((3.0 * n / (4.0 * math.pi)) ** (1.0 / 3.0))) + 2
    ax = np.arange(-half, half + 1)
    grid = np.stack(np.meshgrid(ax, ax, ax, indexing="ij"), -1).reshape(-1, 3)
    centre = rng.uniform(-0.5, 0.5, 3)
    d2 = ((grid - centre) ** 2).sum(-1)
    keep = np.sort(np.argsort(d2, kind="stable")[:n])
    return grid[keep].astype(np.float64) * a, None
