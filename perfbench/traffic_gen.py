"""The one traffic generator: seeded atomistic graphs from a mix file.

A mix (``perfbench/traffic/<name>.json``) is data only. It fixes the set of
graph sizes (quantiles of a clipped log-normal) and the geometries: jittered
sites of the mix's ``shape`` (``traffic/<shape>.py``: a periodic ``slab`` or
a compact non-periodic ``cluster`` today), graph ``i`` drawn from
``(geometry_seed, i)``. The run's seed draws the species (so inputs and
targets) and the order of the graphs in the data set (so every batch's
composition). Every seed therefore trains on the same multiset of node AND
edge counts: the program derives the same padded layouts, and compiles the
same shapes, whatever the seed.

Targets are smooth functions of the geometry, so a model has something to
fit: per-atom species-weighted coordination, its per-graph mean, and (for
``node_target_dim == 3``) the force of a smooth pair potential.

Copied and generalised from ``chip_smoke.py make_graphs`` (slabs) and the
size law of ``benchmarks/bucket_bench.py _oc20_samples``.
"""

import importlib.util
import math
import os
import statistics

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_sizes(law, count):
    """``count`` graph sizes: the (i + 0.5) / count quantiles of
    ``round(lognormal(ln median, sigma))`` clipped to [min, max]."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / count) for i in range(count)])
    n = np.rint(np.exp(math.log(law["median"]) + law["sigma"] * z))
    return np.clip(n, law["min"], law["max"]).astype(np.int64)


def _species_table(num_species, input_dim):
    """Fixed per-species descriptors in [0, 1] (stand-ins for atomic
    number, electronegativity, ...); column 0 is species / (S - 1)."""
    s = np.arange(num_species, dtype=np.float64)[:, None]
    k = np.arange(input_dim, dtype=np.float64)[None, :]
    table = ((s + 1.0) * (2.0 * k + 1.0) % 7.0) / 7.0
    table[:, 0] = s[:, 0] / max(num_species - 1, 1)
    return table


def load_shape(name):
    """``sites(n, mix, rng) -> ([n, 3] sites, cell or None)`` of the shape
    ``name``, from ``traffic/<name>.py``: a new geometry is a new file."""
    path = os.path.join(HERE, "traffic", name + ".py")
    spec = importlib.util.spec_from_file_location("traffic_shape_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.sites


def make_graph(n, mix, shape_sites, geometry_rng, rng):
    """One graph as plain arrays: model inputs ``x_in [n, input_dim]``,
    ``pos [n, 3]``, ``cell`` ([3] or None), ``y_graph [1]``,
    ``y_node [n, node_target_dim]``. Positions come from ``shape_sites``
    and ``geometry_rng``, species from ``rng``."""
    sites, cell = shape_sites(n, mix, geometry_rng)
    pos = sites + geometry_rng.normal(
        0.0, mix["jitter"] * mix["lattice_a"], (n, 3)
    )
    species = rng.integers(0, mix["species"], n)
    dvec = pos[:, None, :] - pos[None, :, :]
    if cell is not None:
        dvec -= np.round(dvec / cell) * cell  # minimum image
    dist = np.linalg.norm(dvec, axis=-1)
    np.fill_diagonal(dist, np.inf)
    weight = 1.0 + 0.3 * species[None, :]
    bell = np.exp(-((dist / mix["radius"]) ** 2) * 4.0) * weight
    coord = (bell.sum(1) - 4.0) / 2.0
    if mix["node_target_dim"] == 1:
        y_node = coord[:, None]
    elif mix["node_target_dim"] == 3:
        # -d/dr_i of sum_ij bell_ij (a smooth, rotation-covariant vector)
        pull = (bell * 8.0 / mix["radius"] ** 2)[..., None] * dvec
        y_node = pull.sum(1)
    else:
        raise ValueError("node_target_dim must be 1 or 3")
    table = _species_table(mix["species"], mix["input_dim"])
    return {
        "x_in": table[species].astype(np.float32),
        "pos": pos.astype(np.float32),
        "cell": cell,
        "y_graph": np.asarray([coord.mean()], np.float32),
        "y_node": y_node.astype(np.float32),
    }


def make_graphs(mix, count, seed, first=0):
    """``count`` graphs from ``seed``: the mix's geometries ``first`` to
    ``first + count - 1`` with this seed's species, in this seed's order."""
    rng = np.random.default_rng(seed)
    sizes = load_sizes(mix["size_law"], count)
    shape_sites = load_shape(mix["shape"])
    graphs = [
        make_graph(
            int(n), mix, shape_sites,
            np.random.default_rng([mix["geometry_seed"], first + i]), rng,
        )
        for i, n in enumerate(sizes)
    ]
    return [graphs[i] for i in rng.permutation(count)]
