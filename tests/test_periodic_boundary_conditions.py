"""PBC neighbor-count checks against analytic expectations.

Mirrors the reference strategy (``tests/test_periodic_boundary_conditions.py:
25-123``): build small crystals with known coordination and assert exact edge
counts with and without periodic images.
"""

import numpy as np
import pytest

from hydragnn_tpu.data.radius_graph import radius_graph, radius_graph_pbc


def _bcc_supercell(n):
    """n x n x n BCC supercell with lattice constant 1."""
    pts = []
    for x in range(n):
        for y in range(n):
            for z in range(n):
                pts.append([x, y, z])
                pts.append([x + 0.5, y + 0.5, z + 0.5])
    return np.asarray(pts, dtype=np.float64), float(n) * np.eye(3)


def pytest_bcc_coordination():
    # BCC first neighbor shell: 8 at distance sqrt(3)/2 ~ 0.866. Use a 2x2x2
    # supercell so each neighbor is a distinct atom (a 1-cell would connect
    # the same pair through several images, which — like the reference's
    # duplicate-edge assert — is rejected).
    pos, cell = _bcc_supercell(2)
    edge_index, lengths, _ = radius_graph_pbc(pos, cell, radius=0.9, max_neighbors=100)
    assert edge_index.shape[1] == 8 * pos.shape[0]
    assert np.allclose(lengths, np.sqrt(3) / 2, atol=1e-6)
    # without PBC the corner atom at the origin keeps only its in-cell shell
    ei = radius_graph(pos, radius=0.9, max_neighbors=100)
    assert ei.shape[1] < 8 * pos.shape[0]


def pytest_bcc_second_shell():
    # radius 1.05 adds the 6 second-shell neighbors at distance 1.0
    # (3x3x3 supercell keeps +x / -x neighbors distinct atoms)
    pos, cell = _bcc_supercell(3)
    edge_index, lengths, _ = radius_graph_pbc(pos, cell, radius=1.05, max_neighbors=100)
    per_atom = edge_index.shape[1] / pos.shape[0]
    assert per_atom == 8 + 6
    n_first = int(np.sum(np.isclose(lengths, np.sqrt(3) / 2, atol=1e-6)))
    n_second = int(np.sum(np.isclose(lengths, 1.0, atol=1e-6)))
    assert n_first == 8 * pos.shape[0]
    assert n_second == 6 * pos.shape[0]


def pytest_dimer_in_vacuum_cell():
    # a dimer in a large cell: PBC must not add any extra neighbors
    pos = np.array([[0.0, 0.0, 0.0], [0.74, 0.0, 0.0]])
    cell = 20.0 * np.eye(3)
    edge_index, lengths, _ = radius_graph_pbc(pos, cell, radius=1.0, max_neighbors=10)
    assert edge_index.shape[1] == 2
    assert np.allclose(lengths, 0.74, atol=1e-6)


def pytest_pbc_edge_lengths_cross_boundary():
    # atom pair split across the boundary: minimum image distance applies
    pos = np.array([[0.05, 0.5, 0.5], [0.95, 0.5, 0.5]])
    cell = np.eye(3)
    edge_index, lengths, _ = radius_graph_pbc(pos, cell, radius=0.2, max_neighbors=10)
    assert edge_index.shape[1] == 2
    assert np.allclose(lengths, 0.1, atol=1e-6)


def _loop_pbc(pos, cell, radius, cap, pbc=None):
    """The per-edge loop this search replaced (PR 36): every image in
    (i, j, k) order, pairs row by row, each (j, i) once, then the first
    ``cap`` per receiver in that order, with each edge's image offset."""
    shifts = [np.array(s) for s in np.ndindex(3, 3, 3)]
    out = []
    for s in shifts:
        s = s - 1
        if pbc is not None and np.any((s != 0) & ~np.asarray(pbc)):
            continue
        o = s @ cell
        for i in range(len(pos)):
            for j in range(len(pos)):
                if not s.any() and i == j:
                    continue
                d = np.linalg.norm(pos[j] + o - pos[i])
                if d <= radius:
                    out.append((i, j, d, o))
    assert len({(j, i) for i, j, _, _ in out}) == len(out)
    out.sort(key=lambda e: e[0])  # stable: insertion order per receiver
    kept, seen = [], {}
    for e in out:
        seen[e[0]] = seen.get(e[0], 0) + 1
        if seen[e[0]] <= cap:
            kept.append(e)
    return kept


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def pytest_offsets_give_each_edges_length_in_the_loops_order(seed):
    """Against the per-edge loop: the same edges in the same order, the same
    lengths, and each edge's image offset ``o`` (a lattice vector) with
    ``|pos[j] + o - pos[i]|`` its length; triclinic cells, a slab's pbc mask,
    and the receiver cap included."""
    rng = np.random.default_rng(seed)
    cell = np.diag(rng.uniform(2.2, 3.0, 3)) + rng.uniform(-0.3, 0.3, (3, 3)) * (seed % 2)
    pos = rng.random((int(rng.integers(8, 16)), 3)) @ cell  # fills the cell
    pbc = [None, [True, True, False]][seed // 2]
    cap = int(rng.integers(2, 9))
    edge_index, lengths, offsets = radius_graph_pbc(
        pos, cell, radius=1.0, max_neighbors=cap, pbc=pbc)
    want = _loop_pbc(pos, cell, 1.0, cap, pbc)
    assert edge_index.tolist() == [[j for _, j, _, _ in want], [i for i, _, _, _ in want]]
    np.testing.assert_allclose(lengths, [d for _, _, d, _ in want], rtol=1e-6)
    np.testing.assert_allclose(offsets, [o for _, _, _, o in want], atol=1e-6)
    s, r = edge_index
    np.testing.assert_allclose(
        np.linalg.norm(pos[s] + offsets - pos[r], axis=1), lengths, rtol=1e-5)
    assert offsets.dtype == np.float32 and np.any(offsets != 0)


def pytest_offsets_across_the_boundary():
    pos = np.array([[0.05, 0.5, 0.5], [0.95, 0.5, 0.5]])
    edge_index, _, offsets = radius_graph_pbc(
        pos, np.eye(3), radius=0.2, max_neighbors=10)
    # 1 -> 0 through the image one cell to the left, 0 -> 1 to the right
    assert edge_index.tolist() == [[1, 0], [0, 1]]
    assert offsets.tolist() == [[-1.0, 0.0, 0.0], [1.0, 0.0, 0.0]]
