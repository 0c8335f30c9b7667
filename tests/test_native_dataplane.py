"""Native (C++) components: GraphPack shard store round-trip, DistStore
remote fetch over TCP, region-timer call-tree (reference analogs: ADIOS2
AdiosWriter/AdiosDataset, pyddstore DistDataset, gptl4py tracer —
SURVEY.md §2.4)."""

import json
import os
import tempfile
import time

import numpy as np
import pytest

from hydragnn_tpu.data.dataobj import GraphData


def _mk(rng, n):
    d = GraphData()
    d.x = rng.random((n, 2)).astype(np.float32)
    d.pos = rng.random((n, 3)).astype(np.float32)
    e = 2 * n
    d.edge_index = rng.integers(0, n, (2, e)).astype(np.int64)
    d.edge_attr = rng.random((e, 1)).astype(np.float32)
    d.y = rng.random(4).astype(np.float32)
    d.supercell_size = np.eye(3, dtype=np.float32)
    d.targets = [
        rng.random(2).astype(np.float32),
        rng.random((n, 1)).astype(np.float32),
    ]
    d.target_types = ["graph", "node"]
    return d


def _assert_same(a, b):
    assert np.allclose(a.x, b.x)
    assert np.allclose(a.pos, b.pos)
    assert np.array_equal(a.edge_index, b.edge_index)
    assert np.allclose(a.edge_attr, b.edge_attr)
    assert np.allclose(a.y, b.y)
    assert b.target_types == ["graph", "node"]
    assert np.allclose(a.targets[0], b.targets[0])
    assert np.allclose(a.targets[1], b.targets[1])


def pytest_graphpack_roundtrip():
    from hydragnn_tpu.data.shard_store import ShardDataset, ShardWriter

    rng = np.random.default_rng(0)
    samples = [_mk(rng, int(rng.integers(3, 9))) for _ in range(40)]
    with tempfile.TemporaryDirectory() as tmp:
        label = os.path.join(tmp, "trainset")
        w0 = ShardWriter(label, rank=0)
        w0.add(samples[:25])
        w0.add_global("pna_deg", np.array([1, 2, 3]))
        w0.save()
        w1 = ShardWriter(label, rank=1)
        w1.add(samples[25:])
        w1.save()

        for preload in (False, True):
            ds = ShardDataset(label, preload=preload)
            assert len(ds) == 40
            assert ds.meta["pna_deg"] == [1, 2, 3]
            for i in (0, 13, 24, 25, 39):
                _assert_same(samples[i], ds.get(i))
            assert np.allclose(
                ds.get(7).supercell_size, samples[7].supercell_size
            )
            ds.close()


def pytest_graphpack_bulk_view():
    from hydragnn_tpu.data.shard_store import ShardDataset, ShardWriter

    rng = np.random.default_rng(1)
    samples = [_mk(rng, 5) for _ in range(8)]
    with tempfile.TemporaryDirectory() as tmp:
        label = os.path.join(tmp, "set")
        w = ShardWriter(label, rank=0)
        w.add(samples)
        w.save()
        ds = ShardDataset(label)
        xs = ds.readers[0].read_all("x")
        assert xs.shape == (40, 2)
        assert not xs.flags.writeable  # zero-copy mmap view
        assert np.allclose(xs[:5], samples[0].x)
        counts = ds.readers[0].counts("x")
        assert counts.tolist() == [5] * 8
        ds.close()


def pytest_graphpack_empty_shard():
    """A rank with zero local samples still writes a valid (empty) shard."""
    from hydragnn_tpu.data.shard_store import ShardDataset, ShardWriter

    rng = np.random.default_rng(3)
    with tempfile.TemporaryDirectory() as tmp:
        label = os.path.join(tmp, "s")
        w1 = ShardWriter(label, rank=1)
        w1.add([])
        w1.save()
        w0 = ShardWriter(label, rank=0)
        w0.add([_mk(rng, 4)])
        w0.save()
        ds = ShardDataset(label)
        assert len(ds) == 1
        assert ds.get(0).num_nodes == 4
        ds.close()


def pytest_graphpack_subset_view():
    """Subset views expose only the chosen global indices through len/[i]
    (AdiosDataset subset parity, ``utils/adiosdataset.py:610-636``)."""
    from hydragnn_tpu.data.shard_store import ShardDataset, ShardWriter

    rng = np.random.default_rng(7)
    with tempfile.TemporaryDirectory() as tmp:
        label = os.path.join(tmp, "s")
        w = ShardWriter(label, rank=0)
        samples = [_mk(rng, 3 + i) for i in range(6)]
        w.add(samples)
        w.save()
        ds = ShardDataset(label, subset=[4, 1, 5])
        assert len(ds) == 3
        assert ds.num_samples_total() == 6
        assert ds[0].num_nodes == samples[4].x.shape[0]
        assert ds[1].num_nodes == samples[1].x.shape[0]
        # get() still addresses the GLOBAL index space
        assert ds.get(0).num_nodes == samples[0].x.shape[0]
        # iteration follows the subset view
        assert [d.num_nodes for d in ds] == [
            samples[i].x.shape[0] for i in (4, 1, 5)
        ]
        ds.close()


def pytest_diststore_remote_fetch():
    from hydragnn_tpu.data.distdataset import DistDataset

    rng = np.random.default_rng(2)
    all_samples = [_mk(rng, int(rng.integers(3, 9))) for _ in range(30)]
    # single-process twin-store test: the host-side allgather of per-rank
    # maxima can't run (one jax process), so pass the global maxima directly
    mc = {"nodes": 8, "edges": 16}
    ds0 = DistDataset(
        all_samples[:20], rank=0, world=2, samples_per_rank=[20, 10],
        base_port=23810, max_counts=mc,
    )
    ds1 = DistDataset(
        all_samples[20:], rank=1, world=2, samples_per_rank=[20, 10],
        base_port=23810, max_counts=mc,
    )
    try:
        assert len(ds0) == 30 and len(ds1) == 30
        ds0.epoch_begin()
        ds1.epoch_begin()
        for idx in (0, 19, 20, 29):  # local + remote both directions
            _assert_same(all_samples[idx], ds0.get(idx))
        _assert_same(all_samples[5], ds1.get(5))
        ds0.epoch_end()
        ds1.epoch_end()
        # window reopens
        ds0.epoch_begin()
        ds1.epoch_begin()
        _assert_same(all_samples[25], ds0.get(25))
        ds0.epoch_end()
        ds1.epoch_end()
    finally:
        ds0.close()
        ds1.close()


def pytest_diststore_subgroup_replication():
    """ddstore_width analog: with subgroup_width the world splits into
    blocks that each hold a FULL replica, and every get() resolves inside
    the caller's block. Out-of-block ranks get dead addresses here, so any
    cross-subgroup fetch would error — the sweep passing proves locality."""
    from hydragnn_tpu.data.distdataset import (
        DistDataset,
        subgroup_local_indices,
        subgroup_of,
    )

    # split arithmetic incl. the smaller trailing group
    assert subgroup_of(0, 4, 2) == (0, 0, 2, 0)
    assert subgroup_of(3, 4, 2) == (1, 1, 2, 2)
    assert subgroup_of(3, 4, 3) == (1, 0, 1, 3)  # trailing group of one
    assert subgroup_of(2, 4, None) == (0, 2, 4, 0)
    assert list(subgroup_local_indices(5, 3, 4, 3)) == [0, 1, 2, 3, 4]
    cover = [list(subgroup_local_indices(7, r, 4, 2)) for r in range(4)]
    assert cover[0] + cover[1] == list(range(7))  # group 0 = full replica
    assert cover[2] + cover[3] == list(range(7))  # group 1 = full replica

    rng = np.random.default_rng(7)
    all_samples = [_mk(rng, int(rng.integers(3, 9))) for _ in range(30)]
    mc = {"nodes": 8, "edges": 16}
    dead = "127.0.0.1:9"  # nothing listens there — contact would fail

    def shard(rank):
        return [all_samples[i] for i in subgroup_local_indices(30, rank, 4, 2)]

    def spr(rank):
        return [
            len(subgroup_local_indices(30, r, 4, 2))
            for r in range(*{0: (0, 2), 1: (2, 4)}[rank // 2])
        ]

    # group 0 (ranks 0,1) with ranks 2,3 unreachable
    addrs0 = ["127.0.0.1:23870", "127.0.0.1:23871", dead, dead]
    ds0 = DistDataset(shard(0), rank=0, world=4, addresses=addrs0,
                      samples_per_rank=spr(0), max_counts=mc,
                      subgroup_width=2)
    ds1 = DistDataset(shard(1), rank=1, world=4, addresses=addrs0,
                      samples_per_rank=spr(1), max_counts=mc,
                      subgroup_width=2)
    # group 1 (ranks 2,3) with ranks 0,1 unreachable — independent replica
    addrs1 = [dead, dead, "127.0.0.1:23872", "127.0.0.1:23873"]
    ds2 = DistDataset(shard(2), rank=2, world=4, addresses=addrs1,
                      samples_per_rank=spr(2), max_counts=mc,
                      subgroup_width=2)
    ds3 = DistDataset(shard(3), rank=3, world=4, addresses=addrs1,
                      samples_per_rank=spr(3), max_counts=mc,
                      subgroup_width=2)
    try:
        assert ds0.store.group_index == 0 and ds3.store.group_index == 1
        assert ds0.store.world == 2  # the subgroup IS the store's world
        for ds in (ds0, ds1, ds2, ds3):
            assert len(ds) == 30  # global index space in every block
            ds.epoch_begin()
        for idx in range(30):  # full sweep: local + intra-block remote
            _assert_same(all_samples[idx], ds0.get(idx))
            _assert_same(all_samples[idx], ds3.get(idx))
        _assert_same(all_samples[0], ds1.get(0))
        _assert_same(all_samples[29], ds2.get(29))
        for ds in (ds0, ds1, ds2, ds3):
            ds.epoch_end()
    finally:
        for ds in (ds0, ds1, ds2, ds3):
            ds.close()


def _subgroup_worker(rank, base_port, results, barrier):
    """One REAL process of a 4-rank world with subgroup_width=2: builds its
    subgroup shard, serves it, sweeps the full global index space, and
    reports per-index node counts for cross-process verification. Ranks
    outside the block get dead addresses, so any cross-subgroup fetch
    would error instead of silently succeeding."""
    try:
        import numpy as _np

        from hydragnn_tpu.data.distdataset import (
            DistDataset,
            subgroup_local_indices,
        )

        rng = _np.random.default_rng(11)
        all_samples = [_mk(rng, int(rng.integers(3, 9))) for _ in range(20)]
        dead = "127.0.0.1:9"
        group = rank // 2
        addrs = [
            f"127.0.0.1:{base_port + r}" if r // 2 == group else dead
            for r in range(4)
        ]
        mine = subgroup_local_indices(20, rank, 4, 2)
        ds = DistDataset(
            [all_samples[i] for i in mine],
            rank=rank,
            world=4,
            addresses=addrs,
            samples_per_rank=[
                len(subgroup_local_indices(20, group * 2 + p, 4, 2))
                for p in range(2)
            ],
            max_counts={"nodes": 8, "edges": 16},
            subgroup_width=2,
        )
        try:
            ds.epoch_begin()
            counts = [ds.get(i).num_nodes for i in range(20)]
            # every fetch resolved inside the subgroup; verify content
            expected = [s.num_nodes for s in all_samples]
            assert counts == expected, (rank, counts, expected)
            # barrier: no rank tears its server down while a subgroup
            # peer may still be mid-sweep (a sleep would be skew-flaky)
            barrier.wait(timeout=90)
            ds.epoch_end()
        finally:
            ds.close()
        # "ok" only after teardown so epoch_end/close failures surface
        results.put((rank, "ok"))
    except Exception as e:  # surface on the parent
        results.put((rank, f"{type(e).__name__}: {e}"))


@pytest.mark.skipif(
    int(os.getenv("HYDRAGNN_FAST_TEST", "0")) == 1,
    reason="spawns 4 real processes: default tier",
)
def pytest_diststore_subgroup_multiprocess():
    """4 REAL processes, subgroup_width=2: both blocks independently serve
    a full replica and every get() resolves within the caller's block
    (out-of-block ranks are unreachable by construction)."""
    import multiprocessing as mp

    ctx = mp.get_context("spawn")
    results = ctx.Queue()
    barrier = ctx.Barrier(4)
    base_port = 23960
    procs = [
        ctx.Process(
            target=_subgroup_worker, args=(r, base_port, results, barrier)
        )
        for r in range(4)
    ]
    for p in procs:
        p.start()
    outcomes = {}
    try:
        for _ in range(4):
            rank, status = results.get(timeout=120)
            outcomes[rank] = status
    finally:
        for p in procs:
            p.join(timeout=30)
            if p.is_alive():
                p.terminate()
    assert outcomes == {r: "ok" for r in range(4)}, outcomes


def pytest_region_timer_calltree():
    """The GPTL analog, once the C++ region timer, now the span recorder
    (``utils/tracer.py``): nested regions accumulate per call-tree path,
    the per-rank table and the chrome trace come from ``save()``."""
    from hydragnn_tpu.utils import tracer as tr

    tr.initialize(("native",))
    tr.reset()
    for _ in range(2):
        tr.start("train")
        tr.start("forward")
        time.sleep(0.002)
        tr.stop("forward")
        tr.stop("train")
    paths = [s.path for s in tr.spans().records]
    assert paths.count("train") == 2
    assert paths.count("train/forward") == 2
    totals = tr.totals()
    assert totals["train"] >= totals["train/forward"] > 0
    with tempfile.TemporaryDirectory() as tmp:
        tr.save(os.path.join(tmp, "trace"))
        text = open(os.path.join(tmp, "trace.0")).read()
        assert "forward" in text and "train" in text
        events = json.load(open(os.path.join(tmp, "trace.0.trace.json")))
        spans = [e for e in events if e["ph"] == "X"]
        assert len(spans) == 4
        assert {e["ph"] for e in events} == {"X", "M"}
    tr.reset()


def pytest_tracer_facade_native_backend():
    """Every former backend name gives the one recorder."""
    from hydragnn_tpu.utils import tracer as tr

    for backends in (("native",), ("timer",), ("jax",), ()):
        assert tr.initialize(backends) == tr.initialize()
    tr.reset()
    tr.start("epoch")
    tr.stop("epoch")
    assert list(tr.totals()) == ["epoch"]
    with tempfile.TemporaryDirectory() as tmp:
        tr.save(os.path.join(tmp, "t"))
        assert os.path.exists(os.path.join(tmp, "t.0"))
        assert os.path.exists(os.path.join(tmp, "t.0.trace.json"))
    tr.reset()
