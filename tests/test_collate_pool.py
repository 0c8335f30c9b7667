"""The input pipeline's host buffers (``graph/slots.py``): the arrays of a
collated batch, the ``E``-row temporaries and a group's stacked arrays come
from a pool the loader owns when the consumer holds the release end (the
trainer's transfer stage over ``GraphLoader.pooled``), and are fresh for
everyone else. Bitwise results, memory identity, counters and threads only;
nothing here asserts on timing."""

import gc
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hydragnn_tpu.data.loaders import (
    GraphLoader,
    collate_for_layout,
    compute_layout,
)
from hydragnn_tpu.graph.slots import SlotPool
from hydragnn_tpu.train import trainer as trainer_mod
from hydragnn_tpu.train.trainer import Trainer
from hydragnn_tpu.utils import tracer as tr

from test_prefetch_loader import _dataset
from test_staged_pipeline import _no_stage_threads, _trainer

# what a layout may ask of the collate: nothing; dense neighbour lists and
# reverse lists with ``nbr_reach``; those plus DimeNet's slot tables; the
# T-axis triplet tables of ``pack_triplets``
KINDS = {
    "plain": dict(need_triplets=False, need_neighbors=False),
    "neighbors": dict(need_triplets=False, need_neighbors=True),
    "slot_tables": dict(need_triplets=True, need_neighbors=True),
    "triplets": dict(need_triplets=True, need_neighbors=False),
}
EXTRAS = {
    "plain": set(),
    "neighbors": {"nbr_idx", "nbr_edge", "nbr_mask", "rev_idx", "rev_mask",
                  "nbr_reach"},
    "slot_tables": {"nbr_idx", "nbr_edge", "nbr_mask", "rev_idx", "rev_mask",
                    "nbr_reach", "out_edge", "edge_slot", "out_slot"},
    "triplets": {"trip_i", "trip_j", "trip_k", "trip_kj", "trip_ji",
                 "trip_mask"},
}


class _Unpooled:
    """The loader as a consumer without the release end sees it: the same
    batches, plan and keys, and no ``pooled``."""

    def __init__(self, loader):
        self.loader = loader

    def __iter__(self):
        return iter(self.loader)

    def __len__(self):
        return len(self.loader)

    def batch_keys(self):
        return self.loader.batch_keys()

    def set_epoch(self, epoch):
        self.loader.set_epoch(epoch)


def _loader(kind="plain", n=41, buckets=3, **kw):
    ds = _dataset(n)
    layout = compute_layout([ds], batch_size=4, num_buckets=buckets,
                            **KINDS[kind])
    kw.setdefault("contiguous_buckets", buckets > 1)
    return ds, layout, GraphLoader(ds, 4, layout, shuffle=True, **kw)


def _leaves(tree):
    return [np.array(a) for a in jax.tree_util.tree_leaves(tree)]


def _assert_trees_equal(a, b):
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    for x, y in zip(_leaves(a), _leaves(b)):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(x, y)


def _device_payloads(trainer, loader, K, epochs=2):
    """What the transfer stage puts on the device, epoch by epoch, read
    back: ``[(count, payload), ...]``."""
    out = []
    for epoch in range(epochs):
        loader.set_epoch(epoch)
        plan = Trainer._group_plan(loader, len(loader), K)
        for dev, count in trainer._prefetch_put(
            plan, float("inf"), 2, put=trainer._put_group, ledger_waits=False
        ):
            out.append((count, jax.device_get(dev)))
    return out


# ---- (a) bitwise with the pool and without --------------------------------


@pytest.mark.parametrize("K", [1, 4])
@pytest.mark.parametrize("kind", list(KINDS))
def pytest_every_leaf_put_is_bitwise_the_unpooled_one(kind, K):
    _, _, loader = _loader(kind)
    _, _, plain = _loader(kind)
    trainer = _trainer(K, 2)
    pooled = _device_payloads(trainer, loader, K)
    fresh = _device_payloads(trainer, _Unpooled(plain), K)
    assert loader.pool_counts()["reused"] > 0
    assert plain.pool_counts()["made"] == 0
    assert [c for c, _ in pooled] == [c for c, _ in fresh]
    assert any(c == K for c, _ in pooled)
    for (_, a), (_, b) in zip(pooled, fresh):
        assert set(a.extras or {}) == EXTRAS[kind]
        _assert_trees_equal(a, b)


def _train(K, pooled, epochs=2):
    _, _, loader = _loader()
    trainer = _trainer(K, 2)
    state = trainer.init_state(next(iter(loader)))
    rng = jax.random.PRNGKey(3)
    losses = []
    source = loader if pooled else _Unpooled(loader)
    for epoch in range(epochs):
        loader.set_epoch(epoch)
        state, rng, loss, tasks = trainer.train_epoch(state, source, rng)
        losses.append((loss, tuple(tasks)))
    evaluated = trainer.evaluate(state, source)
    return loader, jax.device_get(state), losses, evaluated


@pytest.mark.parametrize("K", [1, 4])
def pytest_two_epochs_of_training_end_bitwise_equal(K):
    loader, state, losses, evaluated = _train(K, pooled=True)
    loader0, state0, losses0, evaluated0 = _train(K, pooled=False)
    assert loader.pool_counts()["reused"] > 0
    assert loader0.pool_counts() == {"reused": 0, "made": 0, "bytes": 0}
    assert losses == losses0
    assert evaluated[0] == evaluated0[0]
    np.testing.assert_array_equal(evaluated[1], evaluated0[1])
    _assert_trees_equal(state, state0)


def pytest_pool_of_collate_workers_takes_slots_under_the_lock(monkeypatch):
    monkeypatch.setenv("HYDRAGNN_NUM_WORKERS", "3")
    _, _, loader = _loader("neighbors")
    _, _, plain = _loader("neighbors")
    trainer = _trainer(4, 2)
    pooled = _device_payloads(trainer, loader, 4, epochs=3)
    fresh = _device_payloads(trainer, _Unpooled(plain), 4, epochs=3)
    for (_, a), (_, b) in zip(pooled, fresh):
        _assert_trees_equal(a, b)
    counts = loader.pool_counts()
    assert counts["reused"] > counts["made"] > 0


# ---- (b) a reused slot holds no residue -------------------------------------


@pytest.mark.parametrize("kind", list(KINDS))
def pytest_a_reused_slot_holds_no_residue(kind):
    ds, layout, _ = _loader(kind, buckets=1)
    order = sorted(range(len(ds)), key=lambda i: ds[i].num_nodes)
    large = [ds[i] for i in order[-4:]]
    small = [ds[i] for i in order[:2]]
    pool = SlotPool()
    slot = pool.acquire("k")
    big = collate_for_layout(large, layout, slot=slot)
    _assert_trees_equal(big, collate_for_layout(large, layout))
    held = {id(a) for a in jax.tree_util.tree_leaves(big)}
    slot.release()
    again = pool.acquire("k")
    assert again is slot and again.state == "reused"
    got = collate_for_layout(small, layout, slot=again)
    _assert_trees_equal(got, collate_for_layout(small, layout))
    # the same memory, rewritten
    assert {id(a) for a in jax.tree_util.tree_leaves(got)} & held
    assert pool.counts()["reused"] == pool.counts()["made"] == 1


# ---- (c) a held slot is not handed out again --------------------------------


def pytest_a_held_slot_is_not_handed_out_again():
    _, _, loader = _loader("neighbors", buckets=1)
    batches = loader.pooled()
    kept = []
    later = []
    for i, (batch, slot) in enumerate(batches):
        if i < 2:
            kept.append((batch, _leaves(batch)))  # a put stage that keeps it
        else:
            later.append(batch)
            slot.release()
    assert len(later) > 4
    for batch, copy in kept:
        for other in [b for b, _ in kept if b is not batch] + later:
            for a in jax.tree_util.tree_leaves(batch):
                for b in jax.tree_util.tree_leaves(other):
                    assert not np.shares_memory(a, b)
        for a, b in zip(_leaves(batch), copy):
            np.testing.assert_array_equal(a, b)
    # the released ones went round: three slots serve what came after
    assert loader.pool_counts()["made"] <= 2 + 4


# ---- (d) a consumer that releases nothing keeps what it got -----------------


@pytest.mark.parametrize("how", ["list", "collate_for_layout", "pooled_kept"])
def pytest_kept_batches_are_never_rewritten(how):
    ds, layout, loader = _loader("neighbors")
    loader.set_epoch(0)
    if how == "list":
        kept = list(loader)
    elif how == "collate_for_layout":
        kept = [collate_for_layout(ds[:3], layout.layouts[-1]),
                collate_for_layout(ds[3:5], layout.layouts[-1],
                                   with_targets=False)]
    else:
        kept = [batch for batch, _ in loader.pooled()]
    copies = [_leaves(b) for b in kept]
    trainer = _trainer(4, 2)
    state = trainer.init_state(kept[0]) if how != "collate_for_layout" else (
        trainer.init_state(next(iter(loader))))
    rng = jax.random.PRNGKey(0)
    for epoch in (0, 1):
        loader.set_epoch(epoch)
        state, rng, _, _ = trainer.train_epoch(state, loader, rng)
    assert loader.pool_counts()["reused"] > 0
    for batch, copy in zip(kept, copies):
        for a, b in zip(_leaves(batch), copy):
            np.testing.assert_array_equal(a, b)


# ---- (e) the CPU backend's zero-copy alias ----------------------------------


def _aligned_like(a, align=64):
    raw = np.empty(a.nbytes + align, np.uint8)
    off = (-raw.ctypes.data) % align
    return raw[off:off + a.nbytes].view(a.dtype).reshape(a.shape)


def pytest_a_device_batch_that_aliases_its_slot_keeps_its_memory():
    assert jax.default_backend() == "cpu"
    _, _, loader = _loader("neighbors", buckets=1, prefetch=0)
    trainer = _trainer(1, 0)
    batches = loader.pooled()
    batch, slot = next(batches)
    # every array of the slot at a 64-byte boundary: this backend takes
    # such a buffer as it is
    for name, a in list(slot._arrays.items()):
        slot._arrays[name] = _aligned_like(a)
    slot.release()
    batches.close()
    loader.set_epoch(0)
    devs, wants = [], []
    aliased = 0
    for round_ in range(3):  # the pool goes round: 3 epochs over one key
        plan = Trainer._group_plan(loader, len(loader), 1)
        for group in plan:
            host = trainer._compact_for_transfer(
                group[0], slot=group.slots[0])
            want = _leaves(host)
            dev, _ = trainer._put_group(group)
            if not devs:
                aliased = len(trainer_mod._taken_by_device(host, dev))
            devs.append(dev)
            wants.append(want)
    assert aliased > 0  # the case is real here
    assert loader.pool_counts()["reused"] >= 2 * len(loader)
    for dev, want in zip(devs, wants):
        for a, b in zip(_leaves(dev), want):
            np.testing.assert_array_equal(a, b)


def pytest_an_aliased_array_leaves_its_slot():
    pool = SlotPool()
    slot = pool.acquire("k")
    a = slot.array("a", (256,), np.float32, 1)
    slot._arrays["a"] = a = _aligned_like(a)
    a.fill(1)
    b = slot.array("b", (3,), np.int32, 2)
    host = {"a": a, "b": b[1:]}  # b's view starts off a 64-byte boundary
    dev = jax.tree_util.tree_map(jnp.asarray, host)
    taken = trainer_mod._taken_by_device(host, dev)
    assert len(taken) == 1 and taken[0] is a
    trainer_mod._give_back((slot,), host, dev)
    again = pool.acquire("k")
    assert again is slot
    assert again.array("b", (3,), np.int32) is b
    fresh = again.array("a", (256,), np.float32, 7)
    assert not np.shares_memory(fresh, a)
    np.testing.assert_array_equal(np.asarray(dev["a"]), np.ones(256))


# ---- (f) interrupted epochs -------------------------------------------------


@pytest.mark.parametrize("how", ["cap", "step_raises", "collate_raises"])
def pytest_interrupted_epochs_leave_no_slot_busy(monkeypatch, how):
    ds, layout, loader = _loader("neighbors")
    trainer = _trainer(2, 2)
    state0 = trainer.init_state(next(iter(loader)))
    rng = jax.random.PRNGKey(0)
    assert len(loader) > 4
    poisoned = len(ds) // 2
    sample = ds[poisoned]
    with monkeypatch.context() as m:
        if how == "cap":
            m.setenv("HYDRAGNN_MAX_NUM_BATCH", "3")
        elif how == "step_raises":
            def boom(*a, **k):
                raise RuntimeError("boom on the epoch loop")

            m.setattr(trainer, "_acc_add", boom)
        else:
            ds[poisoned] = None  # some batch's collate raises
        for epoch in range(3):
            loader.set_epoch(epoch)
            try:  # the step donates its state: a copy each time
                trainer.train_epoch(
                    trainer_mod._copy_tree(state0), loader, rng)
            except Exception:  # noqa: BLE001
                assert how != "cap"
            tr.stop("train")  # the span an interrupted epoch leaves open
    ds[poisoned] = sample
    assert _no_stage_threads()
    # nothing is marked busy: whole epochs run to their end on the pool
    state = state0
    for epoch in range(3, 6):
        loader.set_epoch(epoch)
        before = loader.pool_counts()
        state, rng, _, _ = trainer.train_epoch(state, loader, rng)
        assert loader.pool_counts()["reused"] > before["reused"]
    assert _no_stage_threads()
    # ... and what the interrupted epochs had handed out is gone, not
    # leaked: every slot still alive is a free one, and they are no more
    # than can be alive at once
    gc.collect()
    pool = loader._pool
    alive = list(pool._alive)
    free = [slot for slots in pool._free.values() for slot in slots]
    assert sorted(map(id, alive)) == sorted(map(id, free))
    assert len(alive) <= len(set(loader.batch_keys())) * 7


# ---- (g) the counters --------------------------------------------------------


@pytest.fixture
def recorder(monkeypatch):
    monkeypatch.delenv("HYDRAGNN_TRACE_LEVEL", raising=False)
    monkeypatch.setattr(tr, "_state", tr._State())
    tr.initialize()
    return tr


@pytest.mark.parametrize("staged", [False, True])
@pytest.mark.parametrize("K", [1, 2, 4])
def pytest_second_epoch_makes_nothing_and_memory_stays(recorder, K, staged):
    """Inline (no stage thread) the slots alive at once are the same every
    epoch, so the counts are exact. Staged, how many are alive at once is
    the threads' timing: there the sum is exact and the made ones bounded."""
    _, _, loader = _loader("neighbors", prefetch=2 if staged else 0)
    trainer = _trainer(K, 2 if staged else 0)
    state = trainer.init_state(next(iter(loader)))
    rng = jax.random.PRNGKey(0)
    # one shuffle replayed, as the benchmark's window replays its cycle
    loader.set_epoch(0)
    nbatch = len(loader)
    groups = sum(
        len(g) > 1 for g in Trainer._group_plan(
            _Unpooled(loader), nbatch, K))
    by_epoch = []
    for epoch in range(5):
        before = loader.pool_counts()
        tr.reset()
        state, rng, _, _ = trainer.train_epoch(state, loader, rng)
        after = loader.pool_counts()
        by_epoch.append({k: after[k] - before[k] for k in ("reused", "made")}
                        | {"bytes": after["bytes"]})
        records = tr.spans().records
        slots = {
            name: [s.attrs["slot"] for s in records if s.name == name]
            for name in ("collate", "stack_batch", "put_group")
        }
        assert len(slots["collate"]) == len(slots["put_group"]) + (
            len(slots["stack_batch"]) - groups) == nbatch
        assert len(slots["stack_batch"]) == groups * K
        said = sum(slots.values(), [])
        assert "fresh" not in said
        # a span says what its slot was when it was acquired
        assert by_epoch[-1]["made"] == (
            slots["collate"].count("made")
            + slots["stack_batch"][::K].count("made"))
        assert by_epoch[-1]["made"] + by_epoch[-1]["reused"] == (
            nbatch + groups)
    assert by_epoch[0]["made"] > 0
    # never more than can be alive at once: the collate queue's 2, one
    # being collated, one being put or stacked, one whose transfer is in
    # flight; and per key two stacks (one in flight, one being laid down)
    keys = len(set(loader.batch_keys()))
    assert sum(c["made"] for c in by_epoch) <= keys * (5 + (2 if groups else 0))
    assert by_epoch[-1]["bytes"] == max(c["bytes"] for c in by_epoch) or (
        by_epoch[-1]["bytes"] > 0.95 * max(c["bytes"] for c in by_epoch))
    if not staged:
        # one slot a key, and a second where a batch is collated while
        # the transfer of the one before it is still in flight
        stacked = len({
            key for key, run in _runs(loader.batch_keys()) if run >= K > 1})
        assert keys <= by_epoch[0]["made"] <= 2 * (keys + stacked)
        for counts in by_epoch[1:]:
            assert counts["made"] == 0
            assert counts["reused"] == nbatch + groups
            # the same slots, so the same bytes, but for the small arrays
            # that this backend took for its own (64-byte-aligned by
            # chance): those left their slot till its next use
            assert abs(counts["bytes"] - by_epoch[1]["bytes"]) < (
                0.05 * by_epoch[1]["bytes"])


def _runs(keys):
    import itertools

    return [(k, sum(1 for _ in run)) for k, run in itertools.groupby(keys)]


def pytest_a_consumer_without_the_release_end_reads_fresh(recorder):
    _, _, loader = _loader("neighbors")
    tr.reset()
    batches = list(loader)
    collates = [s for s in tr.spans().records if s.name == "collate"]
    assert len(collates) == len(batches) == len(loader)
    assert {s.attrs["slot"] for s in collates} == {"fresh"}
    assert loader.pool_counts() == {"reused": 0, "made": 0, "bytes": 0}
    assert not [t for t in threading.enumerate()
                if t.name.startswith("graphloader")]
