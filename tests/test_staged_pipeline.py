"""The input pipeline as two stages: the loader's collate thread
(``graphloader-prefetch``) in front of the trainer's put thread
(``hydragnn-device-prefetch``), and ``Trainer._group_plan`` handing a batch
that goes alone on the moment it arrives where the loader states its epoch's
shapes (``GraphLoader.batch_keys``). Nothing here asserts on timing: order,
thread names, span attributes and bitwise results only."""

import gc
import threading
import time

import numpy as np
import pytest

import jax

from hydragnn_tpu.data.loaders import GraphLoader, compute_layout
from hydragnn_tpu.graph.batch import stack_batches
from hydragnn_tpu.models import create_model_config
from hydragnn_tpu.train.trainer import Trainer
from hydragnn_tpu.utils import tracer as tr

from test_models_forward import arch_config
from test_prefetch_loader import _dataset

STAGES = ("graphloader-prefetch", "hydragnn-device-prefetch")


def _keys(runs):
    return [r for r, n in enumerate(runs) for _ in range(n)]


def _fake_batches(keys):
    """One dict of arrays a batch: key ``k`` has rows of width ``k + 1``
    (its shape), ``id`` numbers the batch within the epoch."""
    return [{"x": np.zeros((2, k + 1), np.float32), "id": np.full((1,), i)}
            for i, k in enumerate(keys)]


class _Stated:
    """A loader that states its plan, as ``GraphLoader`` does."""

    def __init__(self, batches, keys):
        self.batches, self.keys = batches, keys

    def __len__(self):
        return len(self.batches)

    def batch_keys(self):
        return list(self.keys)

    def __iter__(self):
        return iter(self.batches)


def _dispatches(plan):
    return [
        ("train_multi" if len(g) > 1 else "train_step", g[0]["x"].shape[-1],
         [int(b["id"][0]) for b in g])
        for g in plan
    ]


@pytest.mark.parametrize("K", [1, 4])
@pytest.mark.parametrize(
    "runs", [[4, 3, 2, 1], [2, 3, 1, 4], [3, 3, 3], [1, 1], [9, 4, 5]]
)
def pytest_plan_dispatches_equal_the_look_aheads(runs, K):
    keys = _keys(runs)
    batches = _fake_batches(keys)
    for nbatch in (len(batches), len(batches) - 1):
        ahead = _dispatches(Trainer._group_plan(batches, nbatch, K))
        stated = _dispatches(
            Trainer._group_plan(_Stated(batches, keys), nbatch, K))
        assert stated == ahead
        if nbatch == len(batches):
            _groups_arrive_stacked(batches, keys, K)
        assert [i for _, _, ids in stated for i in ids] == list(range(nbatch))
        assert all(len(ids) in (1, K) for _, _, ids in stated)


def _groups_arrive_stacked(batches, keys, K):
    """A full group of a stated plan carries its ``stack_batches`` already
    (laid down batch by batch); a look-ahead's group is stacked at the put."""
    for group in Trainer._group_plan(_Stated(batches, keys), len(batches), K):
        if len(group) > 1:
            want = stack_batches(list(group))
            assert sorted(group.stacked) == sorted(want)
            for name in want:
                assert group.stacked[name].dtype == want[name].dtype
                np.testing.assert_array_equal(group.stacked[name], want[name])
    for group in Trainer._group_plan(batches, len(batches), K):
        assert getattr(group, "stacked", None) is None


def pytest_equal_layouts_in_two_buckets_are_one_run():
    """Keys name what fixes the shapes, not the bucket: neighbours of two
    buckets with one layout stack as the look-ahead stacks them."""
    ds = _dataset(26)
    layout = compute_layout([ds], batch_size=4, need_triplets=False,
                            num_buckets=3)
    layout.layouts[1] = layout.layouts[2]
    loader = GraphLoader(ds, 4, layout, shuffle=True, contiguous_buckets=True)
    for epoch in range(3):
        loader.set_epoch(epoch)
        keys = loader.batch_keys()
        shapes = [
            tuple(tuple(a.shape) for a in jax.tree_util.tree_leaves(b))
            for b in loader
        ]
        assert len(keys) == len(shapes) == len(loader)
        assert 2 not in keys
        for (ka, sa), (kb, sb) in zip(
            zip(keys, shapes), zip(keys[1:], shapes[1:])
        ):
            assert (ka == kb) == (sa == sb)


class _Withholding(_Stated):
    """Hands out its second batch only after the first has been put."""

    def __init__(self, batches, keys, timeout):
        super().__init__(batches, keys)
        self.first_put = threading.Event()
        self.timeout, self.timed_out = timeout, False

    def __iter__(self):
        yield self.batches[0]
        self.timed_out = not self.first_put.wait(self.timeout)
        yield from self.batches[1:]


def _trainer(steps_per_dispatch, device_prefetch):
    cfg = dict(arch_config("SAGE"), input_dim=2)
    return Trainer(
        create_model_config(cfg),
        {"Optimizer": {"type": "AdamW", "learning_rate": 1e-3},
         "steps_per_dispatch": steps_per_dispatch,
         "device_prefetch": device_prefetch},
    )


@pytest.mark.parametrize("device_prefetch", [0, 2])
def pytest_a_single_is_put_before_the_next_batch_exists(device_prefetch):
    trainer = _trainer(4, device_prefetch)
    batches = _fake_batches([0, 1, 1])

    def drive(loader):
        def put(group):
            loader.first_put.set()
            return group, len(group)

        plan = Trainer._group_plan(loader, len(loader), 4)
        return _dispatches(g for g, _ in trainer._prefetch_put(
            plan, float("inf"), device_prefetch, put=put, ledger_waits=False))

    stated = _Withholding(batches, [0, 1, 1], timeout=30.0)
    want = _dispatches(Trainer._group_plan(batches, 3, 4))
    assert drive(stated) == want
    assert not stated.timed_out
    # a loader that states no plan: held until the next shape shows, and
    # the same dispatches all the same
    unstated = _Withholding(batches, None, timeout=0.3)
    unstated.batch_keys = None
    assert drive(unstated) == want
    assert unstated.timed_out


def _bucketed(ds, **kw):
    layout = compute_layout([ds], batch_size=4, need_triplets=False,
                            num_buckets=3)
    return layout, GraphLoader(ds, 4, layout, shuffle=True,
                               contiguous_buckets=True, **kw)


def _two_epochs(prefetch, device_prefetch):
    ds = _dataset(41)
    kw = {} if prefetch is None else {"prefetch": prefetch}
    layout, loader = _bucketed(ds, **kw)
    trainer = _trainer(2, device_prefetch)
    state = trainer.init_state(next(iter(loader)))
    rng = jax.random.PRNGKey(3)
    losses = []
    for epoch in range(2):
        loader.set_epoch(epoch)
        state, rng, loss, _ = trainer.train_epoch(state, loader, rng)
        losses.append(loss)
    return loader, jax.device_get(state.params), losses, jax.device_get(rng)


def pytest_staged_training_is_bitwise_the_inline_one():
    loader, params, losses, rng = _two_epochs(None, 2)
    assert loader.prefetch == 2  # staged is the default
    _, params0, losses0, rng0 = _two_epochs(0, 0)
    assert losses == losses0
    np.testing.assert_array_equal(rng, rng0)
    leaves, leaves0 = (jax.tree_util.tree_leaves(p) for p in (params, params0))
    assert len(leaves) == len(leaves0)
    for a, b in zip(leaves, leaves0):
        np.testing.assert_array_equal(a, b)


@pytest.fixture
def recorder(monkeypatch):
    monkeypatch.delenv("HYDRAGNN_TRACE_LEVEL", raising=False)
    monkeypatch.setattr(tr, "_state", tr._State())
    tr.initialize()
    return tr


def pytest_stages_sit_on_their_threads_and_puts_note_the_collate(recorder):
    ds = _dataset(41)
    layout, loader = _bucketed(ds)
    trainer = _trainer(2, 2)
    state = trainer.init_state(next(iter(loader)))
    tr.reset()
    trainer.train_epoch(state, loader, jax.random.PRNGKey(0))
    records = tr.spans().records
    collates = [s for s in records if s.name == "collate"]
    puts = [s for s in records if s.name == "put_group"]
    steps = [s for s in records if s.name == "train_step"]
    assert len(collates) == len(loader)
    assert {s.thread for s in collates} == {"graphloader-prefetch"}
    assert {s.thread for s in puts} == {"hydragnn-device-prefetch"}
    assert all(type(s.attrs["collate_open"]) is bool for s in puts)
    # the trainer's transfer stage holds the release end: every batch and
    # every group's stack is written into one of the pool's slots
    stacks = [s for s in records if s.name == "stack_batch"]
    assert stacks
    for s in collates + puts + stacks:
        assert s.attrs["slot"] in ("made", "reused")
    assert "made" in {s.attrs["slot"] for s in collates}
    # the dispatches are the plan's: full pairs stacked, run tails alone
    want = _dispatches(
        Trainer._group_plan(_fake_batches(loader.batch_keys()), len(loader), 2))
    assert [s.attrs["steps"] for s in steps] == [len(d[2]) for d in want]


def pytest_open_elsewhere_sees_other_threads_only(recorder):
    opened, release = threading.Event(), threading.Event()

    def producer():
        with tr.span("collate"):
            opened.set()
            assert release.wait(10)

    t = threading.Thread(target=producer, name="collate-under-test")
    with tr.span("put_group"):
        assert not tr.open_elsewhere("collate")
        assert not tr.open_elsewhere("put_group")  # its own does not count
        t.start()
        assert opened.wait(10)
        assert tr.open_elsewhere("collate")
        release.set()
        t.join(10)
        assert not tr.open_elsewhere("collate")


def _no_stage_threads(deadline_s=10.0):
    gc.collect()
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        alive = [t.name for t in threading.enumerate()
                 if t.name.startswith(STAGES)]
        if not alive:
            return True
        time.sleep(0.05)
    return False


@pytest.mark.parametrize("how", ["cap", "step_raises", "collate_raises"])
def pytest_an_interrupted_epoch_leaves_no_stage_thread(monkeypatch, how):
    ds = _dataset(41)
    layout, loader = _bucketed(ds)
    trainer = _trainer(2, 2)
    state = trainer.init_state(next(iter(loader)))
    rng = jax.random.PRNGKey(0)
    assert len(loader) > 4
    if how == "cap":
        monkeypatch.setenv("HYDRAGNN_MAX_NUM_BATCH", "3")
        trainer.train_epoch(state, loader, rng)
    else:
        if how == "step_raises":
            def boom(*a, **k):
                raise RuntimeError("boom on the epoch loop")

            monkeypatch.setattr(trainer, "_acc_add", boom)
        else:
            ds[len(ds) // 2] = None  # some batch's collate raises
        raised = None
        try:
            trainer.train_epoch(state, loader, rng)
        except Exception as e:  # noqa: BLE001
            raised = type(e)
        tr.stop("train")  # the span the interrupted epoch left open
        assert raised is not None
        if how == "step_raises":
            assert raised is RuntimeError
    assert _no_stage_threads()
