"""Dense neighbour lists from per-sample slots (``data/loaders.py``): the
loader's batches carry the lists ``build_neighbor_lists`` gives on the
batch's own edge list, the slots are computed once per sample at layout
time and found again in every batch of every epoch, and a sample that
arrives without them (a serving request, a stripped sample) builds its own."""

import numpy as np
import pytest

from hydragnn_tpu.data.loaders import (
    BucketedLayout,
    GraphLoader,
    _sample_degrees,
    _sample_neighbor_slots,
    compute_layout,
)
from hydragnn_tpu.ops.dense_agg import build_neighbor_lists, edge_slots
from hydragnn_tpu.utils import tracer as tr

from test_bucketed_layouts import _oc20_shaped
from test_serve import _graph as _request

_KEYS = ("nbr_idx", "nbr_edge", "nbr_mask", "rev_idx", "rev_mask")


@pytest.fixture
def recorder(monkeypatch):
    monkeypatch.delenv("HYDRAGNN_TRACE_LEVEL", raising=False)
    monkeypatch.setattr(tr, "_state", tr._State())
    tr.initialize()
    return tr


def pytest_sample_degrees_do_not_wrap_at_a_byte():
    """A hub of exactly 256 edges has slots 0..255 (uint8): its width is
    256, not 255 + 1 wrapped to 0; an edgeless sample reads (0, 0)."""
    rng = np.random.default_rng(0)
    hub = _request(300, rng)
    hub.edge_index = np.stack([np.arange(1, 257), np.zeros(256, np.int64)])
    assert _sample_degrees(hub) == (256, 1)
    assert hub.extras["neighbor_slots"].dtype == np.uint8
    hub.edge_index = np.zeros((2, 0), np.int64)
    assert _sample_degrees(hub) == (0, 0)
    layout = compute_layout([[hub]], batch_size=1, need_neighbors=True)
    assert (layout.k_in, layout.k_out) == (1, 1)


def _assert_lists_of_own_edges(batch, k_in, k_out, with_slot_tables=False):
    want = build_neighbor_lists(
        batch.senders, batch.receivers, batch.edge_mask,
        batch.x.shape[0], k_in, k_out, with_slot_tables=with_slot_tables,
    )
    assert set(want) <= set(batch.extras)
    for key, value in want.items():
        assert batch.extras[key].dtype == value.dtype, key
        np.testing.assert_array_equal(batch.extras[key], value, err_msg=key)


def _list_spans():
    return [s for s in tr.spans().records if s.name == "neighbor_lists"]


def pytest_layout_pass_fills_the_sample_cache():
    samples = _oc20_shaped(40, seed=2)
    assert all("neighbor_slots" not in d.extras for d in samples)
    plain = compute_layout([samples[:30], samples[30:]], batch_size=8)
    assert all("neighbor_slots" not in d.extras for d in samples)
    dense = compute_layout(
        [samples[:30], samples[30:]], batch_size=8, need_neighbors=True
    )
    # every split, and the widths are the degrees the bincounts gave
    for d in samples:
        slots = d.extras["neighbor_slots"]
        assert slots.shape == (2, d.num_edges) and slots.dtype == np.uint8
        np.testing.assert_array_equal(
            slots, edge_slots(d.edge_index[0], d.edge_index[1])
        )
    assert dense.k_in == max(
        np.bincount(d.edge_index[1]).max() for d in samples)
    assert dense.k_out == max(
        np.bincount(d.edge_index[0]).max() for d in samples)
    assert (dense.n_pad, dense.e_pad) == (plain.n_pad, plain.e_pad)
    # the clone of a sample carries the entry; a rewired sample misses
    twin = samples[0].clone()
    assert twin.extras["neighbor_slots"] is samples[0].extras["neighbor_slots"]
    twin.edge_index = twin.edge_index[:, :-2]
    fresh = _sample_neighbor_slots(twin)
    assert fresh.shape == (2, twin.num_edges)
    assert samples[0].extras["neighbor_slots"].shape[1] == twin.num_edges + 2


def pytest_bucketed_loader_lists_over_two_epochs(recorder):
    """Every batch of two differently shuffled epochs equals the whole-batch
    construction, and no batch computes a slot: all were cached."""
    samples = _oc20_shaped(90, seed=9)
    layout = compute_layout(
        [samples], batch_size=8, num_buckets=3, need_neighbors=True
    )
    assert isinstance(layout, BucketedLayout)
    loader = GraphLoader(samples, 8, layout, shuffle=True, num_shards=1,
                         shard_id=0, prefetch=0)
    orders = []
    for epoch in (0, 1):
        loader.set_epoch(epoch)
        tasks = list(loader._batch_tasks())
        orders.append([chunk.tolist() for _, chunk in tasks])
        before = len(_list_spans())
        batches = list(loader)
        spans = _list_spans()[before:]
        assert len(batches) == len(tasks) == len(spans)
        for batch, (lay, chunk), span in zip(batches, tasks, spans):
            _assert_lists_of_own_edges(batch, lay.k_in, lay.k_out)
            assert span.attrs["slots_built"] == 0
            assert span.attrs["slots_cached"] == len(chunk)
            assert (span.attrs["k_in"], span.attrs["k_out"]) == (
                lay.k_in, lay.k_out)
    assert orders[0] != orders[1]

    # stripped of the cache, every sample of the batch builds its own
    # (and keeps it: the next batch with it hits again)
    lay, chunk = next(iter(loader._batch_tasks()))
    for i in chunk:
        del samples[i].extras["neighbor_slots"]
    for built in (len(chunk), 0):
        batch = loader._collate_task((lay, chunk))
        _assert_lists_of_own_edges(batch, lay.k_in, lay.k_out)
        attrs = _list_spans()[-1].attrs
        assert attrs["slots_built"] == built
        assert attrs["slots_cached"] == len(chunk) - built


def pytest_plain_layout_with_slot_tables(recorder):
    """One plain layout, DimeNet's bmm-triplet tables on: the three slot
    tables ride along and equal the whole-batch construction's."""
    samples = _oc20_shaped(20, seed=4)
    layout = compute_layout(
        [samples], batch_size=5, need_triplets=True, need_neighbors=True
    )
    assert not layout.packs_triplets
    loader = GraphLoader(samples, 5, layout, shuffle=True, num_shards=1,
                         shard_id=0, prefetch=0)
    for batch in loader:
        assert {"out_edge", "edge_slot", "out_slot"} <= set(batch.extras)
        _assert_lists_of_own_edges(
            batch, layout.k_in, layout.k_out, with_slot_tables=True
        )
    assert all(s.attrs["slots_built"] == 0 for s in _list_spans())


def pytest_serving_packer_builds_slots_for_fresh_requests(recorder):
    """``ServingBucketPlan.pack`` (``with_targets=False``): the plan's
    samples are cached by ``plan_from_samples``; a fresh request is a miss,
    computed at collate, and its batch carries the same lists."""
    from hydragnn_tpu.serve.buckets import plan_from_samples

    rng = np.random.default_rng(5)
    samples = [_request(int(n), rng) for n in rng.integers(4, 40, 30)]
    plan = plan_from_samples(
        samples, max_batch_graphs=4, num_buckets=2, need_neighbors=True
    )
    assert all("neighbor_slots" in s.extras for s in samples)
    for cap, lay in zip(plan.capacities, plan.layouts):
        assert lay.need_neighbors
        requests = [
            _request(int(n), rng, with_targets=False)
            for n in rng.integers(4, cap.max_nodes + 1, 3)
        ]
        bucket = max(plan.select(r) for r in requests)
        lay = plan.layouts[bucket]
        batch, coords = plan.pack(requests, bucket)
        assert len(coords) == 3 and batch.targets == ()
        _assert_lists_of_own_edges(batch, lay.k_in, lay.k_out)
        attrs = _list_spans()[-1].attrs
        assert (attrs["slots_built"], attrs["slots_cached"]) == (3, 0)
