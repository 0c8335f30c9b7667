"""Bucketed batch layouts (round-3 verdict item 3).

Acceptance criteria from the verdict: padding efficiency (real rows /
padded rows) >= 80% on an OC20-shaped synthetic size distribution
(log-normal, 20-250 atoms) with <= 4 layouts per split, single-layout
behavior unchanged, and the e2e accuracy ceilings still hit through the
public API (the reference's dynamic-batching parity,
``/root/reference/hydragnn/preprocess/load_data.py:226-297``).
"""

import numpy as np
import pytest

from hydragnn_tpu.data.dataobj import GraphData
from hydragnn_tpu.data.loaders import (
    BatchLayout,
    BucketedLayout,
    GraphLoader,
    compute_layout,
    create_dataloaders,
    padding_efficiency,
)


def _graph(n, rng, degree=8):
    d = GraphData(
        x=rng.random((n, 1)).astype(np.float32),
        pos=rng.random((n, 3)).astype(np.float32),
    )
    src = np.repeat(np.arange(n), degree // 2)
    dst = (src + rng.integers(1, n, src.shape[0])) % n
    d.edge_index = np.stack(
        [np.concatenate([src, dst]), np.concatenate([dst, src])]
    ).astype(np.int64)
    d.targets = [np.asarray([d.x.sum()], np.float32), d.x.copy()]
    d.target_types = ["graph", "node"]
    return d


def _oc20_shaped(num, seed=0):
    """Log-normal node counts clipped to [20, 250] — the OC20 size spread
    the verdict names."""
    rng = np.random.default_rng(seed)
    sizes = np.clip(
        np.round(np.exp(rng.normal(np.log(60.0), 0.55, num))), 20, 250
    ).astype(int)
    return [_graph(int(n), rng) for n in sizes]


def pytest_bucketed_efficiency_oc20_distribution():
    samples = _oc20_shaped(600)
    single = compute_layout([samples], batch_size=32)
    bucketed = compute_layout([samples], batch_size=32, num_buckets=4)
    assert isinstance(single, BatchLayout)
    assert isinstance(bucketed, BucketedLayout)
    assert len(bucketed.layouts) <= 4  # <= 4 compiles per split
    eff_single = padding_efficiency([samples], single, 32)
    eff_bucket = padding_efficiency([samples], bucketed, 32)
    # verdict acceptance: >= 80% with buckets; the single layout sized at
    # the dataset max is far below that on this distribution
    assert eff_bucket >= 0.80, f"bucketed efficiency {eff_bucket:.3f}"
    assert eff_bucket > eff_single + 0.2, (eff_bucket, eff_single)


def pytest_bucket_bounds_cover_all_sizes():
    samples = _oc20_shaped(300, seed=1)
    layout = compute_layout([samples], batch_size=16, num_buckets=3)
    for d in samples:
        b = layout.bucket_for(d.num_nodes)
        lay = layout.layouts[b]
        # budget packing: any single graph must fit its bucket's budgets
        assert d.num_nodes + 1 <= lay.n_pad
        assert d.num_edges <= lay.e_pad
        assert lay.g_pad >= 2


def pytest_every_packed_batch_fits_its_layout():
    samples = _oc20_shaped(200, seed=7)
    layout = compute_layout([samples], batch_size=16, num_buckets=4)
    loader = GraphLoader(samples, 16, layout, shuffle=True, num_shards=1,
                         shard_id=0)
    total = 0
    for b, chunk in loader._batch_plan():
        lay = layout.layouts[b]
        n = sum(samples[i].num_nodes for i in chunk)
        e = sum(samples[i].num_edges for i in chunk)
        assert n + 1 <= lay.n_pad and e <= lay.e_pad
        assert len(chunk) + 1 <= lay.g_pad
        total += len(chunk)
    assert total == len(samples)


def pytest_bucket_graph_cap_matches_reference_step_semantics():
    """Default packing caps every batch at batch_size GRAPHS (a reference
    step is batch_size graphs; budget-only packing trains a different
    trajectory — QM9-at-scale, round 4). 'budget' mode keeps
    the pure-throughput fill available."""
    samples = _oc20_shaped(300, seed=3)
    layout = compute_layout([samples], batch_size=8, num_buckets=3)
    capped = GraphLoader(samples, 8, layout, shuffle=False, num_shards=1,
                         shard_id=0)
    assert max(len(c) for _, c in capped._batch_plan()) <= 8
    budget = GraphLoader(samples, 8, layout, shuffle=False, num_shards=1,
                         shard_id=0, bucket_graph_cap="budget")
    # the small-size bucket must actually exercise the budget headroom
    assert max(len(c) for _, c in budget._batch_plan()) > 8
    # both modes cover every sample exactly once
    for ld in (capped, budget):
        seen = sorted(i for _, c in ld._batch_plan() for i in c)
        assert seen == list(range(len(samples)))


def pytest_bucketed_loader_covers_every_sample_once():
    samples = _oc20_shaped(130, seed=2)
    for d, i in zip(samples, range(len(samples))):
        d.extras["uid"] = i
    layout = compute_layout([samples], batch_size=8, num_buckets=3)
    loader = GraphLoader(samples, 8, layout, shuffle=True, num_shards=1,
                         shard_id=0)
    loader.set_epoch(3)
    seen = []
    shapes = set()
    for batch in loader:
        shapes.add((batch.x.shape, batch.senders.shape))
        g = np.asarray(batch.graph_mask)
        # graph targets recover which samples were collated via the sum
        assert batch.targets[0].shape[0] == g.shape[0]
        seen.append(int(g.sum()))
    assert sum(seen) == len(samples)  # every sample exactly once
    assert len(shapes) <= 3  # one static shape per bucket
    assert len(loader) == len(list(loader))


def pytest_bucketed_loader_shards_deterministically():
    """DistributedSampler parity under buckets: two shards of the same
    epoch see the same bucket/batch SEQUENCE (shapes) but disjoint-ish
    sample sets covering the dataset with wraparound."""
    samples = _oc20_shaped(97, seed=3)
    layout = compute_layout([samples], batch_size=8, num_buckets=3)
    loaders = [
        GraphLoader(samples, 8, layout, shuffle=True, num_shards=2,
                    shard_id=s)
        for s in range(2)
    ]
    plans = []
    for ld in loaders:
        ld.set_epoch(5)
        plans.append(ld._batch_plan())
    # same length, same bucket sequence on both shards
    assert [b for b, _ in plans[0]] == [b for b, _ in plans[1]]
    counts = [sum(len(c) for _, c in p) for p in plans]
    assert counts[0] == counts[1]
    union = set()
    for p in plans:
        for _, c in p:
            union.update(int(i) for i in c)
    assert union == set(range(len(samples)))  # wraparound covers all


def pytest_single_bucket_request_returns_plain_layout():
    samples = _oc20_shaped(40, seed=4)
    layout = compute_layout([samples], batch_size=8, num_buckets=1)
    assert isinstance(layout, BatchLayout)
    # uniform sizes: bucketing collapses to one layout
    uniform = [_graph(30, np.random.default_rng(0)) for _ in range(20)]
    layout = compute_layout([uniform], batch_size=4, num_buckets=4)
    assert isinstance(layout, BucketedLayout) is False or len(
        layout.layouts
    ) == 1


@pytest.mark.skipif(
    bool(int(__import__("os").getenv("HYDRAGNN_FAST_TEST", "0"))),
    reason="e2e training (default tier)",
)
def pytest_bucketed_training_matches_reference_ceiling():
    """E2E through the public API with batch_buckets=3: the PNA ceiling
    from the reference CI matrix must still hold (bucketing changes batch
    composition, not semantics). The synthetic BCC dataset has graph sizes
    {2, 4, 8}, so three real buckets form."""
    from e2e_train import unittest_train_model

    unittest_train_model(
        "PNA",
        "ci.json",
        False,
        overwrite_config={
            "NeuralNetwork": {"Training": {"batch_buckets": 3}}
        },
    )


def pytest_bucket_for_edge_cases():
    """bucket_for outside the trained size range: a graph LARGER than the
    largest bucket clamps to the last bucket (collation then fails loudly
    if it truly cannot fit — never a silent wrong bucket), a zero-node
    graph lands in the smallest, and exact boundary sizes stay in their
    own (inclusive-upper-bound) bucket."""
    samples = _oc20_shaped(200, seed=11)
    layout = compute_layout([samples], batch_size=8, num_buckets=3)
    assert isinstance(layout, BucketedLayout)
    last = len(layout.layouts) - 1
    assert layout.bucket_for(layout.node_bounds[-1] + 1000) == last
    assert layout.bucket_for(0) == 0
    assert layout.bucket_for(1) == 0
    for b, bound in enumerate(layout.node_bounds):
        assert layout.bucket_for(bound) == b  # inclusive upper bound
        if b + 1 < len(layout.node_bounds):
            assert layout.bucket_for(bound + 1) == b + 1


def pytest_batch_buckets_env_override(monkeypatch):
    """HYDRAGNN_BATCH_BUCKETS wins over whatever the caller passed — the
    ONE precedence site lives in create_dataloaders — and a non-integer
    value fails loudly instead of silently running unbucketed."""
    samples = _oc20_shaped(120, seed=9)
    third = len(samples) // 3
    splits = (samples[:third], samples[third : 2 * third], samples[2 * third :])

    monkeypatch.setenv("HYDRAGNN_BATCH_BUCKETS", "3")
    train_loader, _, _ = create_dataloaders(*splits, batch_size=8)
    assert isinstance(train_loader.layout, BucketedLayout)
    assert len(train_loader.layout.layouts) <= 3

    # env also DOWNGRADES an explicit request back to a single layout
    monkeypatch.setenv("HYDRAGNN_BATCH_BUCKETS", "1")
    train_loader, _, _ = create_dataloaders(*splits, batch_size=8,
                                            num_buckets=4)
    assert isinstance(train_loader.layout, BatchLayout)

    monkeypatch.setenv("HYDRAGNN_BATCH_BUCKETS", "four")
    with pytest.raises(ValueError):
        create_dataloaders(*splits, batch_size=8)


def pytest_bucketed_dense_aggregation_layout():
    """Dense neighbor-list widths are computed per bucket."""
    samples = _oc20_shaped(60, seed=5)
    layout = compute_layout(
        [samples], batch_size=8, num_buckets=3, need_neighbors=True
    )
    if isinstance(layout, BucketedLayout):
        for lay in layout.layouts:
            assert lay.k_in >= 1 and lay.k_out >= 1
            assert lay.need_neighbors
