"""Batch loaders: samples -> statically-shaped padded GraphBatch streams.

Replaces PyG's DataLoader + DistributedSampler (``preprocess/load_data.py:
207-297``) with a numpy collator targeting ONE compiled XLA program: pad
sizes (the "layout") are computed once over all splits, every batch of a
split shares the same shapes, and per-epoch shuffling follows
DistributedSampler semantics (seeded by epoch via ``set_epoch``, sharded
evenly across processes with wrap-around padding).
"""

import bisect
import os
from dataclasses import astuple, dataclass, field, replace
from typing import List, Optional, Tuple, Union

import numpy as np

from hydragnn_tpu.data.dataobj import GraphData
from hydragnn_tpu.graph.batch import _round_up, collate_graphs, pad_sizes_for
from hydragnn_tpu.graph.slots import SlotPool
from hydragnn_tpu.ops.agg_policy import (
    arch_for_auto_policy,
    needs_dense_neighbors,
)
from hydragnn_tpu.utils import tracer as tr
from hydragnn_tpu.utils.envparse import env_int


@dataclass
class BatchLayout:
    n_pad: int
    e_pad: int
    g_pad: int
    head_types: Tuple[str, ...]
    head_dims: Tuple[int, ...]
    need_triplets: bool = False
    t_pad: int = 0
    # dense neighbor-list aggregation (scatter-free message passing):
    # fixed in/out-degree widths, computed over all splits
    need_neighbors: bool = False
    k_in: int = 0
    k_out: int = 0
    # the largest graph a batch of this layout may hold, when its builder
    # knew it (0: no statement). Collation lays a graph's nodes down
    # contiguously, so every neighbour of a row lies within this many rows
    # of it; dense-list batches carry it on as ``extras["nbr_reach"]`` and
    # the neighbour gather reads it (``ops/dense_agg.py gather_neighbors``)
    nbr_reach: int = 0
    # each edge's periodic image as ``extras["edge_offset"]``, for a stack
    # that computes distances from positions (``graph/batch.py
    # collate_graphs``); off for every other stack, which never pays for it
    need_offsets: bool = False

    @property
    def packs_triplets(self) -> bool:
        """Whether collation materializes T-axis triplet tables. Dense
        layouts never do: the bmm-triplet path (models/dimenet.py) derives
        every triplet from the neighbor lists, so host-side
        ``compute_triplets`` is skipped entirely."""
        return self.need_triplets and not self.need_neighbors


@dataclass
class BucketedLayout:
    """2-4 size-bucketed :class:`BatchLayout`\\ s per split (round-3 verdict
    item 3): instead of ONE layout sized at the dataset max — which wastes
    most of each batch's FLOPs and HBM on padding when graph sizes are
    heterogeneous (OC20: ~20-250 atoms) — samples are binned by node count
    and each bucket gets a layout sized at ITS max. Compile count stays
    bounded: one XLA program per bucket (<= 4), vs the reference's PyG
    dynamic batching which recompiles nothing because it is eager
    (``preprocess/load_data.py:226-297``).

    ``node_bounds[b]`` is the inclusive node-count upper bound of bucket
    ``b`` (ascending); a sample with ``num_nodes`` goes to the first bucket
    whose bound covers it."""

    layouts: List[BatchLayout] = field(default_factory=list)
    node_bounds: List[int] = field(default_factory=list)

    def bucket_for(self, num_nodes: int) -> int:
        b = bisect.bisect_left(self.node_bounds, num_nodes)
        return min(b, len(self.layouts) - 1)

    # shared head schema (identical across buckets)
    @property
    def head_types(self):
        return self.layouts[0].head_types

    @property
    def head_dims(self):
        return self.layouts[0].head_dims

    @property
    def need_triplets(self):
        return self.layouts[0].need_triplets

    @property
    def need_neighbors(self):
        return self.layouts[0].need_neighbors

    @property
    def packs_triplets(self):
        return self.layouts[0].packs_triplets


def _sample_triplets(data: GraphData):
    if "triplets" not in data.extras:
        from hydragnn_tpu.models.dimenet import compute_triplets

        data.extras["triplets"] = compute_triplets(data.edge_index, data.num_nodes)
    return data.extras["triplets"]


def _cached_neighbor_slots(data: GraphData):
    """The sample's cached slots, or None on a miss (no entry, or one that
    no longer matches the sample's edge count)."""
    slots = data.extras.get("neighbor_slots")
    if slots is None or slots.shape[1] != data.num_edges:
        return None
    return slots


def _sample_neighbor_slots(data: GraphData):
    """``[2, num_edges]`` dense-list slots of the sample's edges (rank of
    each edge among those with its receiver / its sender, in edge order:
    ``ops/dense_agg.py:edge_slots``), cached in ``data.extras`` like the
    triplets. A sample's edges stay contiguous and in order in every batch
    it joins, so the slots hold in every batch, bucket and epoch."""
    slots = _cached_neighbor_slots(data)
    if slots is None:
        from hydragnn_tpu.ops.dense_agg import edge_slots

        slots = edge_slots(data.edge_index[0], data.edge_index[1])
        data.extras["neighbor_slots"] = slots
    return slots


def _sample_degrees(data: GraphData) -> Tuple[int, int]:
    """(max in-degree, max out-degree) of the sample, (0, 0) without edges:
    the widths its dense lists need. Read off its slots, so the pass that
    sizes a layout also fills the cache collation reads."""
    if not data.num_edges:
        return 0, 0
    k_in, k_out = _sample_neighbor_slots(data).max(axis=1)
    return int(k_in) + 1, int(k_out) + 1


def _sample_triplet_count(data: GraphData) -> int:
    """Real (k->j->i) triplets of the sample, k != i: per central node j
    its in-edges times its out-edges, less the pairs that only turn back
    (k->j with j->k). What the dense lists' ``K_out x K_in`` grids hold of
    real work; cached on the sample like its slots."""
    count = data.extras.get("triplet_count")
    if count is None:
        send, recv = (np.asarray(r, np.int64) for r in data.edge_index)
        n = int(data.num_nodes)
        pairs = np.bincount(recv, minlength=n) * np.bincount(send, minlength=n)
        back = np.isin(send * n + recv, recv * n + send).sum()
        count = data.extras["triplet_count"] = int(pairs.sum() - back)
    return count


def _lcm(a, b):
    import math

    return a * b // math.gcd(a, b)


def _sample_stats(datasets, need_triplets, need_neighbors):
    """One pass over all samples -> per-sample size arrays (nodes, edges,
    triplets, neighbor-list widths) + the head schema from the first.
    Triplet counting is skipped when dense lists are requested — the bmm
    path never packs a T axis, so running ``compute_triplets`` over the
    whole dataset would be pure startup waste."""
    nodes, edges, trips_n, kis, kos = [], [], [], [], []
    first = None
    with tr.span(
        "sample_stats", need_neighbors=bool(need_neighbors),
        need_triplets=bool(need_triplets),
    ) as span:
        built = 0
        for ds in datasets:
            for d in ds:
                first = first or d
                nodes.append(d.num_nodes)
                edges.append(d.num_edges)
                t = ki = ko = 0
                if need_triplets and not need_neighbors:
                    trips = _sample_triplets(d)
                    t = trips[0].shape[0]
                if need_neighbors:
                    # and the slot cache is filled: here on the main thread,
                    # for every split
                    built += bool(
                        d.num_edges and _cached_neighbor_slots(d) is None
                    )
                    ki, ko = _sample_degrees(d)
                trips_n.append(t)
                kis.append(ki)
                kos.append(ko)
        span.set(graphs=len(nodes), slots_built=built)
    head_types = tuple(first.target_types)
    head_dims = tuple(
        t.shape[-1] if t.ndim > 1 else t.shape[0] for t in first.targets
    )
    return (
        np.asarray(nodes),
        np.asarray(edges),
        np.asarray(trips_n),
        np.asarray(kis),
        np.asarray(kos),
        head_types,
        head_dims,
    )


def _partition_node_bounds(nodes: np.ndarray, num_buckets: int) -> List[int]:
    """Bucket boundaries minimizing total padded node rows: exact DP over
    the distinct node counts (cost of a bucket = its sample count x its max
    node count — exactly the rows the padded layout will allocate)."""
    uniq, counts = np.unique(nodes, return_counts=True)
    m = len(uniq)
    k = min(num_buckets, m)
    if k <= 1:
        return [int(uniq[-1])]
    prefix = np.concatenate([[0], np.cumsum(counts)])
    INF = float("inf")
    # dp[b][j]: min cost covering the first j distinct sizes with b buckets
    dp = np.full((k + 1, m + 1), INF)
    cut = np.zeros((k + 1, m + 1), np.int64)
    dp[0][0] = 0.0
    prefix = prefix.astype(np.float64)
    for b in range(1, k + 1):
        for j in range(1, m + 1):
            # vectorized min over the cut point i (O(k*m) numpy ops total,
            # not an O(k*m^2) Python loop — m can be thousands of distinct
            # sizes at parser-scale datasets)
            cand = dp[b - 1][:j] + (prefix[j] - prefix[:j]) * float(uniq[j - 1])
            i = int(np.argmin(cand))
            dp[b][j] = cand[i]
            cut[b][j] = i
    bounds = []
    j = m
    for b in range(k, 0, -1):
        bounds.append(int(uniq[j - 1]))
        j = int(cut[b][j])
    return bounds[::-1]


def _layout_from_maxima(
    max_nodes, max_edges, max_trip, k_in, k_out,
    batch_size, mult, device_multiple, head_types, head_dims,
    need_triplets, need_neighbors,
) -> BatchLayout:
    n_pad, e_pad, g_pad = pad_sizes_for(
        max_nodes,
        max_edges,
        batch_size,
        node_multiple=mult,
        edge_multiple=mult,
        graph_multiple=max(device_multiple, 1),
    )
    t_pad = 0
    if need_triplets and not need_neighbors:
        t_pad = int(-(-(batch_size * max(max_trip, 1)) // mult) * mult)
    return BatchLayout(
        n_pad=n_pad,
        e_pad=e_pad,
        g_pad=g_pad,
        head_types=head_types,
        head_dims=head_dims,
        need_triplets=need_triplets,
        t_pad=t_pad,
        need_neighbors=need_neighbors,
        k_in=max(int(k_in), 1),
        k_out=max(int(k_out), 1),
        nbr_reach=int(max_nodes) if need_neighbors else 0,
    )


def budget_bucket_layout(
    nodes: np.ndarray,
    edges: np.ndarray,
    trips: np.ndarray,
    batch_size: int,
    mult: int,
    device_multiple: int,
    head_types,
    head_dims,
    need_triplets: bool = False,
    need_neighbors: bool = False,
    k_in: int = 1,
    k_out: int = 1,
) -> BatchLayout:
    """One bucket's layout sized at ``batch_size x bucket MEAN`` (not
    max): the loader packs graphs greedily under these budgets, so every
    batch fits by construction and padding waste is the distance from the
    budget to the last graph that did not fit, not max-vs-mean. ``g_pad``
    allows however many of the bucket's smallest graphs fit the node
    budget. Shared by :func:`compute_layout`'s bucketed path and the
    streaming :class:`~hydragnn_tpu.data.stream.planner.BucketPlanner`
    (one sizing rule — the auto-tuned plan cannot drift from the
    materialized path's)."""
    n_budget = int(max(batch_size * float(nodes.mean()), nodes.max()) + 1)
    e_budget = int(max(batch_size * float(edges.mean()), edges.max(), 1))
    n_pad = _round_up(n_budget, mult)
    e_pad = _round_up(e_budget, mult)
    g_cap = max(batch_size, n_pad // max(int(nodes.min()), 1))
    g_pad = _round_up(g_cap + 1, max(device_multiple, 1))
    t_pad = 0
    if need_triplets and not need_neighbors:
        t_budget = int(max(batch_size * float(trips.mean()), trips.max(), 1))
        t_pad = _round_up(t_budget, mult)
    return BatchLayout(
        n_pad=n_pad,
        e_pad=e_pad,
        g_pad=g_pad,
        head_types=head_types,
        head_dims=head_dims,
        need_triplets=need_triplets,
        t_pad=t_pad,
        need_neighbors=need_neighbors,
        k_in=max(int(k_in), 1),
        k_out=max(int(k_out), 1),
        nbr_reach=int(nodes.max()) if need_neighbors else 0,
    )


def compute_layout(
    datasets: List[List[GraphData]],
    batch_size: int,
    need_triplets: bool = False,
    device_multiple: Optional[int] = None,
    need_neighbors: bool = False,
    num_buckets: int = 1,
    need_offsets: bool = False,
) -> Union[BatchLayout, "BucketedLayout"]:
    """``device_multiple``: every padded leading axis is made divisible by
    this (the data-parallel axis size) so sharded batches split evenly.

    ``num_buckets > 1`` returns a :class:`BucketedLayout`: samples are
    binned by node count (boundaries chosen by an exact DP minimizing
    padded node rows) and each bucket is sized at its own maxima — the
    low-waste answer to heterogeneous graph sizes (SURVEY §5's
    padding/bucketing "hard part"). Compiles stay bounded at one program
    per bucket."""
    if device_multiple is None:
        try:
            # the mesh's DATA axis, not the raw device count: on a 2-D
            # ("data", "model") mesh only the data axis shards batch
            # leading dims (and on a best-fit elastic mesh — e.g. (3, 2)
            # on a 7-device world — the device count does not even divide)
            from hydragnn_tpu.parallel.mesh import data_axis_multiple

            device_multiple = data_axis_multiple()
        except Exception:
            device_multiple = 1
    mult = _lcm(8, max(device_multiple, 1))
    nodes, edges, trips_n, kis, kos, head_types, head_dims = (
        _sample_stats(datasets, need_triplets, need_neighbors)
    )

    def build(mask) -> BatchLayout:
        return replace(_layout_from_maxima(
            max(int(nodes[mask].max()), 1),
            max(int(edges[mask].max()), 1),
            int(trips_n[mask].max()) if need_triplets else 0,
            kis[mask].max() if len(kis) else 1,
            kos[mask].max() if len(kos) else 1,
            batch_size, mult, device_multiple, head_types, head_dims,
            need_triplets, need_neighbors,
        ), need_offsets=need_offsets)

    def build_budget(mask) -> BatchLayout:
        return replace(budget_bucket_layout(
            nodes[mask], edges[mask], trips_n[mask],
            batch_size, mult, device_multiple, head_types, head_dims,
            need_triplets, need_neighbors,
            k_in=int(kis[mask].max()) if len(kis) else 1,
            k_out=int(kos[mask].max()) if len(kos) else 1,
        ), need_offsets=need_offsets)

    with tr.span("compute_layout") as span:
        if num_buckets <= 1:
            layout = build(np.ones(len(nodes), bool))
            layouts = [layout]
        else:
            bounds = _partition_node_bounds(nodes, num_buckets)
            layouts = []
            lo = 0
            for hi in bounds:
                mask = (nodes > lo) & (nodes <= hi)
                layouts.append(build_budget(mask))
                lo = hi
            layout = BucketedLayout(layouts=layouts, node_bounds=bounds)
        span.set(
            buckets=len(layouts), n_pad=[lay.n_pad for lay in layouts],
            e_pad=[lay.e_pad for lay in layouts],
        )
    return layout


def _pack_indices(
    idx: np.ndarray,
    nodes: np.ndarray,
    edges: np.ndarray,
    trips: np.ndarray,
    layout: BatchLayout,
    batch_size: Optional[int] = None,
) -> List[np.ndarray]:
    """Greedy budget packing: fill a batch until the next graph would
    overflow the bucket's node/edge/triplet budget or the graph cap.
    Every batch fits its layout by construction.

    ``batch_size`` caps the GRAPH count per batch at the configured value
    (reference DataLoader semantics: a step is batch_size graphs). Without
    it the node budget alone governs and small-graph buckets pack far
    past the nominal batch size — higher device throughput per epoch but
    a DIFFERENT optimization trajectory (fewer, larger steps): measured
    on QM9-at-scale round 4, budget-only packing trained to val ~6-8
    where batch-capped packing matches the reference-semantics ~3.
    Throughput mode stays available via
    ``Training.bucket_graph_cap: "budget"``."""
    cap = layout.g_pad - 1  # the padding-graph slot stays reserved
    if batch_size is not None:
        cap = min(cap, int(batch_size))
    batches, cur = [], []
    n = e = t = 0
    for i in idx:
        ni, ei, ti = int(nodes[i]), int(edges[i]), int(trips[i])
        if cur and (
            n + ni > layout.n_pad - 1
            or e + ei > layout.e_pad
            or (layout.packs_triplets and t + ti > layout.t_pad)
            or len(cur) >= cap
        ):
            batches.append(np.asarray(cur, np.int64))
            cur, n, e, t = [], 0, 0, 0
        cur.append(int(i))
        n += ni
        e += ei
        t += ti
    if cur:
        batches.append(np.asarray(cur, np.int64))
    return batches


def padding_efficiency(datasets, layout, batch_size: int) -> float:
    """Real node rows / padded node rows over one epoch's worth of batches
    — the round-3 verdict's acceptance metric for bucketed layouts.
    Simulates the loader's own packing (shuffle off, one shard) through
    the SAME accounting the telemetry layer reports per epoch
    (:meth:`GraphLoader.epoch_padding_stats`), so the two can't diverge."""
    samples = [d for ds in datasets for d in ds]
    loader = GraphLoader(
        samples, batch_size, layout, shuffle=False, num_shards=1, shard_id=0,
    )
    real, padded = loader.epoch_padding_stats()
    return real / max(padded, 1)


def collate_for_layout(
    samples, layout: BatchLayout, with_targets: bool = True, slot=None
):
    """Collate ``samples`` into the static shapes of ``layout``, including
    any model-specific extras (DimeNet triplet tables, dense neighbor
    lists). The ONE layout-aware collation path — the training loader and
    the serving request packer (``hydragnn_tpu/serve``) both route through
    here. ``with_targets=False`` packs inputs only (inference requests
    carry no labels). Each of its three parts is a span of the recorder
    (``utils/tracer.py``), on whichever thread collates.

    ``slot`` (``graph/slots.py``) holds the arrays to write into, the
    batch's leaves and the large temporaries, for a caller that will give
    it back once the batch has been read (``GraphLoader.pooled``); without
    it every array is fresh and the caller's to keep. The batch is bitwise
    the same either way."""
    with tr.span("collate_graphs"):
        batch = collate_graphs(
            samples,
            layout.n_pad,
            layout.e_pad,
            layout.g_pad,
            head_types=layout.head_types if with_targets else (),
            head_dims=layout.head_dims if with_targets else (),
            slot=slot,
            offsets=layout.need_offsets,
        )
    if layout.packs_triplets:
        from hydragnn_tpu.graph.batch import pack_triplets

        with tr.span("triplets") as span:
            trips = [
                _sample_triplets(s) + (s.num_nodes, s.num_edges)
                for s in samples
            ]
            span.set(
                triplets=sum(t[0].shape[0] for t in trips),
                triplet_slots=layout.t_pad,
            )
            batch = batch.replace(
                extras=pack_triplets(
                    trips, layout.n_pad, layout.t_pad, slot=slot
                )
            )
    if layout.need_neighbors:
        from hydragnn_tpu.ops.dense_agg import assemble_neighbor_lists

        with tr.span(
            "neighbor_lists", k_in=layout.k_in, k_out=layout.k_out
        ) as span:
            # slots are the samples' own (cached at layout time; a serving
            # request or an unretained streamed sample computes its own
            # here): the batch is never sorted
            built = sum(_cached_neighbor_slots(s) is None for s in samples)
            parts = [_sample_neighbor_slots(s) for s in samples]
            real = sum(p.shape[1] for p in parts)  # laid down first
            into = None
            if slot is not None:
                into = slot.array(
                    "slots", (2, layout.e_pad), np.result_type(*parts)
                )[:, :real]
            slots = np.concatenate(parts, axis=1, out=into)
            nbr = assemble_neighbor_lists(
                batch.senders[:real],
                batch.receivers[:real],
                slots,
                layout.n_pad,
                layout.e_pad,
                layout.k_in,
                layout.k_out,
                with_slot_tables=layout.need_triplets,
                slot=slot,
            )
            span.set(slots_cached=len(samples) - built, slots_built=built)
            if layout.need_triplets:
                # what the bmm-triplet grids hold: real triplets against
                # the n_pad x k_out x k_in slots the step computes over
                span.set(
                    triplets=sum(_sample_triplet_count(s) for s in samples),
                    triplet_slots=layout.n_pad * layout.k_out * layout.k_in,
                )
            merged = dict(batch.extras or {})
            merged.update(nbr)
            if layout.nbr_reach:
                # the statement the neighbour gather selects its block-
                # local product by, carried in a SHAPE (as nbr_idx's
                # carries k_in) so that a trace specialises on it
                largest = max(s.num_nodes for s in samples)
                if largest > layout.nbr_reach:
                    raise ValueError(
                        f"a graph of {largest} nodes exceeds the layout's "
                        f"stated nbr_reach={layout.nbr_reach}; recompute "
                        "the layout"
                    )
                merged["nbr_reach"] = np.zeros(layout.nbr_reach, np.int8)
            batch = batch.replace(extras=merged)
    return batch


_collate_with_extras = collate_for_layout


class ConcatDataset:
    """Read-only concatenation of list-like datasets (the multi-dataset
    GFM training pattern, ``examples/multidataset/train.py`` in the
    reference). Works over in-memory lists, ShardDatasets, DistDatasets."""

    def __init__(self, datasets):
        self.datasets = list(datasets)
        self._cum = np.cumsum([len(d) for d in self.datasets])

    def __len__(self):
        return int(self._cum[-1]) if len(self._cum) else 0

    def __getitem__(self, idx):
        n = len(self)
        if idx < 0:
            idx += n
        if not 0 <= idx < n:
            raise IndexError(idx)
        which = int(np.searchsorted(self._cum, idx, side="right"))
        local = idx - (int(self._cum[which - 1]) if which else 0)
        return self.datasets[which][local]

    def __iter__(self):
        for ds in self.datasets:
            yield from ds


class GraphLoader:
    """Iterates padded batches; DistributedSampler-style sharding + epoch
    shuffling (``load_data.py:237-245``, ``train_validate_test.py:151-153``).

    Collation runs ahead of the consumer on a background thread
    (``graphloader-prefetch``, a bounded queue of ``prefetch`` batches, 2
    unless the caller or ``HYDRAGNN_PREFETCH`` says otherwise) so host-side
    batch assembly overlaps the trainer's transfer stage and the device
    step — the role of the reference's thread-pool ``HydraDataLoader``
    (``load_data.py:94-204``). ``prefetch=0`` collates inline, on whichever
    thread iterates.
    """

    def __init__(
        self,
        dataset: List[GraphData],
        batch_size: int,
        layout: Union[BatchLayout, BucketedLayout],
        shuffle: bool = True,
        seed: int = 42,
        num_shards: Optional[int] = None,
        shard_id: Optional[int] = None,
        prefetch: Optional[int] = None,
        contiguous_buckets: Optional[bool] = None,
        bucket_graph_cap: str = "batch",
    ):
        from hydragnn_tpu.parallel.distributed import get_comm_size_and_rank

        world, rank = get_comm_size_and_rank()
        self.dataset = dataset
        self.batch_size = batch_size
        self.layout = layout
        self.shuffle = shuffle
        self.seed = seed
        self.epoch = 0
        self.num_shards = world if num_shards is None else num_shards
        self.shard_id = rank if shard_id is None else shard_id
        if prefetch is None:
            # validated parse: a typo'd HYDRAGNN_PREFETCH must name the
            # variable, not raise a bare int() ValueError mid-construction
            prefetch = env_int("HYDRAGNN_PREFETCH", 2)
        self.prefetch = prefetch
        self._plan_cache = None  # (epoch, plan) — packing is O(dataset)
        # contiguous_buckets: shuffle samples within buckets and the ORDER
        # of bucket segments, but keep same-bucket batches adjacent — runs
        # of identical shapes let steps_per_dispatch stack K batches into
        # one XLA program on dispatch-latency-bound hosts.
        # HYDRAGNN_BUCKET_CONTIGUOUS overrides whatever the caller passed
        # (the ONE parse site for the env var); absent both, off.
        env_contig = os.getenv("HYDRAGNN_BUCKET_CONTIGUOUS")
        if env_contig is not None:
            contiguous_buckets = env_contig.strip().lower() not in (
                "", "0", "false", "no", "off",
            )
        self.contiguous_buckets = bool(contiguous_buckets)
        # "batch" = at most batch_size graphs per packed batch (reference
        # step semantics); "budget" = fill to the node/edge budget (pure
        # throughput; changes the optimization trajectory — see
        # _pack_indices)
        if bucket_graph_cap not in ("batch", "budget"):
            raise ValueError(
                f"bucket_graph_cap must be 'batch' or 'budget', "
                f"got {bucket_graph_cap!r}"
            )
        if bucket_graph_cap == "budget" and not isinstance(
            layout, BucketedLayout
        ):
            # budget packing only exists on the bucketed plan path; a
            # silent no-op would read as "budget mode has no effect"
            raise ValueError(
                "bucket_graph_cap='budget' requires a bucketed layout "
                "(Training.batch_buckets > 1)"
            )
        self.bucket_graph_cap = bucket_graph_cap
        # lazy: one sizes pass over the dataset (bucketed layouts only)
        self._bucket_ids = None
        self._sizes = None
        self._plain_nodes = None  # node counts cache for the plain layout
        self._padding_stats_cache = None  # (epoch, (real, padded))
        self._pool = SlotPool()  # host buffers of pooled() batches

    def set_epoch(self, epoch: int):
        self.epoch = epoch

    def _graph_cap(self) -> Optional[int]:
        return None if self.bucket_graph_cap == "budget" else self.batch_size

    def _indices(self):
        n = len(self.dataset)
        if self.shuffle:
            rng = np.random.default_rng(self.seed + self.epoch)
            idx = rng.permutation(n)
        else:
            idx = np.arange(n)
        if self.num_shards > 1:
            # pad to a multiple of num_shards by wrapping (DistributedSampler)
            total = -(-n // self.num_shards) * self.num_shards
            idx = np.concatenate([idx, idx[: total - n]])
            idx = idx[self.shard_id :: self.num_shards]
        return idx

    def _bucket_assignments(self):
        """One pass over the dataset caching (bucket id, node/edge/triplet
        counts) per sample — the packer's inputs."""
        if self._bucket_ids is None:
            ids, nodes, edges, trips = [], [], [], []
            with tr.span("bucket_assignments", graphs=len(self.dataset)):
                for i in range(len(self.dataset)):
                    d = self.dataset[i]
                    ids.append(self.layout.bucket_for(d.num_nodes))
                    nodes.append(d.num_nodes)
                    edges.append(d.num_edges)
                    trips.append(
                        _sample_triplets(d)[0].shape[0]
                        if self.layout.packs_triplets
                        else 0
                    )
            self._bucket_ids = np.asarray(ids, np.int64)
            self._sizes = (
                np.asarray(nodes, np.int64),
                np.asarray(edges, np.int64),
                np.asarray(trips, np.int64),
            )
        return self._bucket_ids

    def _batch_plan(self):
        """Bucketed epoch plan: per-bucket DistributedSampler sharding +
        greedy budget packing, then a global shuffle of batch ORDER across
        buckets. Deterministic in (seed, epoch) — every process derives
        the same plan, including every OTHER shard's packing, so all
        processes emit the same number of batches with identical shapes at
        every step (multi-host lockstep without communication). Cached per
        epoch: ``len(loader)`` + iteration must not pack twice."""
        if self._plan_cache is not None and self._plan_cache[0] == self.epoch:
            return self._plan_cache[1]
        assignments = self._bucket_assignments()
        # computed once an epoch, on whichever thread first asks: the
        # epoch boundary's loader work, under its own name
        with tr.span("batch_plan") as span:
            rng = np.random.default_rng(self.seed + self.epoch)
            plan = []
            nodes, edges, trips = self._sizes
            for b in range(len(self.layout.layouts)):
                lay = self.layout.layouts[b]
                bidx = np.nonzero(assignments == b)[0]
                n = len(bidx)
                if n == 0:
                    continue
                if self.shuffle:
                    bidx = bidx[rng.permutation(n)]
                if self.num_shards > 1:
                    total = -(-n // self.num_shards) * self.num_shards
                    bidx = np.concatenate([bidx, bidx[: total - n]])
                    # every process packs ALL shards to learn the common
                    # batch count; shards short of it wrap their own first
                    # batches (sample duplication — DistributedSampler's
                    # padding rule applied at batch granularity)
                    per_shard = [
                        _pack_indices(
                            bidx[s :: self.num_shards], nodes, edges, trips,
                            lay, batch_size=self._graph_cap(),
                        )
                        for s in range(self.num_shards)
                    ]
                    m = max(len(p) for p in per_shard)
                    own = per_shard[self.shard_id]
                    mine = list(own)
                    while len(mine) < m:
                        mine.append(mine[len(mine) % len(own)])
                    plan.extend((b, chunk) for chunk in mine)
                else:
                    plan.extend(
                        (b, chunk)
                        for chunk in _pack_indices(
                            bidx, nodes, edges, trips, lay,
                            batch_size=self._graph_cap(),
                        )
                    )
            if self.shuffle and plan:
                if self.contiguous_buckets:
                    # permute within each bucket segment + the segment
                    # order, preserving same-shape adjacency for multi-step
                    # stacking
                    segments = {}
                    for item in plan:
                        segments.setdefault(item[0], []).append(item)
                    keys = list(segments)
                    plan = []
                    for k in rng.permutation(len(keys)):
                        seg = segments[keys[k]]
                        plan.extend(seg[i] for i in rng.permutation(len(seg)))
                else:
                    order = rng.permutation(len(plan))
                    plan = [plan[i] for i in order]
            span.set(batches=len(plan), buckets=len({b for b, _ in plan}))
        self._plan_cache = (self.epoch, plan)
        return plan

    def batch_keys(self):
        """The epoch's batches in order, each named by what fixes every
        leaf's shape (the first bucket with its layout; one layout: a
        constant), known before anything is collated. Equal neighbours are
        a run that ``Trainer._group_plan`` may stack."""
        if isinstance(self.layout, BucketedLayout):
            layouts = self.layout.layouts
            return [layouts.index(layouts[b]) for b, _ in self._batch_plan()]
        return [0] * len(self)

    def __len__(self):
        if isinstance(self.layout, BucketedLayout):
            return len(self._batch_plan())
        n = len(self._indices())
        return -(-n // self.batch_size)

    def epoch_padding_stats(self):
        """(real_node_rows, padded_node_rows) over THIS epoch's (sharded)
        batch plan, or ``None`` when computing it would cost a dataset
        I/O pass — the training-side padding-waste accounting (the predict
        server tracks the same two integrals per micro-batch, and the
        telemetry layer reports ``1 - real/padded`` per epoch). Reuses the
        cached sizes/plan and is itself cached per epoch — the fit path
        logs a whole chunk of epochs against one unchanged plan."""
        if (
            self._padding_stats_cache is not None
            and self._padding_stats_cache[0] == self.epoch
        ):
            return self._padding_stats_cache[1]
        if isinstance(self.layout, BucketedLayout):
            plan_ready = (
                self._plan_cache is not None
                and self._plan_cache[0] == self.epoch
            )
            if not plan_ready and self._padding_stats_cache is not None:
                # the plan for THIS epoch was never built (device-resident
                # path: the loader is staged once, then only set_epoch
                # advances) — reporting the last computed integrals beats
                # forcing an O(dataset) repack purely for telemetry
                return self._padding_stats_cache[1]
            # the sizes pass is already paid: bucketed planning needs it
            self._bucket_assignments()
            nodes = self._sizes[0]
            plan = self._batch_plan()
            if plan:
                cat = np.concatenate([chunk for _, chunk in plan])
                real = int(nodes[cat].sum())
            else:
                real = 0
            padded = int(
                sum(self.layout.layouts[b].n_pad for b, _ in plan)
            )
        else:
            if self._plain_nodes is None:
                in_memory = isinstance(self.dataset, list) or (
                    isinstance(self.dataset, ConcatDataset)
                    and all(
                        isinstance(d, list) for d in self.dataset.datasets
                    )
                )
                if not in_memory:
                    # disk-backed datasets (ShardDataset, DistDataset)
                    # would deserialize EVERY sample just to read
                    # num_nodes — a full I/O pass stalling the epoch loop;
                    # telemetry simply omits the waste series there
                    return None
                self._plain_nodes = np.fromiter(
                    (d.num_nodes for d in self.dataset),
                    np.int64,
                    count=len(self.dataset),
                )
            idx = np.asarray(self._indices(), np.int64)
            real = int(self._plain_nodes[idx].sum())
            padded = len(self) * int(self.layout.n_pad)
        self._padding_stats_cache = (self.epoch, (real, padded))
        return real, padded

    def _batch_tasks(self):
        """(layout, sample-index chunk) pairs — the cheap plan half of
        iteration, separable from collation so worker pools can fan the
        expensive half out."""
        if isinstance(self.layout, BucketedLayout):
            for b, chunk in self._batch_plan():
                yield (self.layout.layouts[b], chunk)
            return
        idx = self._indices()
        for start in range(0, len(idx), self.batch_size):
            yield (self.layout, idx[start : start + self.batch_size])

    def _collate_task(self, task, pool=None):
        """Fetch and collate one batch: the ``collate`` span, with the
        counts the padding metrics read (real rows are those of the
        samples, not of the padding graph that fills the layout) and
        where its arrays came from (``slot``: ``"fresh"`` without a pool,
        else the slot's ``"made"`` / ``"reused"``). With a pool the batch
        comes with its slot, for the reader to give back."""
        layout, chunk = task
        slot = None
        if pool is not None:
            # what fixes every leaf's shape names the slot
            slot = pool.acquire(("batch",) + astuple(layout))
        with tr.span("collate") as span:
            with tr.span("fetch"):
                samples = [self.dataset[i] for i in chunk]
            batch = _collate_with_extras(samples, layout, slot=slot)
            g = len(samples)
            span.set(
                graphs=g,
                nodes=int(batch.n_node[:g].sum()),
                edges=int(batch.n_edge[:g].sum()),
                bucket=int(layout.n_pad),
                e_pad=int(layout.e_pad),
                slot="fresh" if slot is None else slot.state,
            )
        return batch if pool is None else (batch, slot)

    def _batches(self, pool=None):
        for task in self._batch_tasks():
            yield self._collate_task(task, pool)

    def __iter__(self):
        """Batches made of fresh arrays, the consumer's to keep."""
        return self._iterate(None)

    def pooled(self):
        """``(batch, slot)`` pairs for the consumer that holds the release
        end (the trainer's transfer stage): each batch is written into a
        slot of the loader's pool (``graph/slots.py``), which the consumer
        gives back (``slot.release()``) once nothing reads the batch's
        arrays any more. A slot that is not given back is not handed out
        again. The pool lives as long as the loader; its slots are made as
        they are first needed."""
        return self._iterate(self._pool)

    def pool_counts(self):
        """``{"reused", "made", "bytes"}`` of the pool so far
        (``SlotPool.counts``); zeros while nobody has asked for
        :meth:`pooled` batches."""
        return self._pool.counts()

    def _iterate(self, pool):
        # HYDRAGNN_NUM_WORKERS > 1: fan sample fetch + collation over a
        # worker pool (ordered), optionally core-pinned via OMP_PLACES +
        # HYDRAGNN_AFFINITY — the reference HydraDataLoader's thread-pool
        # + sched_setaffinity design (``load_data.py:94-204``, worker_init
        # ``:118-154``). Matters on many-core TPU-VM hosts feeding
        # multiple processes; pointless on a 1-core box.
        workers = env_int("HYDRAGNN_NUM_WORKERS", 1)
        if workers > 1:
            yield from prefetch_iter(
                self._batch_tasks(),
                max(self.prefetch, workers),
                fn=lambda task: self._collate_task(task, pool),
                workers=workers,
                name="graphloader-worker",
            )
            return
        if self.prefetch <= 0:
            yield from self._batches(pool)
            return
        # the collate stage of the input pipeline: its consumer (the
        # trainer's transfer stage, or whoever iterates) gets each batch as
        # it is finished, while the next one is being collated
        yield from prefetch_iter(
            self._batches(pool), self.prefetch, name="graphloader-prefetch"
        )


def _parse_omp_places(spec: Optional[str] = None):
    """OMP_PLACES -> list of core sets, one per place. Supports the forms
    the reference's worker_init parses (``load_data.py:118-154``):
    ``{0:4},{4:4}`` (start:len[:stride]) and explicit ``{0,2,4}`` lists.
    Unparseable input -> no places (pinning silently off)."""
    import re

    if spec is None:
        spec = os.environ.get("OMP_PLACES", "")
    places = []
    try:
        for m in re.finditer(r"\{([^}]*)\}", spec):
            cores = []
            for part in m.group(1).split(","):
                part = part.strip()
                if not part:
                    continue
                if ":" in part:
                    bits = [int(x) for x in part.split(":")]
                    start, length = bits[0], bits[1]
                    stride = bits[2] if len(bits) > 2 else 1
                    cores.extend(
                        range(start, start + length * stride, stride)
                    )
                else:
                    cores.append(int(part))
            if cores:
                places.append(cores)
    except ValueError:
        return []
    return places


def _pin_worker(index: int, places) -> None:
    """Pin the CURRENT thread to place ``index % len(places)`` — the
    reference's ``sched_setaffinity`` worker pinning. No-op without
    places, without OS support, or on denial (containers)."""
    if not places or not hasattr(os, "sched_setaffinity"):
        return
    try:
        os.sched_setaffinity(0, set(places[index % len(places)]))
    except OSError:
        pass


def _affinity_places():
    """Core places for worker pinning, when ``HYDRAGNN_AFFINITY`` opts in
    (the reference's HYDRAGNN_AFFINITY family, ``load_data.py:120-126``)."""
    if os.getenv("HYDRAGNN_AFFINITY", "0") != "1":
        return []
    return _parse_omp_places()


def prefetch_iter(
    source, depth: int, fn=None, name: str = "prefetch", workers: int = 1,
    probe=None, primed: bool = False,
):
    """Bounded background pipeline stage: applies ``fn`` (identity if
    None) to each item of ``source`` on worker thread(s), up to ``depth``
    results in flight ahead of the consumer, yielded in order.

    ``workers > 1`` fans ``fn`` over an ordered thread pool (the
    reference HydraDataLoader's num_workers model); each worker pins to
    its OMP_PLACES place when ``HYDRAGNN_AFFINITY=1``.

    ``probe``, when given, is called with the queue depth (ready items
    ahead of the consumer) at every consumer-side get — the streaming
    telemetry's ``stream_queue_depth`` gauge feed. Single-worker path
    only; the pool path's in-flight window is not a readiness signal.

    ``primed``: yield ``None`` once before the first item, as soon as the
    stage is up (the worker started, nothing waited for yet), so a consumer
    that times its waits can take the start-up apart from the first wait.

    Shared by the loader's collation prefetch and the trainer's
    double-buffered device transfers. The shutdown protocol matters: puts
    are stop-aware timed puts, so an abandoned consumer (early ``break``
    on HYDRAGNN_MAX_NUM_BATCH, or an exception while something retains the
    frame chain) cannot leak a thread pinning collated or device-resident
    batches; worker errors surface on the consumer side."""
    import queue
    import threading

    if fn is None:
        fn = lambda x: x  # noqa: E731
    places = _affinity_places()
    if workers > 1:
        if primed:
            yield None
        yield from _ordered_pool_map(source, fn, workers, depth, name, places)
        return
    q: "queue.Queue" = queue.Queue(maxsize=max(depth, 1))
    sentinel = object()
    stop = threading.Event()
    err = []

    def _put_stop_aware(item) -> bool:
        try:
            q.put_nowait(item)
            return True
        except queue.Full:
            pass
        # only the time blocked on a full queue is a span: the consumer
        # is the slower side for as long as this lasts
        with tr.span("queue_put_wait", depth=q.qsize()):
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
        return False

    def worker():
        # single pipeline threads deliberately do NOT pin: the collation
        # and device-transfer stages would otherwise all land on place 0
        # and time-share one core — only POOL workers (workers > 1) pin
        try:
            for b in source:
                if not _put_stop_aware(fn(b)):
                    return
        except BaseException as e:  # surface on the consumer side
            err.append(e)
        finally:
            # stop-aware sentinel delivery: on abandonment nobody reads it
            # and a blocking put could wedge on a full queue
            _put_stop_aware(sentinel)

    t = threading.Thread(target=worker, daemon=True, name=name)
    t.start()
    try:
        if primed:
            yield None
        while True:
            if probe is not None:
                probe(q.qsize())
            try:
                item = q.get_nowait()
            except queue.Empty:
                # the twin of ``queue_put_wait``: this thread starved by
                # the stage called ``name``
                with tr.span("queue_get_wait", queue=name):
                    item = q.get()
            if item is sentinel:
                break
            yield item
    finally:
        stop.set()
        # unblock a worker stuck on a full queue, then reap it — with a
        # BOUNDED join: generator close (an interrupted epoch, a break
        # on HYDRAGNN_MAX_NUM_BATCH) must never inherit a wedged
        # collate's wait, and the daemon flag keeps a pathological
        # worker from pinning interpreter exit
        try:
            while True:
                q.get_nowait()
        except queue.Empty:
            pass
        t.join(timeout=10.0)
        if not t.is_alive():
            # the worker is done but `source` may be suspended mid-yield
            # still referencing a collated (or device-resident) batch;
            # closing it runs its finally blocks and drops that
            # reference now instead of at GC time. Only safe once the
            # worker has exited — close() on an executing generator
            # raises ValueError.
            closer = getattr(source, "close", None)
            if callable(closer):
                try:
                    closer()
                except Exception:
                    pass
    if err:
        raise err[0]


def _ordered_pool_map(source, fn, workers, depth, name, places):
    """Ordered bounded map over a thread pool: at most ``max(depth,
    workers)`` items in flight, results yielded in source order. The
    consumer thread walks ``source`` (cheap plan work); workers run
    ``fn`` (fetch + collate). Abandonment cancels queued futures and the
    pool context join reaps the threads."""
    import itertools
    from concurrent.futures import ThreadPoolExecutor

    counter = itertools.count()

    def _init():
        _pin_worker(next(counter), places)

    window = []
    with ThreadPoolExecutor(
        max_workers=workers, thread_name_prefix=name, initializer=_init
    ) as ex:
        try:
            limit = max(depth, workers)
            for item in source:
                window.append(ex.submit(fn, item))
                if len(window) >= limit:
                    yield window.pop(0).result()
            while window:
                yield window.pop(0).result()
        finally:
            for f in window:
                f.cancel()
            # release the plan generator's suspended frame (iterated by
            # THIS thread, so it is suspended — not executing — whenever
            # this cleanup runs; closing it is race-free)
            closer = getattr(source, "close", None)
            if callable(closer):
                try:
                    closer()
                except Exception:
                    pass


def create_dataloaders(
    trainset,
    valset,
    testset,
    batch_size: int,
    need_triplets: bool = False,
    need_neighbors: bool = False,
    num_buckets: Optional[int] = None,
    contiguous_buckets: Optional[bool] = None,
    bucket_graph_cap: str = "batch",
    need_offsets: bool = False,
):
    """``num_buckets`` (the config's ``Training.batch_buckets``):
    size-bucketed layouts — <= num_buckets compiled programs per split,
    padding sized per bucket instead of at the dataset max. Default 1
    (single layout). ``contiguous_buckets`` (the config's
    ``Training.contiguous_buckets``) keeps same-shape batches adjacent so
    ``steps_per_dispatch`` can stack them (env override parsed inside
    ``GraphLoader``). ``HYDRAGNN_BATCH_BUCKETS`` overrides whatever the
    caller passes — the ONE place that env var's precedence lives."""
    num_buckets = env_int("HYDRAGNN_BATCH_BUCKETS", num_buckets or 1, minimum=1)
    layout = compute_layout(
        [trainset, valset, testset],
        batch_size,
        need_triplets,
        need_neighbors=need_neighbors,
        num_buckets=num_buckets,
        need_offsets=need_offsets,
    )
    return (
        GraphLoader(trainset, batch_size, layout, shuffle=True,
                    contiguous_buckets=contiguous_buckets,
                    bucket_graph_cap=bucket_graph_cap),
        GraphLoader(valset, batch_size, layout, shuffle=True,
                    contiguous_buckets=contiguous_buckets,
                    bucket_graph_cap=bucket_graph_cap),
        GraphLoader(testset, batch_size, layout, shuffle=True,
                    contiguous_buckets=contiguous_buckets,
                    bucket_graph_cap=bucket_graph_cap),
    )


def dataset_loading_and_splitting(config: dict):
    """Parity with ``preprocess/load_data.py:207-223``: raw -> serialized ->
    split pkls -> per-split datasets -> loaders."""
    from hydragnn_tpu.data.serialized import SerializedGraphLoader
    from hydragnn_tpu.models.create import needs_edge_offsets

    with tr.span("load_datasets") as span:
        paths = config["Dataset"]["path"]
        if not list(paths.values())[0].endswith(".pkl"):
            transform_raw_data_to_serialized(config["Dataset"])
        if "total" in paths:
            total_to_train_val_test_pkls(config)

        loader = SerializedGraphLoader(config)
        datasets = {}
        for name, p in config["Dataset"]["path"].items():
            if p.endswith(".pkl"):
                files_dir = p
            else:
                files_dir = (
                    f"{os.environ.get('SERIALIZED_DATA_PATH', os.getcwd())}"
                    f"/serialized_dataset/{config['Dataset']['name']}_{name}.pkl"
                )
            datasets[name] = loader.load_serialized_data(files_dir)
        span.set(splits=len(datasets))

        arch = config["NeuralNetwork"]["Architecture"]
        need_triplets = arch.get("model_type") == "DimeNet"
        need_neighbors = needs_dense_neighbors(
            arch_for_auto_policy(config["NeuralNetwork"])
        )
        training = config["NeuralNetwork"]["Training"]
        return create_dataloaders(
            datasets["train"],
            datasets["validate"],
            datasets["test"],
            batch_size=training["batch_size"],
            need_triplets=need_triplets,
            need_neighbors=need_neighbors,
            num_buckets=training.get("batch_buckets"),
            contiguous_buckets=training.get("contiguous_buckets"),
            bucket_graph_cap=training.get("bucket_graph_cap", "batch"),
            need_offsets=needs_edge_offsets(arch),
        )


def transform_raw_data_to_serialized(ds_config: dict):
    """Rank-0 raw parsing + serialization (``load_data.py:349-363``)."""
    from hydragnn_tpu.parallel.distributed import get_comm_size_and_rank

    _, rank = get_comm_size_and_rank()
    if rank == 0:
        fmt = ds_config["format"]
        if fmt in ("LSMS", "unit_test"):
            from hydragnn_tpu.data.lsms import LSMSDataset

            loader = LSMSDataset(ds_config)
        elif fmt == "CFG":
            from hydragnn_tpu.data.cfg import CFGDataset

            loader = CFGDataset(ds_config)
        elif fmt == "XYZ":
            from hydragnn_tpu.data.xyz import XYZDataset

            loader = XYZDataset(ds_config)
        else:
            raise NameError("Data format not recognized for raw data loader")
        loader.load_raw_data()


def total_to_train_val_test_pkls(config: dict, isdist: bool = False):
    """Split a monolithic pkl into train/val/test pkls and point the config at
    them (``load_data.py:366-407``)."""
    import pickle

    from hydragnn_tpu.data.split import split_dataset
    from hydragnn_tpu.parallel.distributed import get_comm_size_and_rank

    _, rank = get_comm_size_and_rank()
    paths = config["Dataset"]["path"]
    if list(paths.values())[0].endswith(".pkl"):
        file_dir = paths["total"]
    else:
        file_dir = (
            f"{os.environ.get('SERIALIZED_DATA_PATH', os.getcwd())}"
            f"/serialized_dataset/{config['Dataset']['name']}.pkl"
        )
    with open(file_dir, "rb") as f:
        minmax_node = pickle.load(f)
        minmax_graph = pickle.load(f)
        total = pickle.load(f)
    trainset, valset, testset = split_dataset(
        total,
        config["NeuralNetwork"]["Training"]["perc_train"],
        config["Dataset"]["compositional_stratified_splitting"],
    )
    serialized_dir = os.path.dirname(file_dir)
    config["Dataset"]["path"] = {}
    for name, ds in zip(
        ["train", "validate", "test"], [trainset, valset, testset]
    ):
        serial_name = f"{config['Dataset']['name']}_{name}.pkl"
        config["Dataset"]["path"][name] = os.path.join(serialized_dir, serial_name)
        if isdist or rank == 0:
            with open(os.path.join(serialized_dir, serial_name), "wb") as f:
                pickle.dump(minmax_node, f)
                pickle.dump(minmax_graph, f)
                pickle.dump(ds, f)
