"""Serving bucket plans: request -> static padding bucket -> padded batch.

The serving counterpart of the training loader's bucketed layouts
(``data/loaders.py``): a fixed, ascending family of
:class:`~hydragnn_tpu.data.loaders.BatchLayout` paddings, each the shape
signature of ONE pre-compiled predict executable. A request is routed to
the smallest bucket whose PER-GRAPH capacity covers it — node count AND
edge count (and triplet count for DimeNet layouts); a dense graph whose
edges overflow its node-natural bucket falls through to the next larger
one instead of failing. Batch packing is budget-greedy like
``_pack_indices``: requests accumulate until the next one would overflow
the bucket's padded sizes, so every packed batch fits its layout by
construction and never recompiles.

Sizing reuses the loader's own machinery (``_partition_node_bounds``
exact-DP boundaries, ``_layout_from_maxima`` worst-case pads) so a plan
derived from a sample of production graphs gives the same low-waste
shapes training already measured (94% padding efficiency on OC20-shaped
distributions, README).
"""

from dataclasses import dataclass, field, replace
from typing import List, Optional, Sequence, Tuple

import numpy as np

from hydragnn_tpu.data.dataobj import GraphData
from hydragnn_tpu.data.loaders import (
    BatchLayout,
    BucketedLayout,
    _layout_from_maxima,
    _lcm,
    _partition_node_bounds,
    _sample_degrees,
    _sample_triplets,
    collate_for_layout,
)


class GraphTooLarge(ValueError):
    """The graph exceeds the largest bucket's per-graph capacity."""


@dataclass(frozen=True)
class BucketCapacity:
    """Per-graph admission limits for one bucket (a single request must
    fit a batch alone: ``n_pad`` reserves one padding node)."""

    max_nodes: int
    max_edges: int
    max_triplets: int = 0

    def admits(self, num_nodes: int, num_edges: int, num_triplets: int = 0):
        return (
            num_nodes <= self.max_nodes
            and num_edges <= self.max_edges
            and (self.max_triplets == 0 or num_triplets <= self.max_triplets)
        )


@dataclass
class ServingBucketPlan:
    """Ascending bucket layouts + per-bucket admission capacities.

    ``warmup_sample`` is a small :class:`GraphData` used to pre-compile
    every bucket's executable at startup (it must fit bucket 0, so it
    fits all)."""

    layouts: List[BatchLayout]
    capacities: List[BucketCapacity]
    warmup_sample: Optional[GraphData] = None
    node_bounds: List[int] = field(default_factory=list)

    def __post_init__(self):
        if not self.layouts:
            raise ValueError("a serving plan needs at least one bucket")
        if len(self.layouts) != len(self.capacities):
            raise ValueError("layouts and capacities must pair up")

    @property
    def num_buckets(self) -> int:
        return len(self.layouts)

    def request_sizes(self, graph: GraphData) -> Tuple[int, int, int]:
        """(nodes, edges, triplets) of one request — triplets computed
        (and cached on the sample) only for triplet-packing layouts."""
        t = 0
        if self.layouts[0].packs_triplets:
            t = int(_sample_triplets(graph)[0].shape[0])
        return int(graph.num_nodes), int(graph.num_edges), t

    def select(self, graph: GraphData) -> int:
        """Smallest admitting bucket, falling through to larger ones when
        edge/triplet counts overflow the node-natural bucket. Raises
        :class:`GraphTooLarge` when nothing admits the graph."""
        return self.admit(graph)[0]

    def admit(self, graph: GraphData) -> Tuple[int, Tuple[int, int, int]]:
        """One-pass admission: ``(bucket, (nodes, edges, triplets))`` —
        what the server's submit path needs, without re-deriving the
        sizes per check. Raises :class:`GraphTooLarge` when nothing
        admits the graph."""
        sizes = self.request_sizes(graph)
        n, e, t = sizes
        for b, cap in enumerate(self.capacities):
            if cap.admits(n, e, t):
                return b, sizes
        raise GraphTooLarge(
            f"graph with {n} nodes / {e} edges exceeds the largest serving "
            f"bucket (max {self.capacities[-1].max_nodes} nodes / "
            f"{self.capacities[-1].max_edges} edges); re-plan with larger "
            "buckets or partition the graph"
        )

    def natural_bucket(self, num_nodes: int) -> int:
        """The bucket the node count alone would pick — ``select`` beyond
        this index means an edge/triplet-overflow fallback."""
        for b, cap in enumerate(self.capacities):
            if num_nodes <= cap.max_nodes:
                return b
        return len(self.capacities) - 1

    def pack(self, graphs: Sequence[GraphData], bucket: int):
        """Collate admitted requests into bucket ``bucket``'s static
        shapes (inputs only — requests carry no targets). Returns the
        padded batch plus per-request (graph-row, node-offset, node-count)
        coordinates for slicing the model outputs back apart."""
        layout = self.layouts[bucket]
        batch = collate_for_layout(list(graphs), layout, with_targets=False)
        coords = []
        off = 0
        for g, sample in enumerate(graphs):
            n = int(sample.num_nodes)
            coords.append((g, off, n))
            off += n
        return batch, coords

    def fits_batch(
        self,
        bucket: int,
        acc_nodes: int,
        acc_edges: int,
        acc_trips: int,
        acc_graphs: int,
        sizes: Tuple[int, int, int],
    ) -> bool:
        """Would adding a request of ``sizes`` keep the accumulating
        batch inside bucket ``bucket``'s padded budgets? (The greedy
        packing rule of ``_pack_indices``, applied online.)"""
        lay = self.layouts[bucket]
        n, e, t = sizes
        return (
            acc_nodes + n <= lay.n_pad - 1
            and acc_edges + e <= lay.e_pad
            and (not lay.packs_triplets or acc_trips + t <= lay.t_pad)
            and acc_graphs + 1 <= lay.g_pad - 1
        )


def plan_from_samples(
    samples: Sequence[GraphData],
    max_batch_graphs: int = 8,
    num_buckets: int = 3,
    need_triplets: bool = False,
    need_neighbors: bool = False,
    headroom: float = 1.0,
    need_offsets: bool = False,
) -> ServingBucketPlan:
    """Derive a serving plan from representative graphs (e.g. the
    training set or a traffic sample).

    Buckets are worst-case sized: a batch of ``max_batch_graphs`` graphs
    each at the bucket's observed maxima always fits, so admission is a
    pure per-graph check and packing never re-plans. ``headroom``
    multiplies the observed per-bucket node/edge maxima so production
    graphs slightly larger than the sample still admit (capacity grows
    with the pad). ``need_offsets``: the served stack's
    ``models/create.py needs_edge_offsets`` (each edge's periodic image in
    the batch); a periodic SchNet refuses a plan without it."""
    if not samples:
        raise ValueError("plan_from_samples needs at least one sample")
    if headroom < 1.0:
        raise ValueError("headroom must be >= 1.0")
    nodes = np.asarray([s.num_nodes for s in samples])
    edges = np.asarray([s.num_edges for s in samples])
    trips = np.zeros(len(samples), np.int64)
    kis = kos = np.ones(len(samples), np.int64)
    if need_triplets and not need_neighbors:
        trips = np.asarray(
            [_sample_triplets(s)[0].shape[0] for s in samples]
        )
    if need_neighbors:
        # (0, 0) for an edgeless sample: the layout keeps widths >= 1
        deg = [_sample_degrees(s) for s in samples]
        kis = np.asarray([d[0] for d in deg])
        kos = np.asarray([d[1] for d in deg])
    try:
        import jax

        device_multiple = jax.device_count()
    except Exception:
        device_multiple = 1
    mult = _lcm(8, max(device_multiple, 1))
    bounds = _partition_node_bounds(nodes, num_buckets)
    layouts, capacities = [], []
    lo = 0
    for hi in bounds:
        mask = (nodes > lo) & (nodes <= hi)
        if not mask.any():
            lo = hi
            continue
        cap_nodes = int(np.ceil(hi * headroom))
        cap_edges = int(np.ceil(int(edges[mask].max()) * headroom))
        cap_trips = int(np.ceil(int(trips[mask].max()) * headroom))
        layouts.append(replace(
            _layout_from_maxima(
                cap_nodes,
                max(cap_edges, 1),
                cap_trips,
                int(kis[mask].max()),
                int(kos[mask].max()),
                max_batch_graphs,
                mult,
                device_multiple,
                (),  # inference batches pack no targets
                (),
                need_triplets,
                need_neighbors,
            ),
            need_offsets=need_offsets,
        ))
        capacities.append(
            BucketCapacity(
                max_nodes=cap_nodes,
                max_edges=max(cap_edges, 1),
                max_triplets=cap_trips if need_triplets else 0,
            )
        )
        lo = hi
    smallest = samples[int(np.argmin(nodes))]
    return ServingBucketPlan(
        layouts=layouts,
        capacities=capacities,
        warmup_sample=smallest.clone(),
        node_bounds=[c.max_nodes for c in capacities],
    )


def plan_from_layout(
    layout,
    warmup_sample: GraphData,
    node_bounds: Optional[Sequence[int]] = None,
) -> ServingBucketPlan:
    """Adopt a training-time layout (``compute_layout`` output) as the
    serving plan — the compiled-shape family then matches training's
    exactly, so a warm training compile cache doubles as the serving
    warmup. Budget-sized training buckets guarantee any SINGLE graph of
    the bucket fits (``n_pad - 1``/``e_pad`` floors in
    ``build_budget``), which is exactly the admission rule here."""
    layouts = (
        list(layout.layouts)
        if isinstance(layout, BucketedLayout)
        else [layout]
    )
    bounds = list(
        node_bounds
        if node_bounds is not None
        else getattr(layout, "node_bounds", [])
    )
    capacities = []
    for i, lay in enumerate(layouts):
        cap_nodes = (
            min(bounds[i], lay.n_pad - 1) if i < len(bounds) else lay.n_pad - 1
        )
        if lay.nbr_reach:
            # a dense-list layout states its largest graph; collate refuses
            # a larger one, so admission does first
            cap_nodes = min(cap_nodes, lay.nbr_reach)
        capacities.append(
            BucketCapacity(
                max_nodes=cap_nodes,
                max_edges=lay.e_pad,
                max_triplets=lay.t_pad if lay.packs_triplets else 0,
            )
        )
    return ServingBucketPlan(
        layouts=layouts,
        capacities=capacities,
        warmup_sample=warmup_sample.clone(),
        node_bounds=[c.max_nodes for c in capacities],
    )
