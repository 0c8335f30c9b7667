"""Serialized (.pkl) dataset pipeline: edges, descriptors, targets.

Parity with ``hydragnn/preprocess/serialized_dataset_loader.py:33-241``:
load the pickled split, optionally rotate to principal axes, (re)compute the
radius graph (PBC-aware), append edge lengths, normalize them by the GLOBAL
max edge length, apply optional descriptors, extract per-head targets, select
input node-feature columns, optional stratified subsampling.
"""

import os
import pickle
from typing import List

import numpy as np

from hydragnn_tpu.data.dataobj import GraphData
from hydragnn_tpu.data.radius_graph import radius_graph, radius_graph_pbc
from hydragnn_tpu.data.transforms import (
    add_edge_lengths,
    normalize_rotation,
    point_pair_features,
    spherical_descriptor,
)
from hydragnn_tpu.utils import faults
from hydragnn_tpu.utils import tracer as tr
from hydragnn_tpu.utils.retry import retry_io


def extract_targets(
    output_type: List[str],
    output_index: List[int],
    graph_feature_dim: List[int],
    node_feature_dim: List[int],
    data: GraphData,
):
    """Per-head target extraction (analog of ``update_predicted_values``,
    ``preprocess/utils.py:237-278``): one array per head instead of packed
    y/y_loc — graph head [dim], node head [n, dim]."""
    targets = []
    for t, idx in zip(output_type, output_index):
        if t == "graph":
            start = sum(graph_feature_dim[:idx])
            dim = graph_feature_dim[idx]
            targets.append(
                np.asarray(data.y[start : start + dim], dtype=np.float32).reshape(
                    dim
                )
            )
        elif t == "node":
            start = sum(node_feature_dim[:idx])
            dim = node_feature_dim[idx]
            targets.append(
                np.asarray(
                    data.x[:, start : start + dim], dtype=np.float32
                ).reshape(data.num_nodes, dim)
            )
        else:
            raise ValueError(f"Unknown output type: {t}")
    data.targets = targets
    data.target_types = list(output_type)
    return data


def select_input_node_features(input_node_features: List[int], data: GraphData):
    """Column-select the model inputs (``update_atom_features``,
    ``preprocess/utils.py:281-292``)."""
    data.x = data.x[:, input_node_features]
    return data


class SerializedGraphLoader:
    def __init__(self, config: dict, dist: bool = False):
        ds = config["Dataset"]
        arch = config["NeuralNetwork"]["Architecture"]
        voi = config["NeuralNetwork"]["Variables_of_interest"]
        self.verbosity = config.get("Verbosity", {}).get("level", 0)
        self.node_feature_dim = ds["node_features"]["dim"]
        self.graph_feature_dim = ds["graph_features"]["dim"]
        self.rotational_invariance = ds.get("rotational_invariance", False)
        self.periodic = arch.get("periodic_boundary_conditions", False)
        from hydragnn_tpu.models.create import needs_edge_offsets

        # each edge's image is kept on the sample only for a stack that
        # reads it: every other stack's samples stay as they were
        self.keep_offsets = needs_edge_offsets(arch)
        self.radius = arch["radius"]
        self.max_neighbours = arch["max_neighbours"]
        self.variables = voi
        self.output_type = voi["type"]
        self.output_index = voi["output_index"]
        self.input_node_features = voi["input_node_features"]
        self.spherical_coordinates = False
        self.point_pair_features = False
        if "Descriptors" in ds:
            self.spherical_coordinates = ds["Descriptors"].get(
                "SphericalCoordinates", False
            )
            self.point_pair_features = ds["Descriptors"].get(
                "PointPairFeatures", False
            )
        self.dist = dist

    def load_serialized_data(self, dataset_path: str) -> List[GraphData]:
        def _read():
            faults.flaky_read(dataset_path)
            with open(dataset_path, "rb") as f:
                _ = pickle.load(f)  # minmax node
                _ = pickle.load(f)  # minmax graph
                return pickle.load(f)

        # one span a stage and split (never one a sample): set-up's split
        # of the loader's time, docs/observability.md "Training spans"
        with tr.span(
            "read_split", split=os.path.basename(dataset_path)
        ) as span:
            # one big read off a shared filesystem: transient OSError gets
            # jittered-backoff retries instead of killing the job at startup
            dataset = retry_io(_read, what=dataset_path)
            span.set(graphs=len(dataset), bytes=os.path.getsize(dataset_path))

        if self.rotational_invariance:
            dataset = [normalize_rotation(d) for d in dataset]

        with tr.span(
            "radius_graph", graphs=len(dataset), periodic=bool(self.periodic),
            max_neighbours=self.max_neighbours,
        ) as span:
            atoms = edges = 0
            for data in dataset:
                if self.periodic:
                    edge_index, lengths, offsets = radius_graph_pbc(
                        data.pos,
                        data.supercell_size,
                        self.radius,
                        self.max_neighbours,
                    )
                    data.edge_index = edge_index
                    if self.keep_offsets:
                        data.extras["edge_offset"] = offsets
                    data.edge_attr = lengths[:, None].astype(np.float32)
                else:
                    data.edge_index = radius_graph(
                        data.pos, self.radius, self.max_neighbours
                    )
                    data.edge_attr = None
                    add_edge_lengths(data)
                atoms += data.num_nodes
                edges += data.num_edges
            span.set(atoms=int(atoms), edges=int(edges))

        with tr.span("finish_split", graphs=len(dataset)):
            max_edge_length = 0.0
            for data in dataset:
                if data.edge_attr.size:
                    max_edge_length = max(
                        max_edge_length, float(data.edge_attr.max())
                    )
            if self.dist:
                from hydragnn_tpu.parallel.distributed import host_allreduce

                max_edge_length = float(
                    host_allreduce(np.asarray([max_edge_length]), op="max")[0]
                )
            max_edge_length = max(max_edge_length, 1e-12)
            for data in dataset:
                data.edge_attr = data.edge_attr / max_edge_length

            if self.spherical_coordinates:
                dataset = [spherical_descriptor(d) for d in dataset]
            if self.point_pair_features:
                dataset = [point_pair_features(d) for d in dataset]

            for data in dataset:
                extract_targets(
                    self.output_type,
                    self.output_index,
                    self.graph_feature_dim,
                    self.node_feature_dim,
                    data,
                )
                select_input_node_features(self.input_node_features, data)

            if "subsample_percentage" in self.variables:
                from hydragnn_tpu.data.split import stratified_subsample

                return stratified_subsample(
                    dataset, self.variables["subsample_percentage"]
                )
            return dataset
