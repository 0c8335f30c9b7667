"""Config-driven giant-graph training — the high-level surface for
graph-partition parallelism.

``run_training`` routes here when ``Architecture.partition_axis`` is set:
every dataset sample is ONE giant graph, partitioned node-wise across the
mesh (``parallel/graph_partition``). The trainer mirrors ``Trainer``'s
method surface (``init_state`` / ``train_epoch`` / ``evaluate`` /
``predict``) so the shared epoch driver (``train_validate_test``),
checkpointing and visualizer work unchanged.

No reference counterpart: HydraGNN's ``run_training`` can only scale over
many small graphs.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from hydragnn_tpu.models.create import init_model_params
from hydragnn_tpu.obs import runtime as obs
from hydragnn_tpu.obs.introspect import instrument
from hydragnn_tpu.train.optimizer import select_optimizer
from hydragnn_tpu.train.trainer import Trainer, TrainState, _nbatch
from hydragnn_tpu.utils import tracer as tr


def scan_budgets(datasets, num_parts, head_types, head_dims, need_triplets=False,
                 need_neighbors=False):
    """Union of the natural partition budgets over several datasets — pass
    the result to every split's ``PartitionedLoader`` so train/val/test
    share ONE compiled step/eval executable."""
    from hydragnn_tpu.parallel.graph_partition import partition_graph

    budgets = {}
    for ds in datasets:
        for s in ds:
            _, info = partition_graph(
                s, num_parts, tuple(head_types), tuple(head_dims),
                need_triplets=need_triplets, need_neighbors=need_neighbors,
            )
            for k, v in info.budgets.items():
                budgets[k] = max(budgets.get(k, 0), v)
    return budgets


class PartitionedLoader:
    """One giant graph per step. Samples are partitioned host-side ONCE with
    dataset-wide static budgets (max over samples, or the caller's
    ``budgets`` union across splits), so every step reuses a single compiled
    executable; results are cached."""

    def __init__(
        self,
        dataset,
        num_parts: int,
        head_types,
        head_dims,
        need_triplets: bool = False,
        need_neighbors: bool = False,
        shuffle: bool = True,
        seed: int = 42,
        axis: str = "graph",
        budgets: dict = None,
        need_offsets: bool = False,
    ):
        from hydragnn_tpu.parallel.graph_partition import partition_graph

        self.dataset = dataset
        self.num_parts = num_parts
        self.head_types = tuple(head_types)
        self.head_dims = tuple(head_dims)
        self.shuffle = shuffle
        self.seed = seed
        self.epoch = 0
        self.axis = axis

        if budgets is None:
            budgets = scan_budgets(
                [dataset], num_parts, self.head_types, self.head_dims,
                need_triplets, need_neighbors,
            )
        self._batches = []
        self.infos = []
        for s in dataset:
            b, info = partition_graph(
                s, num_parts, self.head_types, self.head_dims,
                need_triplets=need_triplets, need_neighbors=need_neighbors,
                budgets=budgets, need_offsets=need_offsets,
            )
            self._batches.append(b)
            self.infos.append(info)
        self.budgets = budgets

    def set_epoch(self, epoch: int):
        self.epoch = epoch

    def _order(self):
        n = len(self._batches)
        if self.shuffle:
            return np.random.default_rng(self.seed + self.epoch).permutation(n)
        return np.arange(n)

    def __len__(self):
        return len(self._batches)

    def __iter__(self):
        for i in self._order():
            yield self._batches[int(i)]


class PartitionedTrainer:
    """Drop-in trainer for partitioned giant-graph workloads.

    ``model`` carries ``partition_axis``; ``ref_model`` is its unpartitioned
    twin used only for parameter init (flax init cannot trace collectives
    outside shard_map; parameters are identical between the two).
    """

    def __init__(
        self,
        model,
        ref_model,
        training_config: dict,
        mesh,
        axis: str = "graph",
        verbosity: int = 0,
        freeze_conv: bool = False,
    ):
        self.model = model
        self.ref_model = ref_model
        self.training_config = training_config
        self.mesh = mesh
        self.axis = axis
        self.verbosity = verbosity
        self.freeze_conv = freeze_conv
        self.tx = None
        self._train_step = None
        self._eval_step = None
        # process-global optimizer-step counter: drives the fault-injection
        # hooks and the elastic heartbeat, same contract as Trainer
        self._host_step = 0
        opt_cfg = training_config.get("Optimizer", {})
        if opt_cfg.get("use_zero_redundancy") or int(
            opt_cfg.get("zero_stage") or 0
        ) >= 1:
            import warnings

            warnings.warn(
                "ZeRO sharding (use_zero_redundancy / zero_stage) is not "
                "applied in graph-partition mode: the mesh axis shards the "
                "GRAPH, not the batch, so optimizer state (and stage-3 "
                "parameters) stay replicated",
                stacklevel=2,
            )

    def init_state(self, sample, seed: int = 0) -> TrainState:
        """Parameters from the unpartitioned twin on a single collated copy
        of ``sample`` (one raw GraphData-like giant graph) — the production
        collation path, so DimeNet triplet tables come along automatically."""
        from hydragnn_tpu.data.dataobj import GraphData
        from hydragnn_tpu.data.loaders import _collate_with_extras, compute_layout
        from hydragnn_tpu.parallel.graph_partition import (
            make_partitioned_eval_step,
            make_partitioned_train_step,
            put_partitioned_state,
        )

        need_triplets = any(
            c.__name__ == "DIMEStack" for c in type(self.ref_model).__mro__
        )
        g = GraphData(
            x=np.asarray(sample.x),
            pos=None if getattr(sample, "pos", None) is None else np.asarray(sample.pos),
            edge_index=np.asarray(sample.edge_index),
            edge_attr=None
            if getattr(sample, "edge_attr", None) is None
            else np.asarray(sample.edge_attr),
        )
        g.targets = list(sample.targets)
        g.target_types = list(self.model.output_type)
        offset = (getattr(sample, "extras", None) or {}).get("edge_offset")
        if offset is not None:
            g.extras["edge_offset"] = np.asarray(offset, np.float32)
        layout = compute_layout(
            [[g]], batch_size=1, need_triplets=need_triplets,
            need_offsets=self.ref_model.needs_edge_offsets,
        )
        example_batch = _collate_with_extras([g], layout)

        variables = init_model_params(
            self.ref_model,
            jax.tree_util.tree_map(jnp.asarray, example_batch),
            seed=seed,
        )
        params = variables["params"]
        self.tx = select_optimizer(
            self.training_config, params=params, freeze_conv=self.freeze_conv
        )
        state = TrainState(
            params=params,
            batch_stats=variables.get("batch_stats", {}),
            opt_state=self.tx.init(params),
            step=jnp.zeros((), jnp.int32),
        )
        state = put_partitioned_state(state, self.mesh)
        # same XLA introspection as the data-parallel steps (steps.py):
        # per-bucket compiled cost/memory lands in the compile events
        self._train_step = instrument(
            "partitioned_train_step",
            make_partitioned_train_step(
                self.model, self.tx, self.mesh, self.axis
            ),
        )
        self._eval_step = instrument(
            "partitioned_eval_step",
            make_partitioned_eval_step(self.model, self.mesh, self.axis),
        )
        return state

    def put_batch(self, batch):
        from hydragnn_tpu.parallel.graph_partition import put_partitioned_batch

        return put_partitioned_batch(batch, self.mesh, self.axis)

    def place_state(self, state):
        """Re-impose the step's sharding after a checkpoint restore (see
        Trainer.place_state / put_partitioned_state). The
        use_zero_redundancy warning fires in ``__init__``, which every
        construction path goes through."""
        from hydragnn_tpu.parallel.graph_partition import put_partitioned_state

        return put_partitioned_state(state, self.mesh)

    # ---- epoch loops (Trainer surface) ---------------------------------
    @staticmethod
    def _acc_add(acc, metrics):
        """Collect per-step metrics without a host readback (device parts,
        stacked + fetched once per epoch, float64 host summation); on
        multi-host, eager ops on non-addressable jit outputs are disallowed
        so the (permitted) per-step host fetch is used instead. See
        Trainer._acc_add."""
        if jax.process_count() > 1:
            part = np.concatenate(
                [
                    [np.asarray(metrics["loss"], np.float64)],
                    [1.0],
                    np.asarray(metrics["tasks"], np.float64),
                ]
            )
        else:
            part = jnp.concatenate(
                [
                    metrics["loss"].astype(jnp.float32)[None],
                    jnp.ones((1,), jnp.float32),
                    metrics["tasks"].astype(jnp.float32),
                ]
            )
        acc = [] if acc is None else acc
        acc.append(part)
        return acc

    # identical readback contract (stack, ONE explicit device_get, float64
    # host sum) — shared with the data-parallel trainer so the two cannot
    # drift apart
    _acc_read = staticmethod(Trainer._acc_read)

    def train_epoch(self, state, loader, rng):
        from hydragnn_tpu.train import elastic
        from hydragnn_tpu.utils import faults

        acc = None
        nbatch = _nbatch(loader)
        tr.start("train")
        # one global read per epoch, per-step hooks only when live — the
        # same contract as Trainer.train_epoch
        _telemetry = obs.active()
        for ibatch, batch in enumerate(loader):
            if ibatch >= nbatch:
                break
            batch = self.put_batch(batch)
            rng, sub = jax.random.split(rng)
            t0 = time.perf_counter() if _telemetry is not None else 0.0
            # straggler injection inside the timed window, so the delay
            # reaches on_step -> flight-recorder stall detection
            faults.slow_step(self._host_step)
            state, metrics = self._train_step(state, batch, sub)
            if _telemetry is not None:
                _telemetry.on_step(time.perf_counter() - t0)
            acc = self._acc_add(acc, metrics)
            faults.kill_at_step(self._host_step)
            faults.lose_host_at_step(self._host_step)
            self._host_step += 1
            elastic.note_step(self._host_step)
        loss, tasks = self._acc_read(acc)
        tr.stop("train")
        return state, rng, loss, tasks

    def evaluate(self, state, loader, desc="validate"):
        acc = None
        nbatch = _nbatch(loader)
        for ibatch, batch in enumerate(loader):
            if ibatch >= nbatch:
                break
            batch = self.put_batch(batch)
            metrics = self._eval_step(state.params, state.batch_stats, batch)
            acc = self._acc_add(acc, metrics)
        return self._acc_read(acc)

    def predict(self, state, loader):
        """Per-sample outputs gathered back to global node order."""
        num_heads = self.model.num_heads
        head_types = self.model.output_type
        acc = None
        true_values = [[] for _ in range(num_heads)]
        predicted_values = [[] for _ in range(num_heads)]
        infos = getattr(loader, "infos", None)
        order = (
            loader._order() if hasattr(loader, "_order") else range(len(loader))
        )
        for i in (int(j) for j in order):
            batch = loader._batches[i]
            info = infos[i]
            dev = self.put_batch(batch)
            metrics = self._eval_step(state.params, state.batch_stats, dev)
            # loss/tasks accumulate on device, ONE readback at the end —
            # the per-sample float()/np.asarray() this replaces cost a
            # host round trip per giant graph (jaxlint:
            # host-sync-in-hot-loop)
            acc = self._acc_add(acc, metrics)
            # sample collection needs the outputs on host: one EXPLICIT
            # bulk fetch (device_get is transfer-guard-sanctioned), then
            # pure numpy below — targets/gather tables are host data
            outputs = jax.device_get(metrics["outputs"])
            for ihead in range(num_heads):
                # NLL mode appends a log-variance channel to every head's
                # output — collected values are the mean prediction only
                d = self.model.output_dim[ihead]
                if head_types[ihead] == "graph":
                    # replicated: shard 0's real-graph row
                    pred = outputs[ihead].reshape(
                        info.num_parts, 2, -1
                    )[0, 0][:d].reshape(-1, 1)
                    true = batch.targets[ihead].reshape(
                        info.num_parts, 2, -1
                    )[0, 0].reshape(-1, 1)
                else:
                    pred = info.gather_nodes(
                        outputs[ihead]
                    )[..., :d].reshape(-1, 1)
                    true = info.gather_nodes(
                        batch.targets[ihead]
                    ).reshape(-1, 1)
                predicted_values[ihead].append(pred)
                true_values[ihead].append(true)
        loss, tasks = self._acc_read(acc)
        true_values = [np.concatenate(v, axis=0) for v in true_values]
        predicted_values = [np.concatenate(v, axis=0) for v in predicted_values]
        return (loss, np.atleast_1d(tasks), true_values, predicted_values)
