"""The ``Trainer``: state management, device placement, and epoch loops.

TPU-first redesign of ``hydragnn/train/train_validate_test.py``: instead of
an imperative hot loop (zero_grad / forward / backward / step as separate
CUDA launches, ``:437-540``), ONE XLA program per training step — forward,
masked multi-task loss, backward, optimizer update and BatchNorm-stat
update fused by the compiler. Data parallelism comes from sharding the
batch over the mesh's ``data`` axis; gradient all-reduce is inserted by
XLA over ICI (no NCCL, no DDP hooks).

Round-3 split (verdict item 10): the traced programs live in
``steps.py`` (:func:`~hydragnn_tpu.train.steps.build_steps`), the wire
format in ``transfer.py``, the predict paths in ``predict.py``
(:class:`~hydragnn_tpu.train.predict.PredictMixin`), the epoch driver in
``epoch_driver.py``, and shared state containers in ``common.py``. This
module re-exports the public names so existing imports keep working.
"""

import itertools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from hydragnn_tpu.graph.batch import GraphBatch, stack_batches, stack_into
from hydragnn_tpu.graph.slots import filled
from hydragnn_tpu.obs import runtime as obs
from hydragnn_tpu.models.create import init_model_params
from hydragnn_tpu.train.common import (  # noqa: F401  (re-exported API)
    SchedState,
    TrainState,
    _env_flag,
    _is_oom,
    _nbatch,
)
from hydragnn_tpu.train.epoch_driver import (  # noqa: F401  (re-exported)
    train_validate_test,
)
from hydragnn_tpu.train.optimizer import select_optimizer
from hydragnn_tpu.train.predict import PredictMixin
from hydragnn_tpu.train.steps import build_steps
from hydragnn_tpu.train.transfer import (  # noqa: F401  (re-exported API)
    _decompact_traced,
    _offset_local_shard,
)
from hydragnn_tpu.utils import tracer as tr

# cached at module scope: a fresh ``jax.jit(lambda ...)`` built at the call
# site re-traces on EVERY invocation (the jit cache keys on function object
# identity) — one deep-copy program serves every fit_staged best-state seed
_copy_tree = jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t))


def _goes_alone(loader, nbatch, K):
    """The epoch's batches that are dispatched alone, by index: the tail of
    a run of equal keys that is short of ``K`` (``Trainer._group_plan``).
    ``None`` where the loader states no keys."""
    keys_of = getattr(loader, "batch_keys", None)
    if K == 1 or keys_of is None:
        return None
    alone, start = set(), 0
    for _, run in itertools.groupby(keys_of()[:nbatch]):
        n = sum(1 for _ in run)
        alone.update(range(start + n - n % K, start + n))
        start += n
    return alone


class _Group(list):
    """The batches of one dispatch. ``stacked`` is their ``stack_batches``
    where ``Trainer._group_plan`` laid them down as they arrived; ``slots``
    are the pool's buffers (``graph/slots.py``) that what will be put is
    made of, for the put stage to give back once the transfer has read
    them."""

    stacked = None
    slots = ()

    def singles(self):
        """Its batches one by one, for a group that a new shape or the
        loader's end found short: each takes its own slot along. Where
        they were stacked on arrival they are rows of the stacked arrays,
        whose slot then stays out of the pool."""
        own = self.slots if self.stacked is None else ()
        for i, batch in enumerate(self):
            yield _single(batch, own[i] if own else None)


def _pooled(loader):
    """``(batch, slot)`` pairs: the loader's own where it hands out the
    release end (``GraphLoader.pooled``), else every slot ``None`` (a list,
    ``StreamLoader``: fresh arrays, as they come)."""
    pooled = getattr(loader, "pooled", None)
    if pooled is None:
        return ((batch, None) for batch in loader)
    return pooled()


def _pool_counts(loader):
    """The loader's pool counters (``SlotPool.counts``), None for a loader
    that has no pool."""
    counts = getattr(loader, "pool_counts", None)
    return None if counts is None else counts()


def _single(batch, slot):
    group = _Group([batch])
    if slot is not None:
        group.slots = (slot,)
    return group


def _taken_by_device(host, dev):
    """The host arrays that a device array ALIASES: the CPU backend takes a
    64-byte-aligned numpy buffer as it is (zero copy), for the device
    array's whole life. Decided by looking: a device array of another
    platform lives in its own memory; one on the CPU is asked where."""
    devs = jax.tree_util.tree_leaves(dev)
    if not devs or next(iter(devs[0].devices())).platform != "cpu":
        return []
    taken = []
    for h, d in zip(jax.tree_util.tree_leaves(host), devs):
        if not isinstance(h, np.ndarray) or not h.nbytes:
            continue
        lo = h.ctypes.data
        if any(
            lo <= shard.data.unsafe_buffer_pointer() < lo + h.nbytes
            for shard in d.addressable_shards
        ):
            taken.append(h)
    return taken


def _give_back(slots, host, dev):
    """The release rule of the put stage. ``jnp.asarray`` / ``device_put``
    of a numpy array return before the runtime has read it (jax 0.9.0,
    TPU v5e and CPU alike: an overwrite right after the call shows in the
    device copy; PERF.md section 6, PR 33), so the slots go back only after
    the transfer has completed, waited for on the thread that put
    (``h2d_wait``). An array the device took for its own leaves its slot
    first."""
    with tr.span("h2d_wait"):
        jax.block_until_ready(dev)
    taken = _taken_by_device(host, dev)
    for slot in slots:
        slot.release(forget=taken)


class Trainer(PredictMixin):
    def __init__(
        self,
        model,
        training_config: dict,
        mesh=None,
        verbosity: int = 0,
        freeze_conv: bool = False,
    ):
        # every Trainer front-door (driver, examples, benches) gets the
        # persistent XLA cache; idempotent. NOTE this mutates process-
        # global JAX config (utils/compile_cache.py), i.e. it affects every
        # jit compilation of the embedding process, not just this
        # library's; HYDRAGNN_COMPILE_CACHE=0 opts out
        from hydragnn_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
        self.model = model
        self.training_config = training_config
        self.mesh = mesh
        self.verbosity = verbosity
        self.freeze_conv = freeze_conv
        self.tx = None
        self._steps = None
        self._batch_sharding = None
        self._stacked_sharding = None
        # rule-engine state placement (parallel/rules.py), computed by
        # place_state and declared as the step programs' in/out shardings
        self._state_shardings = None
        self._sharding_summary = None
        # one dispatch runs this many optimizer steps via lax.scan (1 = the
        # plain per-batch path); settable in config or HYDRAGNN_STEPS_PER_DISPATCH
        from hydragnn_tpu.utils.envparse import env_int

        self.steps_per_dispatch = env_int(
            "HYDRAGNN_STEPS_PER_DISPATCH",
            int(training_config.get("steps_per_dispatch", 1)),
        )
        # streaming double-buffering: keep this many batches' H2D transfers
        # in flight AHEAD of the step consuming them, issued from a
        # background thread (the role of the reference's DDStore
        # double-buffered loader, train_validate_test.py:459-536). Costs
        # `depth` extra batches of HBM. Default OFF: jax's async dispatch
        # already overlaps transfer and compute when the host is not the
        # bottleneck, and whether the extra thread pays on a TPU host is
        # not measured on the current tree (benchmarks/streaming_bench.py
        # is the A/B). Enable via config or HYDRAGNN_DEVICE_PREFETCH.
        self.device_prefetch = env_int(
            "HYDRAGNN_DEVICE_PREFETCH",
            int(training_config.get("device_prefetch", 0)),
        )
        # divergence guard (train/guard.py): skip non-finite steps, restore
        # last-good with halved LR after N consecutive bad ones. Opt-in —
        # it costs a snapshot + a scalar fetch per step.
        from hydragnn_tpu.train.guard import DivergenceGuard, guard_enabled

        self.guard = (
            DivergenceGuard(training_config)
            if guard_enabled(training_config)
            else None
        )
        # process-global optimizer-step counter: drives the fault-injection
        # hooks (kill_at_step / nan_at_step, utils/faults.py)
        self._host_step = 0
        # the last put whose host arrays are the pool's: (slots, host, dev)
        # until its transfer is known to have completed (``_settle``)
        self._in_flight = None

    # compiled-program accessors: tests and the partitioned trainer reach
    # these by their historical names
    @property
    def _train_step(self):
        return self._steps.train_step

    @property
    def _train_multi(self):
        return self._steps.train_multi

    @property
    def _epoch_scan(self):
        return self._steps.epoch_scan

    @property
    def _eval_epoch(self):
        return self._steps.eval_epoch

    @property
    def _predict_scan(self):
        return self._steps.predict_scan

    @_predict_scan.setter
    def _predict_scan(self, fn):  # tests monkeypatch this hook
        self._steps.predict_scan = fn

    @property
    def _fit_scan(self):
        return self._steps.fit_scan

    @property
    def _eval_step(self):
        return self._steps.eval_step

    @property
    def _eval_multi(self):
        return self._steps.eval_multi

    # ---- state ---------------------------------------------------------
    def init_state(self, example_batch: GraphBatch, seed: int = 0) -> TrainState:
        if self.mesh is None or jax.process_count() == 1:
            init_batch = self.put_batch(example_batch)
        else:
            # multi-host: init on a process-local copy — parameters depend
            # only on shapes and the seed, so every process derives identical
            # values (flax init cannot trace non-addressable global shards)
            init_batch = jax.tree_util.tree_map(jnp.asarray, example_batch)
        variables = init_model_params(self.model, init_batch, seed=seed)
        params = variables["params"]
        batch_stats = variables.get("batch_stats", {})
        self.tx = select_optimizer(
            self.training_config, params=params, freeze_conv=self.freeze_conv
        )
        opt_state = self.tx.init(params)
        state = TrainState(
            params=params,
            batch_stats=batch_stats,
            opt_state=opt_state,
            step=jnp.zeros((), jnp.int32),
        )
        state = self.place_state(state)
        self._build_steps()
        return state

    def place_state(self, state: TrainState) -> TrainState:
        """Build the state DIRECTLY at the step programs' input shardings
        — used at init AND after checkpoint restore (a host-restored
        state fed straight in costs a duplicate sharding-signature
        compile; on the 2-D mesh it would hard-error against the
        explicit ``in_shardings``).

        Placement is the rule engine's (``parallel/rules.py``): matmul
        weights column-split over ``model``, biases/norms replicated,
        ZeRO's ``data``-axis overlay on optimizer moments (stage >= 1)
        and parameters (stage 3) — every leaf lands at its target
        sharding in one hop, no host-side replicate-then-reshard (which
        would transiently hold the full state on every device). The
        multi-process path assembles each leaf's global array from the
        identical host-local values (seeded init / restored checkpoint)."""
        if self.mesh is None:
            return jax.tree_util.tree_map(jnp.asarray, state)
        from hydragnn_tpu.parallel import rules

        self._state_shardings = rules.state_shardings(
            state,
            self.mesh,
            zero_stage=self._zero_stage(),
            rules=rules.resolve_rules(self.training_config),
        )
        self._sharding_summary = rules.summarize_shardings(
            state, self._state_shardings
        )
        return rules.put_tree(state, self._state_shardings)

    def sharding_summary(self):
        """Rule-engine placement report of the last ``place_state`` (the
        ``param_sharding`` event payload); None before placement."""
        return self._sharding_summary

    def _zero_stage(self) -> int:
        """Resolved ZeRO stage: ``Training.Optimizer.zero_stage`` (0-3,
        DeepSpeed's scale — ``run_training.py:134-151``); absent, the
        reference's ``use_zero_redundancy`` bool maps to stage 1. Stages
        1 and 2 are one implementation (gradient partitioning is XLA's
        scheduling decision, not a user knob); stage 3 also shards the
        parameters."""
        opt = self.training_config.get("Optimizer", {})
        stage = opt.get("zero_stage")
        if stage is None:
            return 1 if opt.get("use_zero_redundancy") else 0
        return int(stage)

    def _zero_enabled(self) -> bool:
        """ZeRO sharding active? — the reference's ZeroRedundancyOptimizer
        / DeepSpeed-ZeRO switch (``utils/optimizer.py:142-151``). A
        sharding decision, not a different optimizer — XLA inserts the
        all-gathers."""
        return self._zero_stage() >= 1

    def _compact_for_transfer(
        self, batch: GraphBatch, allow_pos_placeholder: bool = True,
        slot=None,
    ):
        """Shrink the host->device wire format (streaming is H2D-bound;
        undone INSIDE the jitted step by ``_decompact_traced``):

        - index arrays (senders/receivers/node_graph) travel as int16 when
          the node/graph counts fit, and are cast back to int32 on device —
          the jitted step still sees int32, so nothing else changes;
        - ``pos`` is replaced by a ``[..., 1, 3]`` placeholder when the
          model never reads positions (no distance/coordinate convs, no
          equivariance); the step synthesizes a device-side fill. Disabled
          under a mesh (``allow_pos_placeholder=False``): a 1-row axis
          cannot shard over the data axis.

        Applies to single-process transfers (plain and mesh-sharded); the
        multi-host assembly path ships uncompacted. ``compact_transfer`` /
        ``HYDRAGNN_COMPACT_TRANSFER`` (default on) disables it entirely.
        ``slot``: the pool's buffer to write the copies into.
        """
        if not _env_flag(
            "HYDRAGNN_COMPACT_TRANSFER", self.training_config,
            "compact_transfer", default=True,
        ):
            return batch
        # shape[-2] of x is the node count for both plain [N, F] and
        # stacked [K, N, F] layouts; n_node's last axis is the graph count
        if batch.x.shape[-2] < 2**15 and batch.n_node.shape[-1] < 2**15:

            def int16(name):
                wide = np.asarray(getattr(batch, name))
                out = filled(slot, "wire/" + name, wide.shape, np.int16, None)
                np.copyto(out, wide, casting="unsafe")
                return out

            batch = batch.replace(
                senders=int16("senders"),
                receivers=int16("receivers"),
                node_graph=int16("node_graph"),
            )
        needs_pos = getattr(self.model, "conv_needs_pos", True) or getattr(
            self.model, "equivariance", False
        )
        if not needs_pos and allow_pos_placeholder:
            placeholder = filled(
                slot, "wire/pos", batch.pos.shape[:-2] + (1, 3), np.float32
            )
            batch = batch.replace(pos=placeholder)
        return batch

    def put_batch(self, batch: GraphBatch) -> GraphBatch:
        """Host batch -> device(s). Under a mesh, every leading axis (nodes /
        edges / graphs / triplets) is sharded over the ``data`` axis — the
        layout pads each to a multiple of the axis size.

        Multi-host (``jax.process_count() > 1``): each process passes ITS
        loader's local shard (the DistributedSampler split) and the global
        sharded batch is assembled with ``make_array_from_process_local_data``
        — the reference's per-rank DataLoader semantics
        (``preprocess/load_data.py:237-245``) with XLA owning the transport.
        """
        return self._put(batch, stacked=False)

    def put_batch_stacked(self, stacked: GraphBatch) -> GraphBatch:
        """Like :meth:`put_batch` for a ``stack_batches`` result: the scan
        axis stays unsharded, each microbatch's leading axis shards over
        ``data``."""
        return self._put(stacked, stacked=True)

    def _put(self, batch: GraphBatch, stacked: bool, slots=()) -> GraphBatch:
        """The one transfer path: the host-side shaping of the wire format
        (the multi-host path offsets its local shard instead), whose time
        is the self time of the caller's ``put_group`` span, then the
        ``h2d`` span, the ``device_put`` tree-map. ``h2d`` measures the
        HOST's side of the put — staging and enqueue; the transfer itself
        is the device's and shows in a profiler trace, not here. ``slots``
        are the pool's buffers ``batch`` is made of: the wire format is
        written into the first, and all go back by the rule of
        :func:`_give_back`, one put later (:meth:`_settle`)."""
        slot = slots[0] if slots else None
        if self.mesh is None:
            host = self._compact_for_transfer(batch, slot=slot)
            put = jnp.asarray
        else:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from hydragnn_tpu.parallel.mesh import DATA_AXIS

            if self._batch_sharding is None:
                self._batch_sharding = NamedSharding(self.mesh, P(DATA_AXIS))
                self._stacked_sharding = NamedSharding(
                    self.mesh, P(None, DATA_AXIS)
                )
            sharding = (
                self._stacked_sharding if stacked else self._batch_sharding
            )
            if batch.extras and "nbr_reach" in batch.extras:
                # the collate's locality statement describes ONE array
                # of rows; here they are split over the data axis (a
                # neighbour may lie on another device), so the sharded
                # batch makes none and keeps XLA's gather
                batch = batch.replace(extras={
                    k: v for k, v in batch.extras.items()
                    if k != "nbr_reach"
                })
            if jax.process_count() > 1:
                host = _offset_local_shard(batch, jax.process_index())

                def put(a):
                    return jax.make_array_from_process_local_data(
                        sharding, np.asarray(a)
                    )
            else:
                host = self._compact_for_transfer(
                    batch, allow_pos_placeholder=False, slot=slot
                )

                def put(a):
                    return jax.device_put(jnp.asarray(a), sharding)
        with tr.span("h2d"):
            dev = jax.tree_util.tree_map(put, host)
        if slots:
            # the put before this one has had this one's time to cross:
            # its wait (``h2d_wait``) comes after this enqueue, so the
            # transfers follow each other without the host between them
            self._settle()
            self._in_flight = (slots, host, dev)
        return dev

    def _settle(self):
        """Give the last pooled put's slots back (:func:`_give_back`): at
        the next pooled put, and at the end of the epoch loop, whose put
        stage has finished by then."""
        in_flight, self._in_flight = self._in_flight, None
        if in_flight is not None:
            _give_back(*in_flight)

    # ---- compiled steps ------------------------------------------------
    def _build_steps(self):
        self._steps = build_steps(
            self.model,
            self.tx,
            self.training_config,
            mesh=self.mesh,
            state_shardings=self._state_shardings,
        )

    # ---- device-resident dataset --------------------------------------
    def stage_batches(self, batches) -> GraphBatch:
        """Stack same-shape collated batches and park them in HBM once.

        Returns a device-resident epoch usable with
        :meth:`train_epoch_staged`. Use when the (padded) training set fits
        device memory — it removes host->device transfers from the training
        loop entirely, which otherwise bound small-graph workloads."""
        batches = list(batches)
        obs.emit("staged", num_batches=len(batches))
        return self.put_batch_stacked(stack_batches(batches))

    def train_epoch_staged(self, state, staged, rng, shuffle=True):
        """One epoch over an HBM-staged dataset in a single dispatch.

        Shuffling permutes microbatch ORDER each epoch (sample->batch
        assignment is fixed at staging time — the streaming ``train_epoch``
        path reshuffles samples fully; restage periodically if you want
        that here). Returns the same (state, rng, loss, tasks) contract as
        :meth:`train_epoch`."""
        nb = jax.tree_util.tree_leaves(staged)[0].shape[0]
        cap = os.getenv("HYDRAGNN_MAX_NUM_BATCH")
        n_use = min(nb, int(cap)) if cap is not None else nb
        rng, prng = jax.random.split(rng)
        if shuffle:
            perm = jax.random.permutation(prng, nb)[:n_use]
        else:
            perm = jnp.arange(n_use)
        subs = jax.random.split(rng, n_use + 1)
        rng = subs[0]
        tr.start("train")
        state, metrics = self._epoch_scan(state, staged, perm, subs[1:])
        g = np.asarray(metrics["num_graphs"], np.float64)
        tot = float(np.asarray(metrics["loss"], np.float64) @ g)
        tasks = (np.asarray(metrics["tasks"], np.float64) * g[:, None]).sum(0)
        tr.stop("train")
        # the staged epoch is ONE dispatch with no per-step hook: trace
        # capture (/profile, HYDRAGNN_PROFILE_AT_STEP) ticks per epoch
        obs.dispatch_boundary()
        n = max(float(g.sum()), 1.0)
        return state, rng, tot / n, tasks / n

    def evaluate_staged(self, state, staged):
        """Whole eval set in one dispatch over an HBM-staged stack — the
        staged counterpart of :meth:`evaluate` (same averaged metrics)."""
        loss, tasks = self._eval_epoch(state.params, state.batch_stats, staged)
        return float(np.asarray(loss)), np.asarray(tasks, np.float64)

    def fit_staged(
        self,
        state,
        staged_train,
        num_epoch: int,
        rng,
        staged_val=None,
        staged_test=None,
        shuffle: bool = True,
        sched: Optional[SchedState] = None,
        best_state: Optional[TrainState] = None,
        pad_to: Optional[int] = None,
    ):
        """Run ``num_epoch`` training epochs as ONE device dispatch.

        Everything the reference's epoch driver does per epoch —
        ReduceLROnPlateau on the val loss, EarlyStopping, best-val state
        tracking (the ``Checkpoint`` analog), val+test evaluation — runs on
        device inside a single ``lax.scan`` over epochs; the metric series
        comes back as one packed array, i.e. ONE host readback per call.
        Call it in chunks (e.g. 10 epochs at a time) when host-side
        per-epoch actions are needed (TensorBoard, SLURM wall-clock guard):
        ``sched``/``best_state`` carry across calls. ``pad_to`` pads the
        scan length so a shorter final chunk reuses the compiled program
        (padded epochs are inert and trimmed from the returned series).

        Returns ``(state, best_state, sched, rng, series)`` where ``rng`` is
        the advanced key and ``series`` is a dict of numpy arrays over
        epochs: ``train_loss``, ``val_loss``, ``test_loss``, ``lr``,
        ``stopped``, ``train_tasks [E, T]`` — NaN rows mark epochs skipped
        after early stop fired.
        """
        nb = jax.tree_util.tree_leaves(staged_train)[0].shape[0]
        cap = os.getenv("HYDRAGNN_MAX_NUM_BATCH")
        n_use = min(nb, int(cap)) if cap is not None else nb
        n_sched = max(num_epoch, pad_to or 0)
        rng, prng = jax.random.split(rng)
        if shuffle:
            perms = jax.vmap(
                lambda k: jax.random.permutation(k, nb)[:n_use]
            )(jax.random.split(prng, n_sched))
        else:
            perms = jnp.tile(jnp.arange(n_use), (n_sched, 1))
        subs = jax.random.split(rng, n_sched * n_use + 1)
        rng = subs[0]
        erngs = subs[1:].reshape(n_sched, n_use, -1)
        active = jnp.arange(n_sched) < num_epoch
        if sched is None:
            sched = SchedState.init()
            if self.mesh is not None:
                sched = jax.tree_util.tree_map(jnp.asarray, sched)
        if best_state is None:
            # explicit copy: ``state`` is donated, the snapshot must not
            # alias its buffers. One jitted dispatch — eager per-leaf copies
            # would cost ~a hundred dispatches on high-latency backends.
            best_state = _copy_tree(state)
        tr.start("train")
        state, best_state, sched, series = self._fit_scan(
            state, best_state, sched, staged_train, staged_val,
            staged_test, perms, erngs, active,
        )
        series = np.asarray(series)[:num_epoch]  # the single readback
        tr.stop("train")
        out = {
            "train_loss": series[:, 0],
            "val_loss": series[:, 1],
            "test_loss": series[:, 2],
            "lr": series[:, 3],
            "stopped": series[:, 4] > 0.5,
            "train_tasks": series[:, 5:],
        }
        return state, best_state, sched, rng, out

    # ---- epoch loops ---------------------------------------------------
    @staticmethod
    def _acc_add(acc, metrics, multi):
        """Collect per-batch epoch metrics WITHOUT a host readback: each
        batch appends one packed [loss_sum, graph_count, task_sums...]
        device vector to ``acc`` — per-batch ``float(...)`` fetches cost a
        full round trip each on TPU backends AND serialize the dispatch
        pipeline. :meth:`_acc_read` stacks the parts, does the epoch's ONE
        readback, and sums in float64 on the host (exact, unlike a
        sequential on-device f32 running sum).

        Multi-host: eager jnp ops on jit outputs spanning non-addressable
        devices are disallowed — fall back to the (permitted) per-batch
        host fetch of the replicated scalars, as before this optimization.
        """
        g32 = metrics["num_graphs"]
        if jax.process_count() > 1:
            g = np.asarray(g32, np.float64)
            t = np.asarray(metrics["tasks"], np.float64)
            loss = np.asarray(metrics["loss"], np.float64)
            if multi:
                part = np.concatenate([[loss @ g], [g.sum()], t.T @ g])
            else:
                part = np.concatenate([[loss * g], [g], t * g])
        else:
            g32 = g32.astype(jnp.float32)
            t = metrics["tasks"].astype(jnp.float32)
            if multi:  # stacked [K] / [K, T] from a scan
                part = jnp.concatenate(
                    [(metrics["loss"] @ g32)[None], g32.sum()[None], t.T @ g32]
                )
            else:
                part = jnp.concatenate(
                    [(metrics["loss"] * g32)[None], g32[None], t * g32]
                )
        acc = [] if acc is None else acc
        acc.append(part)
        return acc

    @staticmethod
    def _acc_read(acc):
        """(avg_loss, per-task avg): one readback, float64 host summation."""
        if not acc:
            return 0.0, np.zeros(0)
        if isinstance(acc[0], np.ndarray):
            a = np.stack(acc).astype(np.float64).sum(axis=0)
        else:
            # the epoch's single readback — EXPLICIT device_get, so the
            # transfer-guard harness (analysis/guards.py no_host_syncs)
            # can hard-error every implicit fetch in the epoch loop while
            # this one sanctioned transfer passes.
            # ``drain``: until the stacked accumulators are ready on the
            # device: the
            # stack's dispatches, which overlap the steps still queued, and
            # the wait for that queue; the transfer and the summation after
            # it are the host's own. No synchronisation added (the
            # device_get waits for the same array) and nothing serialised:
            # waiting for the parts BEFORE stacking them put the stack's
            # 3 ms behind the queue, +3 ms a readback in every cell
            # (PERF.md section 6, PR 34)
            with tr.span("drain", dispatches=len(acc)):
                stacked = jax.block_until_ready(jnp.stack(acc))
            a = np.asarray(jax.device_get(stacked), np.float64).sum(axis=0)
        n = max(a[1], 1.0)
        return a[0] / n, a[2:] / n

    def _prefetch_put(self, loader, nbatch, depth, put=None,
                      ledger_waits=True, opened=None):
        """Yield device-resident batches with up to ``depth`` transfers in
        flight ahead of the consumer. The transfers are issued from a
        background thread (shared :func:`prefetch_iter` machinery): both
        halves of a put's cost — the host-side compaction/assembly (numpy,
        releases the GIL) and the H2D copy — overlap the steps already
        dispatched on earlier batches.
        ``depth <= 0`` degrades to the strict transfer/step alternation.
        ``opened``, when given, is called once, where the stage's start-up
        ends and the wait for the first batch begins (the transfer thread
        started; with ``depth <= 0`` before the loader is first asked)."""
        put = put or self.put_batch
        # goodput ledger (obs/ledger.py): the wall the consumer spends
        # waiting on the data plane is the data_stall category — resolved
        # once per epoch like the trainer's step hook. Callers whose
        # source loader reports its OWN stalls (StreamLoader via
        # stream_epoch_stats) pass ledger_waits=False so the same starved
        # seconds are not attributed twice.
        _telemetry = obs.active() if ledger_waits else None
        _ledger = _telemetry.ledger if _telemetry is not None else None

        def limited():
            for ibatch, batch in enumerate(loader):
                if ibatch >= nbatch:
                    break
                yield batch

        # the ``dataload`` span's clock is the ledger's: one reading
        if depth <= 0:
            if opened is not None:
                opened()
            for batch in limited():
                wait = tr.start("dataload")
                dev = put(batch)
                wait.stop()
                if _ledger is not None:
                    _ledger.data_wait(wait.seconds)
                yield dev
            return
        from hydragnn_tpu.data.loaders import prefetch_iter

        found = [0]  # ready items ahead of the consumer at its last get

        def note_depth(n):
            found[0] = n

        it = prefetch_iter(
            limited(), depth, fn=put, name="hydragnn-device-prefetch",
            probe=note_depth, primed=True,
        )
        next(it)  # the transfer thread is started; nothing waited for yet
        if opened is not None:
            opened()
        while True:
            wait = tr.start("dataload")  # time spent WAITING on the transfer stage
            try:
                try:
                    item = next(it)
                except StopIteration:
                    return
            finally:
                # a worker-side error re-raised by next(it) must not leave
                # the dataload span open for the rest of the process
                wait.stop(queue_depth=found[0])
                if _ledger is not None:
                    _ledger.data_wait(wait.seconds)
            yield item

    @staticmethod
    def _group_plan(loader, nbatch, K):
        """Host-side dispatch plan: yield ``K``-long shape-uniform groups
        (the multi-step scan path) and single batches (everything else).
        Only FULL K-groups take the scan — a partial group would compile a
        fresh scan program per novel length (bucketed layouts hit this at
        every segment boundary) — so partial groups stream through the
        single-step program.

        A loader that states its epoch's shapes before collating anything
        (``GraphLoader.batch_keys``) says which batches end a run short of
        ``K``: those are handed on the moment they arrive, and a batch of
        a full group is laid into the group's stacked arrays on arrival
        (``_Group.stacked``), so the transfer stage never sits on a
        finished batch. Without the statement (a list, ``StreamLoader``) a
        run's end is found by looking ahead: its partial group is held
        until the next shape or the loader's end shows it, and a full one
        is stacked at the put. The dispatches are the same."""

        def _shape_key(b):
            # ALL leaf shapes (incl. extras: triplet tables, neighbor
            # lists) — two buckets can share node/edge/graph pads while
            # their t_pad or k widths differ, and those must not stack
            return tuple(
                tuple(a.shape) for a in jax.tree_util.tree_leaves(b)
            )

        # asked BEFORE the loader's first batch: its collate thread then
        # finds the epoch's plan cached
        alone = _goes_alone(loader, nbatch, K)
        pending = _Group()
        for ibatch, (batch, slot) in enumerate(_pooled(loader)):
            if ibatch >= nbatch:
                break
            if K == 1 or (
                alone is not None and ibatch in alone and not pending
            ):
                yield _single(batch, slot)
                continue
            # bucketed layouts interleave batch shapes; a stack group must
            # be shape-uniform, so a shape change flushes the open group
            if pending and _shape_key(batch) != _shape_key(pending[0]):
                yield from pending.singles()
                pending = _Group()
            pending.append(batch)
            if alone is None:
                pending.slots += () if slot is None else (slot,)
            else:
                # the plan says this run fills a group: the batch goes
                # into the group's stacked arrays now, beside the collate
                # of the next one, and not all K at the last one's arrival
                index = len(pending) - 1
                with tr.span("stack_batch", index=index) as span:
                    if index == 0 and slot is not None:
                        pending.slots = (
                            slot.pool.acquire(("stack", K) + slot.key),
                        )
                    into = pending.slots[0] if pending.slots else None
                    pending.stacked = stack_into(
                        pending.stacked, batch, index, K, slot=into
                    )
                    span.set(slot="fresh" if into is None else into.state)
                if slot is not None:
                    # the batch lives in the group's arrays from here on,
                    # and its own slot is the next collate's
                    pending[-1] = jax.tree_util.tree_map(
                        lambda a: a[index], pending.stacked
                    )
                    slot.release()
            if len(pending) == K:
                yield pending
                pending = _Group()
        # trailing partial group: single-step path
        yield from pending.singles()

    def _put_group(self, group):
        """Transfer stage: a group becomes (device_payload, count). Runs on
        the prefetch thread when ``device_prefetch > 0`` — so stacked
        multi-step transfers double-buffer exactly like single batches —
        behind the loader's collate thread (``GraphLoader.__iter__``): the
        producer's pace is its slower stage, not their sum."""
        # collate_open: whether the loader's thread was collating when this
        # put began (the two stages overlap); its share over a window is the
        # pipeline's overlap share
        slots = getattr(group, "slots", ())
        with tr.span(
            "put_group", batches=len(group),
            collate_open=tr.open_elsewhere("collate"),
            slot=slots[0].state if slots else "fresh",
        ) as span:
            if len(group) > 1:
                stacked = getattr(group, "stacked", None)
                if stacked is None:
                    with tr.span("stack_batches"):
                        stacked = stack_batches(group)
                    # the batches have been read; the stack is fresh
                    for slot in slots:
                        slot.release()
                    slots = ()
                    span.set(slot="fresh")
                dev = self._put(stacked, stacked=True, slots=slots)
            else:
                dev = self._put(group[0], stacked=False, slots=slots)
            # what device_put was handed: the arrays keep dtype and shape
            span.set(bytes=sum(
                int(a.nbytes) for a in jax.tree_util.tree_leaves(dev)
            ))
        return dev, len(group)

    def train_epoch(self, state, loader, rng):
        from hydragnn_tpu.train import elastic
        from hydragnn_tpu.utils import faults

        acc = None
        nbatch = _nbatch(loader)
        guard = self.guard
        # the guard must isolate ONE step to skip it; stacked multi-step
        # dispatches apply K updates atomically, so guarded runs stream
        K = 1 if guard is not None else max(1, self.steps_per_dispatch)
        if guard is not None and guard.last_good is None:
            guard.commit(state)
        tr.start("train")
        # the epoch's start-up on this thread, closed by ``_prefetch_put``
        # where the wait for the first batch begins (the first ``dataload``)
        opening = tr.start("epoch_open", prefetch=self.device_prefetch)
        # resolved once per epoch: the per-step telemetry hooks must cost
        # one global read when observability is off
        _telemetry = obs.active()
        pool_before = _pool_counts(loader)
        plan = self._group_plan(loader, nbatch, K)
        batches = groups = 0
        for dev, count in self._prefetch_put(
            plan, float("inf"), self.device_prefetch, put=self._put_group,
            ledger_waits=not getattr(loader, "reports_stream_stats", False),
            opened=opening.stop,
        ):
            batches += count
            groups += 1
            if count > 1:
                # eager dispatches on the loop thread: each one gives the
                # GIL up and waits to get it back from a producer thread
                with tr.span("split_rng", steps=count):
                    subs = jax.random.split(rng, count + 1)
                    rng = subs[0]
                step = tr.start(
                    "train_step", steps=count, program="train_multi",
                    bucket=dev.x.shape[-2],
                )
                # straggler injection INSIDE the span: the delay must
                # reach on_step -> flight recorder, or the stall
                # detection the fault exists to exercise never sees it.
                # Every step id the K-group covers gets its check, same
                # as the kill loop below.
                for s in range(self._host_step, self._host_step + count):
                    faults.slow_step(s)
                state, metrics = self._train_multi(state, dev, subs[1:])
                step.stop()
                if _telemetry is not None:
                    # the full per-step hook: metrics + flight recorder
                    # (stall alerts) + on-demand trace-capture ticks; the
                    # span's seconds are the dispatch's
                    _telemetry.on_step(step.seconds, count)
                # its eager ops read the step's outputs: on the chip they
                # wait for the dispatch, so this is where a host that runs
                # ahead of the device is held (PERF.md section 5)
                with tr.span("acc_add"):
                    acc = self._acc_add(acc, metrics, multi=True)
                first = self._host_step
                self._host_step += count
                elastic.note_step(self._host_step)
                for s in range(first, self._host_step):
                    faults.kill_at_step(s)
                    faults.lose_host_at_step(s)
            else:
                if faults.nan_at_step(self._host_step):
                    dev = dev.replace(x=dev.x * jnp.nan)
                prev = None if guard is None else guard.snapshot(state)
                with tr.span("split_rng", steps=1):
                    rng, sub = jax.random.split(rng)
                step = tr.start(
                    "train_step", steps=1, program="train_step",
                    bucket=dev.x.shape[-2],
                )
                # inside the span — see the multi-step branch
                faults.slow_step(self._host_step)
                state, metrics = self._train_step(state, dev, sub)
                step.stop()
                if _telemetry is not None:
                    _telemetry.on_step(step.seconds)
                # the guard's documented cost: ONE scalar fetch per step to
                # learn whether the update was finite — opt-in, and there is
                # no async way to branch host control flow on a device value
                if guard is not None and not bool(
                    np.asarray(metrics["finite"])  # jaxlint: disable=host-sync-in-hot-loop
                ):
                    # poisoned update: discard it (or restore last-good
                    # with halved LR after a streak) and keep the batch's
                    # metrics out of the epoch average
                    state = guard.on_bad_step(prev)
                else:
                    if guard is not None:
                        guard.bad_streak = 0
                    with tr.span("acc_add"):
                        acc = self._acc_add(acc, metrics, multi=False)
                faults.kill_at_step(self._host_step)
                faults.lose_host_at_step(self._host_step)
                self._host_step += 1
                elastic.note_step(self._host_step)
        opening.set(batches=batches, groups=groups)
        with tr.span("settle", waited=self._in_flight is not None):
            self._settle()
        with tr.span("epoch_readback", dispatches=len(acc or ())):
            loss, tasks = self._acc_read(acc)  # the epoch's one readback
        tr.stop("train")
        if _telemetry is not None and pool_before is not None:
            # how often the epoch's host buffers were the pool's own
            now = _pool_counts(loader)
            _telemetry.emit(
                "pool",
                reused=now["reused"] - pool_before["reused"],
                made=now["made"] - pool_before["made"],
                bytes=now["bytes"],
            )
        return state, rng, loss, tasks

    def evaluate(self, state, loader, desc="validate"):
        """Streaming eval with the SAME multi-step dispatch as training:
        ``steps_per_dispatch`` same-shape batches stack into one scan
        program (at-scale QM9, per-batch eval dispatches cost as much
        wall as the whole stacked train epoch)."""
        acc = None
        nbatch = _nbatch(loader)
        K = max(1, self.steps_per_dispatch)
        plan = self._group_plan(loader, nbatch, K)
        for dev, count in self._prefetch_put(
            plan, float("inf"), self.device_prefetch, put=self._put_group,
            ledger_waits=not getattr(loader, "reports_stream_stats", False),
        ):
            if count > 1:
                metrics = self._eval_multi(
                    state.params, state.batch_stats, dev
                )
                acc = self._acc_add(acc, metrics, multi=True)
            else:
                metrics = self._eval_step(
                    state.params, state.batch_stats, dev
                )
                acc = self._acc_add(acc, metrics, multi=False)
        with tr.span("settle", waited=self._in_flight is not None):
            self._settle()
        with tr.span("epoch_readback", dispatches=len(acc or ())):
            return self._acc_read(acc)
