"""Run-scoped telemetry: the glue between training code and obs primitives.

One :class:`RunTelemetry` per training run bundles the three tentpole
pieces — the structured event stream (``events.jsonl``), the live
``/metrics``+``/healthz`` endpoint, and the training metrics registry —
behind module-level hook functions (:func:`emit`, :func:`epoch_complete`,
:func:`guard_skip`, ...) that the epoch driver, trainer, divergence
guard, and checkpoint layer call unconditionally. (The trainer's per-step
path resolves :func:`active` once per epoch and calls
``metrics.on_step`` directly — one global read per epoch, not per step.)

The hooks follow the fault-injection harness pattern
(``utils/faults.py``): with no active telemetry each call is ONE global
read and a return, so instrumented code costs nothing when observability
is off — the acceptance bar is "telemetry-disabled epoch-loop wall time
within noise of baseline", enforced by ``tests/test_observability.py``.

Enablement (rank 0 only; other ranks keep the no-op hooks):

- events + metrics: on by default for driver runs; ``HYDRAGNN_TELEMETRY=0``
  or ``config["Telemetry"]["enable"] = false`` disables.
- HTTP endpoint: opt-in — ``HYDRAGNN_OBS_PORT=<port>`` (0 = ephemeral)
  or ``config["Telemetry"]["port"]``.
"""

import hashlib
import json
import os
import time
from typing import Dict, List, Optional

from hydragnn_tpu.obs.events import SCHEMA_VERSION, RunEventLog
from hydragnn_tpu.obs.metrics import (
    DEFAULT_LATENCY_BOUNDS,
    EPOCH_LATENCY_BOUNDS,
    MetricsRegistry,
)
from hydragnn_tpu.utils import tracer as _tracer

_active: Optional["RunTelemetry"] = None


class FlightRecorder:
    """Ring buffer of the last K step-dispatch times + stall detection.

    A step counts as a STALL when its dispatch time strictly exceeds
    ``stall_factor`` x the rolling median of the buffered window (median,
    not mean — one earlier stall must not drag the threshold up). No
    stall can fire until ``min_fill`` steps are buffered, so warmup and
    first-epoch compile steps never alert; the caller additionally skips
    recording steps that contained an XLA compile (their wall time IS
    compile time). Not thread-safe by design — one training thread owns
    it; ``snapshot()`` from other threads reads a consistent-enough copy
    for diagnostics.
    """

    def __init__(self, capacity: int = 64, stall_factor: float = 8.0,
                 min_fill: int = 8):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = int(capacity)
        self.stall_factor = float(stall_factor)
        # clamped into [1, capacity]: a window smaller than min_fill
        # could otherwise never satisfy the fill gate, silently disabling
        # detection for the operator who SHRANK it to react faster
        self.min_fill = max(min(int(min_fill), self.capacity), 1)
        self._buf: List[float] = [0.0] * self.capacity
        self._count = 0  # total steps ever recorded

    def record(self, seconds: float) -> Optional[Dict]:
        """Add one step time; returns the stall payload (step/seconds/
        median/factor) when the step stalled, else None. The check runs
        against the window BEFORE this step enters it — a stalled step is
        judged by its predecessors, then buffered so a genuine regime
        change re-baselines the median within a window."""
        stall = None
        filled = min(self._count, self.capacity)
        if filled >= self.min_fill:
            window = sorted(self._buf[:filled] if self._count < self.capacity
                            else self._buf)
            mid = filled // 2
            median = (
                window[mid]
                if filled % 2
                else 0.5 * (window[mid - 1] + window[mid])
            )
            if seconds > self.stall_factor * median:
                stall = {
                    "step": self._count,
                    "seconds": seconds,
                    "median": median,
                    "factor": self.stall_factor,
                }
        self._buf[self._count % self.capacity] = float(seconds)
        self._count += 1
        return stall

    @property
    def count(self) -> int:
        return self._count

    def snapshot(self) -> List[float]:
        """Buffered step times, oldest first."""
        if self._count < self.capacity:
            return self._buf[: self._count]
        i = self._count % self.capacity
        return self._buf[i:] + self._buf[:i]


class TrainingMetrics:
    """The training run's live series — everything ``/metrics`` reports.

    Built on the shared :class:`MetricsRegistry` core; serving's
    ``ServeMetrics`` is the other client of the same machinery."""

    def __init__(self):
        # scrape-time poll hooks (device memory is built in; the elastic
        # fleet poll registers here) — run on every render, never in the
        # step loop
        self.extra_polls = []
        r = MetricsRegistry("hydragnn_train")
        r.counter("epochs_total", "Completed epochs")
        r.counter("steps_total", "Dispatched optimizer steps")
        r.counter("guard_skips_total", "Non-finite steps/epochs skipped")
        r.counter("guard_restores_total", "Last-good restores (halved LR)")
        r.counter("checkpoints_saved_total", "Checkpoint files written")
        r.counter("compiles_total", "XLA compilations observed")
        r.gauge("epoch", "Current epoch index")
        r.gauge("train_loss", "Last epoch training loss")
        r.gauge("val_loss", "Last epoch validation loss")
        r.gauge("test_loss", "Last epoch test loss")
        r.gauge("graphs_per_second", "Last epoch training throughput")
        r.gauge("nodes_per_second", "Last epoch real-node-row throughput")
        r.gauge(
            "padding_waste_ratio",
            "Padded node rows carrying no real node (training batches)",
        )
        r.gauge(
            "heartbeat_age_seconds",
            "Seconds since the training loop last reported progress",
        )
        r.counter("stalls_total", "Steps exceeding the stall threshold")
        # elastic training (train/elastic.py): current world size and the
        # last re-mesh's detection->first-step recovery time
        r.gauge("world_size", "Processes in the current training world")
        r.gauge(
            "last_recovery_seconds",
            "Detection-to-first-step time of the last world resize",
        )
        # compiled-program accounting (obs/introspect.py): one label set
        # per (program, shape-signature) bucket
        r.labeled_gauge(
            "flops_per_step", "Compiled-program FLOPs (XLA cost model)"
        )
        r.labeled_gauge(
            "hbm_peak_bytes",
            "Compiled-program peak memory (arg+out+temp-aliased)",
        )
        # aggregation family (ops/agg_policy.py): 1 on the (bucket,
        # choice) label set each bucket actually uses
        r.labeled_gauge(
            "aggregation_kernel",
            "Chosen aggregation kernel family per bucket (1 = active)",
        )
        # 2-D mesh collective accounting (parallel/collectives.py):
        # per-dispatch collective result bytes attributed to each mesh
        # axis, summed over every captured compiled program — a reshard
        # regression (all-gather storm) moves this before it moves wall
        r.labeled_gauge(
            "collective_bytes",
            "Compiled-program collective result bytes per mesh axis",
        )
        # streaming data plane (data/stream/): per-epoch pipeline health
        # — queue depth at last consumer get, seconds the step loop spent
        # blocked on the data plane, ingestion bandwidth, and the
        # shard-window residency high-waters the RAM bound rests on
        r.gauge("stream_queue_depth", "Collated batches ready ahead of the consumer")
        r.gauge(
            "stream_stall_seconds",
            "Seconds the consumer waited on the stream pipeline last epoch",
        )
        r.gauge("stream_bytes_per_second", "Streamed sample bytes/sec last epoch")
        r.gauge(
            "stream_open_shards_peak",
            "Most shards any source held resident at once",
        )
        r.gauge(
            "stream_resident_bytes_peak",
            "Peak host bytes pinned by stream window buffers",
        )
        r.counter("stream_samples_total", "Samples drawn from the stream mix")
        r.counter(
            "stream_oversize_dropped_total",
            "Samples dropped because no bucket of the plan could hold them",
        )
        r.labeled_gauge(
            "stream_source_fraction",
            "Fraction of last epoch's draws per mix source",
        )
        # goodput & MFU ledger (obs/ledger.py): per-category wall-time
        # fractions of the last closed epoch window (sum to 1), and
        # per-bucket model FLOPs utilization against the device's peak
        r.labeled_gauge(
            "goodput_fraction",
            "Last epoch's wall-time fraction per goodput category",
        )
        r.labeled_gauge(
            "mfu",
            "Model FLOPs utilization per train bucket (vs device peak)",
        )
        # fleet view (elastic runs; the leader polls peer heartbeat
        # digests at scrape time — obs/ledger.py poll_fleet_gauges)
        r.labeled_gauge(
            "fleet_step_p50_seconds",
            "Per-host step-time p50 from elastic heartbeat digests",
        )
        r.gauge(
            "fleet_straggler_hosts",
            "Hosts whose step p50 exceeds the fleet median threshold",
        )
        # live device memory, polled from device 0's memory_stats() at
        # scrape time (stays 0 on backends that report none, e.g. CPU)
        r.gauge("device_bytes_in_use", "Live device memory in use")
        r.gauge(
            "device_peak_bytes_in_use", "Peak device memory since start"
        )
        r.histogram(
            "epoch_seconds", "Epoch wall time", bounds=EPOCH_LATENCY_BOUNDS
        )
        r.histogram(
            "step_dispatch_seconds",
            "Host-side train-step dispatch latency",
            bounds=DEFAULT_LATENCY_BOUNDS,
        )
        self.registry = r
        self.last_beat = time.time()

    def beat(self):
        self.last_beat = time.time()

    def on_step(self, seconds: float, count: int = 1):
        self.registry.inc("steps_total", count)
        self.registry.observe("step_dispatch_seconds", seconds)
        # steps ARE progress: without this, heartbeat_age grows for the
        # whole of a long epoch and stall alerts fire on healthy runs
        self.last_beat = time.time()

    def on_epoch(
        self,
        epoch: int,
        train_loss: float,
        val_loss: float,
        test_loss: float,
        seconds: Optional[float] = None,
        graphs_per_sec: Optional[float] = None,
        nodes_per_sec: Optional[float] = None,
        padding_waste: Optional[float] = None,
    ):
        r = self.registry
        r.inc("epochs_total")
        r.set("epoch", float(epoch))
        r.set("train_loss", float(train_loss))
        r.set("val_loss", float(val_loss))
        r.set("test_loss", float(test_loss))
        if seconds is not None:
            r.observe("epoch_seconds", seconds)
        if graphs_per_sec is not None:
            r.set("graphs_per_second", float(graphs_per_sec))
        if nodes_per_sec is not None:
            r.set("nodes_per_second", float(nodes_per_sec))
        if padding_waste is not None:
            r.set("padding_waste_ratio", float(padding_waste))
        self.beat()

    def poll_device_memory(self):
        """Refresh the live-memory gauges from device 0 (the heartbeat's
        companion poll — runs at scrape time, never in the step loop)."""
        try:
            import jax

            stats = jax.local_devices()[0].memory_stats()
        except Exception:
            return
        if not stats:
            return
        self.registry.set(
            "device_bytes_in_use", float(stats.get("bytes_in_use", 0))
        )
        self.registry.set(
            "device_peak_bytes_in_use",
            float(stats.get("peak_bytes_in_use", 0)),
        )

    def render_prometheus(self) -> str:
        self.registry.set(
            "heartbeat_age_seconds", max(time.time() - self.last_beat, 0.0)
        )
        self.poll_device_memory()
        for poll in self.extra_polls:
            try:
                poll()
            except Exception:
                pass  # a poll hook must never break /metrics
        return self.registry.render_prometheus()

    def snapshot(self) -> Dict:
        return self.registry.snapshot()


_compile_listener_registered = False
# jax.monitoring duration events that are part of getting a program ready
_COMPILE_FAMILY = (
    "/jax/core/compile/",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)
# process-global backend-compile count: always bumped once the listener is
# installed, whether or not a telemetry run is active. The recompile
# sentinel (analysis/guards.py) diffs it around a warmed-up region.
_compile_events = 0
# ... and the matching duration integral: total backend-compile seconds,
# the goodput ledger's `compile` category signal (obs/ledger.py)
_compile_seconds = 0.0


def _register_compile_listener():
    """Count XLA compilations via jax's monitoring events when the API is
    available (it is internal-ish; absence just leaves the counter at 0).
    ONE process-global listener routing to whatever telemetry is active —
    jax has no unregister API, so a per-run listener would leak a closure
    (and retain its metrics) for every run in a long-lived process."""
    global _compile_listener_registered
    if _compile_listener_registered:
        return
    try:
        from jax import monitoring

        def _on_duration(event: str, duration: float = 0.0, **kwargs):
            # every duration of the compile family (jaxpr trace, lowering,
            # backend compile, persistent-cache load) is a ``compile`` span
            # of the recorder, under whatever span is open on the thread
            # that compiled; the counters below stay backend compiles only.
            # '/jax/core/compile/backend_compile_duration' fires once per
            # program the backend is asked for: jax 0.9.0 sends it around
            # compile_or_get_cached, so a persistent-cache hit counts too,
            # with its 'cache_retrieval_time_sec' inside the duration
            global _compile_events, _compile_seconds
            if event.startswith(_COMPILE_FAMILY):
                _tracer.record(
                    "compile", duration, event=event.rsplit("/", 1)[-1],
                    seconds=float(duration), fun=kwargs.get("fun_name"),
                )
            if "backend_compile" in event:
                _compile_events += 1
                try:
                    _compile_seconds += float(duration)
                except (TypeError, ValueError):
                    pass
                t = _active
                if t is not None:
                    t.metrics.registry.inc("compiles_total")

        if hasattr(monitoring, "register_event_duration_secs_listener"):
            monitoring.register_event_duration_secs_listener(_on_duration)
            _compile_listener_registered = True
    except Exception:
        pass


def install_compile_listener() -> bool:
    """Public idempotent installer (the sentinel's entry point). Returns
    whether the listener is live — False means the monitoring API is
    unavailable and :func:`compile_events` will stay at 0."""
    _register_compile_listener()
    return _compile_listener_registered


def compile_events() -> int:
    """Backend compilations observed since the listener was installed."""
    return _compile_events


def compile_seconds() -> float:
    """Cumulative backend-compile wall seconds (0.0 when the monitoring
    API is unavailable — the ledger's compile category then reads 0)."""
    return _compile_seconds


def _config_hash(config: dict) -> str:
    try:
        blob = json.dumps(config, sort_keys=True, default=str)
    except (TypeError, ValueError):
        blob = repr(config)
    return hashlib.sha256(blob.encode()).hexdigest()[:12]


def _git_rev() -> str:
    try:
        import subprocess

        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True,
            text=True,
            timeout=5,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        return out.stdout.strip() or "unknown"
    except Exception:
        return "unknown"


class RunTelemetry:
    """Everything observable about one training run, under one lifetime.

    Satisfies the :class:`~hydragnn_tpu.obs.http.ObservabilityServer`
    provider protocol (``health()`` + ``.metrics.render_prometheus()``),
    so the serving listener exposes a live training job unchanged."""

    def __init__(
        self,
        run_name: str,
        log_dir: str,
        port: Optional[int] = None,
        events: bool = True,
        events_file: str = "events.jsonl",
    ):
        from hydragnn_tpu.obs.introspect import (
            TraceCapture,
            parse_profile_at_step,
        )
        from hydragnn_tpu.obs.ledger import GoodputLedger, poll_fleet_gauges

        self.run_name = run_name
        self.log_dir = log_dir
        self.metrics = TrainingMetrics()
        self.events: Optional[RunEventLog] = (
            RunEventLog(os.path.join(log_dir, events_file))
            if events
            else None
        )
        self.server = None
        self._closed = False
        # step-time flight recorder + on-demand trace capture — both
        # driven from on_step() on the training thread
        self.flight = FlightRecorder(
            capacity=int(os.getenv("HYDRAGNN_FLIGHT_STEPS", "64")),
            stall_factor=float(os.getenv("HYDRAGNN_STALL_FACTOR", "8.0")),
        )
        self.trace = TraceCapture(os.path.join(log_dir, "profile"))
        self._profile_at = parse_profile_at_step(
            os.getenv("HYDRAGNN_PROFILE_AT_STEP")
        )
        self._profile_steps = int(os.getenv("HYDRAGNN_PROFILE_STEPS", "3"))
        self.current_epoch = 0
        self._step_in_epoch = 0
        # per-axis collective-bytes running totals (record_compile)
        self._collective_totals: Dict[str, float] = {}
        self._compile_events_at_step = _compile_events
        self._compile_seconds_at_step = _compile_seconds
        # goodput & MFU ledger: per-epoch wall-time attribution + the
        # hydragnn_train_mfu{bucket=} gauges (obs/ledger.py)
        self.ledger = GoodputLedger(
            registry=self.metrics.registry,
            emit=self.emit,
            compile_seconds=compile_seconds,
        )
        # elastic runs: the leader's /metrics scrape also polls the peer
        # heartbeat digests into the fleet gauges
        coord_dir = os.getenv("HYDRAGNN_ELASTIC_DIR")
        if coord_dir:
            self.metrics.extra_polls.append(
                lambda: poll_fleet_gauges(
                    coord_dir, self.metrics.registry
                )
            )
        _register_compile_listener()
        if port is not None:
            from hydragnn_tpu.obs.http import ObservabilityServer

            self.server = ObservabilityServer(self, port=port).start()

    # ---- provider protocol ---------------------------------------------
    def health(self) -> Dict:
        s = self.metrics.snapshot()
        return {
            "status": "ok" if not self._closed else "stopped",
            "run": self.run_name,
            "epoch": int(s["epoch"]),
            "epochs_total": s["epochs_total"],
            "heartbeat_age_s": round(
                max(time.time() - self.metrics.last_beat, 0.0), 3
            ),
        }

    @property
    def address(self):
        return None if self.server is None else self.server.address

    # ---- per-step instrumentation --------------------------------------
    def on_step(self, seconds: float, count: int = 1):
        """One training-step dispatch completed: metrics, flight
        recorder / stall detection, trace-capture tick, env-armed
        profiling. Called from the training thread only."""
        self.metrics.on_step(seconds, count)
        # a step whose dispatch included an XLA compile is compile time,
        # not a stall — keep it out of the ring so it neither alerts nor
        # skews the rolling median (warmup is additionally covered by the
        # recorder's min_fill). Without compile visibility (no
        # jax.monitoring listener on this jax version) stalls are
        # recorded but never ALERTED: a guaranteed false alarm on every
        # mid-run novel-bucket compile is worse than no alarm.
        compiled_now = _compile_events != self._compile_events_at_step
        self._compile_events_at_step = _compile_events
        compile_delta = _compile_seconds - self._compile_seconds_at_step
        self._compile_seconds_at_step = _compile_seconds
        # goodput attribution + the elastic heartbeat's step-time digest
        # (the digest skips compile-heavy steps the same way the flight
        # recorder does — a 3-step host must not read as a straggler
        # because its first step compiled)
        self.ledger.on_step(
            seconds, count, compile_delta if compiled_now else 0.0
        )
        from hydragnn_tpu.train import elastic as _elastic

        _elastic.note_step_time(seconds, count, compiled=compiled_now)
        if not compiled_now:
            # per-step time: K-step scan dispatches must compare against
            # single-step dispatches on the same scale, or bucketed runs
            # mixing the two alert on every full group
            stall = self.flight.record(seconds / max(int(count), 1))
            if stall is not None and _compile_listener_registered:
                self.metrics.registry.inc("stalls_total")
                self.emit(
                    "stall",
                    step=int(stall["step"]),
                    seconds=round(float(stall["seconds"]), 6),
                    median=round(float(stall["median"]), 6),
                    factor=float(stall["factor"]),
                    epoch=int(self.current_epoch),
                )
        self._step_in_epoch += count
        if (
            self._profile_at is not None
            and self.current_epoch == self._profile_at[0]
            and self._step_in_epoch >= self._profile_at[1]
        ):
            self._profile_at = None
            self.profile(self._profile_steps)
        transition = self.trace.tick()
        if transition is not None:
            self.emit("profile", **transition)

    def on_epoch_start(self, epoch: int):
        self.current_epoch = int(epoch)
        self._step_in_epoch = 0
        # closes (and publishes) the previous goodput window — post-epoch
        # work like the resumable checkpoint save lands in ITS epoch
        self.ledger.epoch_begin(epoch)

    def on_dispatch_boundary(self):
        """Fit-path granularity: whole-training chunks dispatch as ONE
        XLA program with no per-step hook, so trace capture ticks (and
        HYDRAGNN_PROFILE_AT_STEP arming, resolved against the chunk's
        starting epoch — the step part is unsatisfiable here) advance at
        chunk boundaries instead. A ``/profile`` "step" on this path is
        one chunk; without this hook an arm request would wedge the
        endpoint in 'busy' forever."""
        if (
            self._profile_at is not None
            and self.current_epoch >= self._profile_at[0]
        ):
            self._profile_at = None
            self.profile(self._profile_steps)
        transition = self.trace.tick()
        if transition is not None:
            self.emit("profile", **transition)

    def record_compile(self, rec: Dict):
        """One novel (program, shape signature) was compiled: event +
        per-bucket cost/memory gauges (obs/introspect.py calls this)."""
        cost = rec.get("cost") or {}
        mem = rec.get("memory") or {}
        coll = rec.get("collectives") or {}
        bucket = rec["bucket"]
        self.ledger.note_program(rec)  # train-bucket FLOPs feed the MFU
        if cost.get("flops"):
            self.metrics.registry.set_labeled(
                "flops_per_step", float(cost["flops"]), bucket=bucket
            )
        if mem.get("peak_bytes"):
            self.metrics.registry.set_labeled(
                "hbm_peak_bytes", float(mem["peak_bytes"]), bucket=bucket
            )
        for axis, nbytes in coll.items():
            # cumulative across captured programs: the run's collective
            # footprint per axis, not the last bucket's
            self._collective_totals[axis] = (
                self._collective_totals.get(axis, 0.0) + float(nbytes)
            )
            self.metrics.registry.set_labeled(
                "collective_bytes",
                self._collective_totals[axis],
                axis=axis,
            )
        self.emit(
            "compile", name=rec["name"], bucket=bucket, cost=cost,
            memory=mem, kernels=int(rec.get("kernels", 0)),
            **({"collectives": coll} if coll else {}),
        )

    def profile(self, steps: int) -> Dict:
        """Arm device-trace capture for the next ``steps`` steps — the
        ``/profile?steps=N`` provider hook (any thread)."""
        result = self.trace.arm(steps)
        if result.get("status") == "armed":
            self.emit("profile", **result)
        return result

    # ---- lifecycle -----------------------------------------------------
    def emit(self, event: str, **fields):
        if self.events is not None:
            self.events.emit(event, **fields)

    def emit_manifest(self, config: dict, run_name: str):
        import jax

        devices = jax.devices()
        self.metrics.registry.set("world_size", float(jax.process_count()))
        host = os.getenv("HYDRAGNN_ELASTIC_HOST")
        self.emit(
            "run_manifest",
            schema_version=SCHEMA_VERSION,
            run=run_name,
            config_hash=_config_hash(config),
            git_rev=_git_rev(),
            world_size=jax.process_count(),
            device_kind=devices[0].device_kind if devices else "none",
            device_count=len(devices),
            num_epoch=int(
                config.get("NeuralNetwork", {})
                .get("Training", {})
                .get("num_epoch", 0)
            ),
            # elastic runs: which HOST wrote this stream segment — the
            # fleet rollup attributes rank 0's shared events.jsonl to
            # hosts by walking these manifests across generations
            **({} if host is None else {"host": int(host)}),
        )

    def close(self, status: str = "complete"):
        if self._closed:
            return
        self._closed = True
        # the last epoch's goodput window closes with the run
        try:
            self.ledger.finalize()
        except Exception:
            pass
        # a run dying mid-capture must still flush a loadable trace
        flushed = self.trace.close()
        if flushed is not None:
            self.emit("profile", **flushed)
        self.emit("run_end", status=status)
        if self.events is not None:
            self.events.close()
        if self.server is not None:
            self.server.stop()
            self.server = None


# ---- module-level hooks (no-op fast path when no run is active) ----------


def active() -> Optional[RunTelemetry]:
    return _active


def activate(telemetry: RunTelemetry):
    global _active
    prev = _active
    _active = telemetry
    if prev is not None and prev is not telemetry:
        # a run that never deactivated (crashed between init and its
        # cleanup) must not leak its event-stream handle into this one
        prev.close(status="abandoned")
    return telemetry


def deactivate(status: str = "complete"):
    global _active
    t = _active
    _active = None
    if t is not None:
        t.close(status)


def emit(event: str, **fields):
    t = _active
    if t is not None:
        t.emit(event, **fields)


def epoch_start(epoch: int):
    """The epoch driver announces each epoch (resets the per-epoch step
    counter behind HYDRAGNN_PROFILE_AT_STEP's <epoch>:<step> target)."""
    t = _active
    if t is not None:
        t.on_epoch_start(epoch)


def dispatch_boundary():
    """The fit path announces each whole-chunk dispatch completing (see
    :meth:`RunTelemetry.on_dispatch_boundary`)."""
    t = _active
    if t is not None:
        t.on_dispatch_boundary()


def epoch_complete(
    epoch: int,
    train_loss,
    val_loss,
    test_loss,
    seconds=None,
    graphs_per_sec=None,
    nodes_per_sec=None,
    padding_waste=None,
    mode: str = "stream",
):
    t = _active
    if t is None:
        return
    t.metrics.on_epoch(
        int(epoch),
        float(train_loss),
        float(val_loss),
        float(test_loss),
        seconds=seconds,
        graphs_per_sec=graphs_per_sec,
        nodes_per_sec=nodes_per_sec,
        padding_waste=padding_waste,
    )
    if seconds is not None:
        # whole-dispatch epochs (staged / fit chunks) have no per-step
        # hook; the driver's measured train wall is their compute signal
        t.ledger.note_train_wall(seconds)
    t.emit(
        "epoch",
        epoch=int(epoch),
        train_loss=float(train_loss),
        val_loss=float(val_loss),
        test_loss=float(test_loss),
        mode=mode,
        **(
            {}
            if seconds is None
            else {
                "wall_time_s": round(float(seconds), 6),
                "graphs_per_sec": (
                    None
                    if graphs_per_sec is None
                    else round(float(graphs_per_sec), 3)
                ),
                "nodes_per_sec": (
                    None
                    if nodes_per_sec is None
                    else round(float(nodes_per_sec), 3)
                ),
            }
        ),
        **(
            {}
            if padding_waste is None
            else {"padding_waste": round(float(padding_waste), 6)}
        ),
    )


def guard_skip(scope: str, skipped: int, streak: int = 0):
    t = _active
    if t is None:
        return
    t.metrics.registry.inc("guard_skips_total")
    t.emit("guard_skip", scope=scope, skipped=int(skipped),
           streak=int(streak))


def guard_restore(restores: int, lr: float, seconds: float = 0.0):
    t = _active
    if t is None:
        return
    t.metrics.registry.inc("guard_restores_total")
    t.ledger.guard_cost(seconds)
    t.emit(
        "guard_restore", restores=int(restores), lr=float(lr),
        **({} if not seconds else {"seconds": round(float(seconds), 6)}),
    )


def checkpoint_saved(name: str, kind: str, **fields):
    t = _active
    if t is None:
        return
    t.metrics.registry.inc("checkpoints_saved_total")
    # goodput: a sync save costs the loop snapshot + serialize/write; an
    # async one only the device->host snapshot (the write overlaps)
    cost = float(fields.get("snapshot_s") or 0.0)
    if not fields.get("async"):
        cost += float(fields.get("write_s") or 0.0)
    t.ledger.checkpoint_cost(cost)
    t.emit("checkpoint_saved", name=name, kind=kind, **fields)


def checkpoint_restored(name: str, source: str):
    t = _active
    if t is None:
        return
    t.emit("checkpoint_restored", name=name, source=source)


def stream_epoch_stats(
    queue_depth: int = 0,
    stall_s: float = 0.0,
    bytes_per_sec: float = 0.0,
    open_shards_peak: int = 0,
    resident_bytes_peak: int = 0,
    samples: int = 0,
    oversize_dropped: int = 0,
    source_counts: Optional[Dict[str, int]] = None,
):
    """One epoch of the streaming data plane completed (data/stream/):
    refresh the ``stream_*`` gauge family. No event — the epoch event
    already carries the loss/throughput story; these are live-health
    series."""
    t = _active
    if t is None:
        return
    t.ledger.data_wait(stall_s)  # the goodput data_stall signal
    r = t.metrics.registry
    r.set("stream_queue_depth", float(queue_depth))
    r.set("stream_stall_seconds", float(stall_s))
    r.set("stream_bytes_per_second", float(bytes_per_sec))
    r.set("stream_open_shards_peak", float(open_shards_peak))
    r.set("stream_resident_bytes_peak", float(resident_bytes_peak))
    if samples:
        r.inc("stream_samples_total", int(samples))
    if oversize_dropped:
        r.inc("stream_oversize_dropped_total", int(oversize_dropped))
    if source_counts:
        total = max(sum(source_counts.values()), 1)
        for name, n in source_counts.items():
            r.set_labeled(
                "stream_source_fraction", n / total, source=name
            )


def world_resized(old_world: int, new_world: int, gen: int,
                  recovery_s: float, **fields):
    """Elastic re-mesh completed (train/elastic.py): event + gauges. The
    recovery time spans loss DETECTION to the first optimizer step at the
    new world size — everything an operator would otherwise do by hand."""
    t = _active
    if t is None:
        return
    t.metrics.registry.set("world_size", float(new_world))
    t.metrics.registry.set("last_recovery_seconds", float(recovery_s))
    t.emit(
        "world_resize",
        old_world=int(old_world),
        new_world=int(new_world),
        gen=int(gen),
        recovery_s=float(recovery_s),
        **fields,
    )


def eval_start():
    """The epoch driver is entering its val/test evaluation — opens a
    goodput eval span (compile time and data waits inside the span stay
    in their own categories)."""
    t = _active
    if t is not None:
        t.ledger.eval_begin()


def eval_complete():
    t = _active
    if t is not None:
        t.ledger.eval_end()


# ---- run construction ----------------------------------------------------


def init_run_telemetry(
    config: dict, log_name: str, path: str = "./logs/"
) -> Optional[RunTelemetry]:
    """Build + activate telemetry for a driver run, honoring the env/config
    knobs (module docstring). Returns None (hooks stay no-ops) on
    non-zero ranks — EXCEPT under elastic mode, where every host writes
    its own ``events-host<k>.jsonl`` next to rank 0's ``events.jsonl``
    (no HTTP endpoint, no shared-file contention) so the fleet rollup
    (``python -m hydragnn_tpu.obs fleet``) has a per-host record of
    stalls, goodput, and step times — a straggler is only visible from
    the host it lives on."""
    from hydragnn_tpu.parallel.distributed import get_comm_size_and_rank

    _, rank = get_comm_size_and_rank()
    tcfg = config.get("Telemetry", {}) or {}
    env = os.getenv("HYDRAGNN_TELEMETRY")
    enabled = (
        env.strip().lower() not in ("", "0", "false", "no", "off")
        if env is not None
        else bool(tcfg.get("enable", True))
    )
    if not enabled:
        return None
    if rank != 0:
        host = os.getenv("HYDRAGNN_ELASTIC_HOST")
        if not os.getenv("HYDRAGNN_ELASTIC_DIR") or host is None:
            return None
        telemetry = RunTelemetry(
            log_name,
            os.path.join(path, log_name),
            port=None,
            events_file=f"events-host{int(host)}.jsonl",
        )
        telemetry.emit_manifest(config, log_name)
        return activate(telemetry)
    port_env = os.getenv("HYDRAGNN_OBS_PORT")
    port: Optional[int]
    if port_env is not None and port_env.strip() != "":
        port = int(port_env)
    elif tcfg.get("port") is not None:
        port = int(tcfg["port"])
    else:
        port = None
    telemetry = RunTelemetry(
        log_name, os.path.join(path, log_name), port=port
    )
    telemetry.emit_manifest(config, log_name)
    return activate(telemetry)
