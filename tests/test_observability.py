"""Unified telemetry (hydragnn_tpu/obs): shared metrics core parity with
serving, structured run events + schema validation, ScalarWriter fan-out,
live training /metrics endpoint, padding-waste accounting, honest tracer
sync — and the acceptance e2e: a tiny training with telemetry enabled,
scraped WHILE it runs, leaving a schema-valid events.jsonl behind.
"""

import json
import os
import sys
import time
import urllib.request
import warnings

import numpy as np
import pytest

import jax

from hydragnn_tpu import obs
from hydragnn_tpu.obs import runtime as obs_rt
from hydragnn_tpu.obs.events import RunEventLog, validate_events
from hydragnn_tpu.obs.metrics import MetricsRegistry
from hydragnn_tpu.obs.scalars import (
    CsvScalarBackend,
    JsonlScalarBackend,
    ScalarWriter,
)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _resilience_worker import make_samples  # noqa: E402

# ---- shared-core parity with serving -------------------------------------

# render_prometheus() of the PRE-REFACTOR hydragnn_tpu/serve/metrics.py for
# exactly the traffic _drive_serve_traffic() generates — the shared-core
# promotion must keep the serving exposition byte-identical. DELIBERATE
# extension (goodput/SLO PR): the deadline-outcome + SLO-miss series are
# appended AFTER the historical lines, so every pre-existing consumer's
# byte offsets are untouched and the golden grew by exactly that tail.
# DELIBERATE extension (multi-tenant PR): the response-cache series are
# appended after the SLO tail under the same rule.
_GOLDEN_SERVE = """\
# HELP hydragnn_serve_requests_total Accepted requests
# TYPE hydragnn_serve_requests_total counter
hydragnn_serve_requests_total 5
# HELP hydragnn_serve_responses_total Completed requests
# TYPE hydragnn_serve_responses_total counter
hydragnn_serve_responses_total 5
# HELP hydragnn_serve_shed_total Queue-full rejections
# TYPE hydragnn_serve_shed_total counter
hydragnn_serve_shed_total 1
# HELP hydragnn_serve_timeouts_total Deadline expiries
# TYPE hydragnn_serve_timeouts_total counter
hydragnn_serve_timeouts_total 1
# HELP hydragnn_serve_errors_total Failed requests
# TYPE hydragnn_serve_errors_total counter
hydragnn_serve_errors_total 2
# HELP hydragnn_serve_batches_total Dispatched micro-batches
# TYPE hydragnn_serve_batches_total counter
hydragnn_serve_batches_total 2
# HELP hydragnn_serve_compiles_total Novel-shape compiles
# TYPE hydragnn_serve_compiles_total counter
hydragnn_serve_compiles_total 1
# HELP hydragnn_serve_bucket_fallbacks_total Requests served by a larger bucket than their node count
# TYPE hydragnn_serve_bucket_fallbacks_total counter
hydragnn_serve_bucket_fallbacks_total 1
# HELP hydragnn_serve_queue_depth Requests waiting
# TYPE hydragnn_serve_queue_depth gauge
hydragnn_serve_queue_depth 3
# HELP hydragnn_serve_padding_waste_ratio Padded node rows carrying no real node
# TYPE hydragnn_serve_padding_waste_ratio gauge
hydragnn_serve_padding_waste_ratio 0.241071
hydragnn_serve_bucket_hits_total{bucket="32"} 3
hydragnn_serve_bucket_hits_total{bucket="64"} 2
# TYPE hydragnn_serve_request_latency_seconds summary
hydragnn_serve_request_latency_seconds{quantile="0.5"} 0.0375
hydragnn_serve_request_latency_seconds{quantile="0.99"} 2.455
hydragnn_serve_request_latency_seconds_sum 1.732
hydragnn_serve_request_latency_seconds_count 3
# TYPE hydragnn_serve_batch_latency_seconds summary
hydragnn_serve_batch_latency_seconds{quantile="0.5"} 0.025
hydragnn_serve_batch_latency_seconds{quantile="0.99"} 0.495
hydragnn_serve_batch_latency_seconds_sum 0.412
hydragnn_serve_batch_latency_seconds_count 2
# HELP hydragnn_serve_slo_misses_total Deadline-carrying requests that missed their deadline
# TYPE hydragnn_serve_slo_misses_total counter
hydragnn_serve_slo_misses_total 2
hydragnn_serve_deadline_outcomes_total{outcome="met"} 2
hydragnn_serve_deadline_outcomes_total{outcome="missed"} 2
# HELP hydragnn_serve_slo_miss_ratio Fraction of deadline-carrying requests that missed
# TYPE hydragnn_serve_slo_miss_ratio gauge
hydragnn_serve_slo_miss_ratio 0.5
# HELP hydragnn_serve_cache_hits_total Requests answered from the response cache
# TYPE hydragnn_serve_cache_hits_total counter
hydragnn_serve_cache_hits_total 2
# HELP hydragnn_serve_cache_misses_total Cache lookups that fell through to dispatch
# TYPE hydragnn_serve_cache_misses_total counter
hydragnn_serve_cache_misses_total 3
# HELP hydragnn_serve_cache_evictions_total Entries evicted by the LRU bounds
# TYPE hydragnn_serve_cache_evictions_total counter
hydragnn_serve_cache_evictions_total 1
# HELP hydragnn_serve_cache_bytes Resident response-cache payload bytes
# TYPE hydragnn_serve_cache_bytes gauge
hydragnn_serve_cache_bytes 4096
"""


def _drive_serve_traffic(m):
    for _ in range(5):
        m.on_submit()
    m.on_shed()
    m.on_timeout()  # in-queue expiry: also a missed deadline
    m.on_error(2)
    m.on_compile()
    m.set_queue_depth(3)
    m.on_batch(bucket=32, num_requests=3, real_nodes=70, padded_nodes=96,
               batch_seconds=0.012, fallbacks=1)
    m.on_batch(bucket=64, num_requests=2, real_nodes=100, padded_nodes=128,
               batch_seconds=0.4)
    for s in (0.002, 0.03, 1.7):
        m.on_response_latency(s)
    # per-request deadline outcomes (SLO accounting): 2 met, 1 delivered
    # late -> with the timeout above, 2 met / 2 missed, miss ratio 0.5
    m.on_deadline(True)
    m.on_deadline(True)
    m.on_deadline(False)
    # response-cache traffic (multi-tenant PR): 2 hits, 3 misses, one
    # LRU eviction, 4 KiB resident
    m.on_cache_hit(2)
    m.on_cache_miss(3)
    m.on_cache_evict()
    m.set_cache_bytes(4096)
    return m


def pytest_serve_metrics_prometheus_byte_parity():
    from hydragnn_tpu.serve.metrics import ServeMetrics

    m = _drive_serve_traffic(ServeMetrics())
    assert m.render_prometheus() == _GOLDEN_SERVE


def pytest_serve_reexports_shared_core():
    import hydragnn_tpu.serve.http as serve_http
    import hydragnn_tpu.serve.metrics as serve_metrics

    assert serve_metrics.ServeMetrics is obs.ServeMetrics
    assert serve_metrics.LatencyHistogram is obs.LatencyHistogram
    assert serve_http.ObservabilityServer is obs.ObservabilityServer
    # the serve package facade too
    from hydragnn_tpu.serve import ObservabilityServer, ServeMetrics

    assert ServeMetrics is obs.ServeMetrics
    assert ObservabilityServer is obs.ObservabilityServer


# ---- metrics registry ----------------------------------------------------


def pytest_metrics_registry_declare_record_render():
    r = MetricsRegistry("t")
    r.counter("a_total", "help a")
    r.gauge("g", "a gauge")
    r.histogram("lat_seconds", "a histogram")
    r.inc("a_total", 3)
    r.set("g", 0.25)
    r.observe("lat_seconds", 0.01)
    r.observe("lat_seconds", 0.02)
    snap = r.snapshot()
    assert snap["a_total"] == 3
    assert snap["g"] == 0.25
    assert snap["lat_seconds"]["count"] == 2
    text = r.render_prometheus()
    assert "# TYPE t_a_total counter\nt_a_total 3" in text
    assert "# TYPE t_g gauge\nt_g 0.25" in text
    assert 't_lat_seconds{quantile="0.5"}' in text
    assert "t_lat_seconds_count 2" in text
    # declaration order is exposition order
    assert text.index("t_a_total") < text.index("t_g") < text.index(
        "t_lat_seconds"
    )
    with pytest.raises(ValueError):
        r.counter("a_total")


# ---- run-event stream ----------------------------------------------------


def pytest_event_log_roundtrip_and_validation(tmp_path):
    path = str(tmp_path / "events.jsonl")
    log = RunEventLog(path)
    log.emit("run_manifest", schema_version=1, run="r", config_hash="c",
             git_rev="g", world_size=1, device_kind="cpu", device_count=1,
             num_epoch=2)
    log.emit("epoch", epoch=0, train_loss=np.float32(0.5), val_loss=0.6,
             test_loss=0.7, mode="stream", wall_time_s=0.1)
    log.emit("custom_future_event", anything=True)  # unknown types are legal
    # a diverged epoch's NaN losses must yield STRICT JSON (null, not a
    # bare NaN token jq/JS consumers reject)
    log.emit("epoch", epoch=1, train_loss=float("nan"),
             val_loss=np.float32("inf"), test_loss=0.1, mode="stream")
    log.emit("run_end", status="complete")
    log.close()

    def _no_constants(name):
        raise ValueError(f"non-standard JSON constant {name}")

    for line in open(path):
        json.loads(line, parse_constant=_no_constants)  # strict parse
    recs = validate_events(path, require=["run_manifest", "epoch", "run_end"])
    assert [r["seq"] for r in recs] == [0, 1, 2, 3, 4]
    assert recs[1]["train_loss"] == 0.5  # numpy scalar serialized as float
    assert recs[3]["train_loss"] is None  # NaN -> null
    assert recs[3]["val_loss"] is None  # inf -> null
    assert recs[3]["test_loss"] == pytest.approx(0.1)

    with pytest.raises(ValueError, match="never emitted"):
        validate_events(path, require=["guard_restore"])

    # a known type missing a required field is a violation
    bad = str(tmp_path / "bad.jsonl")
    b = RunEventLog(bad)
    b.emit("epoch", epoch=0)
    b.close()
    with pytest.raises(ValueError, match="missing required fields"):
        validate_events(bad)

    # a torn/interleaved stream (seq gap) is a violation
    torn = str(tmp_path / "torn.jsonl")
    with open(torn, "w") as f:
        f.write('{"event": "x", "ts": 1.0, "seq": 0}\n')
        f.write('{"event": "x", "ts": 2.0, "seq": 2}\n')
    with pytest.raises(ValueError, match="seq"):
        validate_events(torn)


def pytest_event_log_append_resumes_seq_and_repairs_torn_tail(tmp_path):
    """A rerun/resume of the same run name continues the stream: seq picks
    up where the previous process stopped, and a hard-kill's partial final
    line (no newline) is truncated away instead of merging with the first
    resumed event."""
    path = str(tmp_path / "events.jsonl")
    log = RunEventLog(path)
    log.emit("run_manifest", schema_version=1, run="r", config_hash="c",
             git_rev="g", world_size=1, device_kind="cpu", device_count=1,
             num_epoch=2)
    log.emit("epoch", epoch=0, train_loss=0.5, val_loss=0.6, test_loss=0.7,
             mode="stream")
    log.close()
    # simulate a SIGKILL mid-write: a partial line with no newline
    with open(path, "a") as f:
        f.write('{"event": "epoch", "ts": 3.0, "se')
    resumed = RunEventLog(path)
    resumed.emit("resume", start_epoch=1)
    resumed.emit("run_end", status="complete")
    resumed.close()
    recs = validate_events(path, require=["resume", "run_end"])
    assert [r["seq"] for r in recs] == [0, 1, 2, 3]
    assert recs[2]["event"] == "resume"  # the torn partial line is gone


# ---- ScalarWriter fan-out ------------------------------------------------


def pytest_scalar_writer_jsonl_roundtrip(tmp_path):
    path = str(tmp_path / "scalars.jsonl")
    w = ScalarWriter([JsonlScalarBackend(path)])
    w.add_scalar("train error", 0.5, 0)
    w.add_scalar("train error", 0.25, 1)
    w.add_regions({"train": 1.5, "dataload": 0.5}, step=2)
    w.close()
    recs = [json.loads(line) for line in open(path)]
    assert [(r["tag"], r["value"], r["step"]) for r in recs] == [
        ("train error", 0.5, 0),
        ("train error", 0.25, 1),
        ("tracer/dataload_seconds", 0.5, 2),
        ("tracer/train_seconds", 1.5, 2),
    ]
    assert all("ts" in r for r in recs)


def pytest_scalar_writer_csv_backend(tmp_path):
    path = str(tmp_path / "scalars.csv")
    w = ScalarWriter([CsvScalarBackend(path)])
    w.add_scalar("loss", 1.25, 3)
    w.close()
    lines = open(path).read().strip().splitlines()
    assert lines[0] == "tag,value,step,ts"
    assert lines[1].startswith("loss,1.25,3,")


def pytest_scalar_writer_for_run_warns_once_without_tensorboard(
    tmp_path, monkeypatch
):
    from hydragnn_tpu.obs import scalars as sc

    monkeypatch.setattr(sc, "_tb_warned", False)

    def _boom(self, log_dir):
        raise ImportError("no torch here")

    monkeypatch.setattr(sc.TensorBoardScalarBackend, "__init__", _boom)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        w1 = ScalarWriter.for_run("runA", path=str(tmp_path))
        w2 = ScalarWriter.for_run("runB", path=str(tmp_path))
    tb_warnings = [c for c in caught if "TensorBoard" in str(c.message)]
    assert len(tb_warnings) == 1  # exactly once per process
    # the always-on file backend still records
    w1.add_scalar("x", 1.0, 0)
    w1.close()
    w2.close()
    assert os.path.exists(tmp_path / "runA" / "scalars.jsonl")


# ---- no-op fast path -----------------------------------------------------


def pytest_hooks_are_noops_when_inactive():
    obs_rt.deactivate()
    assert obs_rt.active() is None
    n = 100_000
    t0 = time.perf_counter()
    for _ in range(n):
        obs_rt.emit("epoch", epoch=1)
        obs_rt.epoch_complete(1, 0.5, 0.5, 0.5)
        obs_rt.guard_skip("step", 1)
        obs_rt.checkpoint_saved("x", kind="primary")
    dt = time.perf_counter() - t0
    # 400k inactive hook calls; a disabled epoch loop makes a handful per
    # epoch, so even this very lenient bound (~6µs/call) proves the
    # telemetry-off wall time is baseline within noise
    assert dt < 2.5, f"no-op hooks too slow: {dt:.3f}s for {4 * n} calls"


# ---- padding-waste accounting in the loader ------------------------------


def _sized_samples(sizes, seed=3):
    from hydragnn_tpu.data.dataobj import GraphData

    rng = np.random.default_rng(seed)
    out = []
    for n in sizes:
        g = GraphData()
        g.x = rng.random((n, 1)).astype(np.float32)
        g.pos = rng.random((n, 3)).astype(np.float32)
        src = np.arange(n)
        dst = (src + 1) % n
        g.edge_index = np.stack(
            [np.concatenate([src, dst]), np.concatenate([dst, src])]
        ).astype(np.int64)
        g.edge_attr = None
        g.targets = [np.array([g.x.sum()], np.float32), g.x.copy()]
        g.target_types = ["graph", "node"]
        out.append(g)
    return out


def pytest_epoch_padding_stats_plain_and_bucketed():
    from hydragnn_tpu.data.loaders import GraphLoader, compute_layout

    sizes = [4, 6, 8, 12, 5, 9, 11, 4, 7, 10, 6, 8]
    samples = _sized_samples(sizes)
    layout = compute_layout([samples], batch_size=4)
    loader = GraphLoader(
        samples, 4, layout, shuffle=False, num_shards=1, shard_id=0
    )
    real, padded = loader.epoch_padding_stats()
    assert real == sum(sizes)
    assert padded == len(loader) * layout.n_pad
    assert 0.0 < 1.0 - real / padded < 1.0

    bucketed = compute_layout([samples], batch_size=4, num_buckets=2)
    bloader = GraphLoader(
        samples, 4, bucketed, shuffle=False, num_shards=1, shard_id=0
    )
    breal, bpadded = bloader.epoch_padding_stats()
    assert breal == sum(sizes)
    assert bpadded == sum(
        bucketed.layouts[b].n_pad for b, _ in bloader._batch_plan()
    )
    # bucketing exists to cut padding waste — same data, less padding
    assert bpadded <= padded


# ---- honest tracer sync (HYDRAGNN_TRACE_LEVEL=1) -------------------------


def pytest_tracer_sync_absorbs_async_dispatch(monkeypatch):
    import jax.numpy as jnp

    from hydragnn_tpu.utils import tracer as tr

    n = 1800
    x = jnp.ones((n, n))
    f = jax.jit(lambda a: a @ a @ a @ a)
    for _ in range(3):  # compile and settle outside the measurement
        f(x).block_until_ready()
    t0 = time.perf_counter()
    f(x).block_until_ready()
    true_t = time.perf_counter() - t0

    # the recorder reads HYDRAGNN_TRACE_LEVEL once, at initialize
    monkeypatch.setattr(tr, "_state", tr._State())

    # without the sync, stop() returns while the compute is still in
    # flight — the region absorbs ~none of it
    monkeypatch.delenv("HYDRAGNN_TRACE_LEVEL", raising=False)
    tr.initialize()
    tr.start("nosync")
    y = f(x)
    no_sync = tr.stop("nosync").seconds
    y.block_until_ready()
    if no_sync > 0.5 * true_t:
        pytest.skip("backend dispatch is synchronous here; nothing to test")

    monkeypatch.setenv("HYDRAGNN_TRACE_LEVEL", "1")
    tr.start("synced")  # the level is not re-read per region ...
    y = f(x)
    assert tr.stop("synced").seconds < 0.5 * true_t
    y.block_until_ready()
    tr.initialize()  # ... but at initialize
    tr.start("synced")
    y = f(x)
    # must block until the dispatched matmuls finish
    synced = tr.stop("synced").seconds
    assert tr.totals()["synced"] >= synced
    assert synced >= 0.5 * true_t, (
        f"traced region absorbed {synced:.4f}s of a {true_t:.4f}s "
        "async computation — trace level 1 is not device-syncing"
    )


# ---- env/config knobs ----------------------------------------------------


def pytest_init_run_telemetry_knobs(tmp_path, monkeypatch):
    cfg = {"NeuralNetwork": {"Training": {"num_epoch": 3}}}

    monkeypatch.setenv("HYDRAGNN_TELEMETRY", "0")
    assert obs_rt.init_run_telemetry(cfg, "off", path=str(tmp_path)) is None
    assert obs_rt.active() is None

    monkeypatch.delenv("HYDRAGNN_TELEMETRY")
    monkeypatch.setenv("HYDRAGNN_OBS_PORT", "0")
    telem = obs_rt.init_run_telemetry(cfg, "on", path=str(tmp_path))
    try:
        assert telem is not None and obs_rt.active() is telem
        host, port = telem.address
        health = json.loads(
            urllib.request.urlopen(
                f"http://{host}:{port}/healthz", timeout=10
            ).read()
        )
        assert health["status"] == "ok" and health["run"] == "on"
    finally:
        obs_rt.deactivate()
    recs = validate_events(
        str(tmp_path / "on" / "events.jsonl"),
        require=["run_manifest", "run_end"],
    )
    man = recs[0]
    assert man["num_epoch"] == 3
    assert man["device_kind"] == "cpu"
    assert man["world_size"] == 1
    assert len(man["config_hash"]) == 12


# ---- the acceptance e2e --------------------------------------------------


def _build_tiny_training(num_epoch):
    from hydragnn_tpu.data.loaders import GraphLoader, compute_layout
    from hydragnn_tpu.models.create import create_model_config
    from hydragnn_tpu.train.trainer import Trainer

    arch = {
        "model_type": "GIN",
        "input_dim": 1,
        "hidden_dim": 8,
        "num_conv_layers": 2,
        "output_dim": [1, 1],
        "output_type": ["graph", "node"],
        "output_heads": {
            "graph": {
                "num_sharedlayers": 1,
                "dim_sharedlayers": 8,
                "num_headlayers": 1,
                "dim_headlayers": [8],
            },
            "node": {"num_headlayers": 1, "dim_headlayers": [8],
                     "type": "mlp"},
        },
        "task_weights": [1.0, 1.0],
    }
    training = {
        "num_epoch": num_epoch,
        "Optimizer": {"type": "AdamW", "learning_rate": 1e-2},
        "resume_every": 1,
        "divergence_guard": True,
    }
    samples = make_samples()
    layout = compute_layout([samples], batch_size=4)
    loaders = (
        GraphLoader(samples[:16], 4, layout, shuffle=True, seed=7),
        GraphLoader(samples[16:20], 4, layout, shuffle=False),
        GraphLoader(samples[20:], 4, layout, shuffle=False),
    )
    model = create_model_config(arch)
    trainer = Trainer(model, training)
    state = trainer.init_state(next(iter(loaders[0])), seed=0)
    return trainer, state, loaders, training


class _ScrapeOnEpochWriter:
    """writer= hook that scrapes the live endpoint DURING the run (at the
    first epoch>=1 scalar) — the 'concurrent /metrics' acceptance leg."""

    def __init__(self, url):
        self.url = url
        self.scraped = None

    def add_scalar(self, tag, value, step):
        if self.scraped is None and step >= 1:
            self.scraped = urllib.request.urlopen(
                self.url, timeout=10
            ).read().decode()

    def close(self):
        pass


def pytest_training_telemetry_e2e(tmp_path, monkeypatch):
    from hydragnn_tpu.train.epoch_driver import train_validate_test

    monkeypatch.chdir(tmp_path)
    # one poisoned step so the guard path emits into the same stream
    monkeypatch.setenv("HYDRAGNN_FAULT_NAN_AT_STEP", "2")
    num_epoch = 3
    trainer, state, loaders, training = _build_tiny_training(num_epoch)
    assert trainer.guard is not None

    telem = obs_rt.activate(
        obs_rt.RunTelemetry(
            "obs-e2e", str(tmp_path / "logs" / "obs-e2e"), port=0
        )
    )
    try:
        telem.emit_manifest(
            {"NeuralNetwork": {"Training": training}}, "obs-e2e"
        )
        host, port = telem.address
        writer = _ScrapeOnEpochWriter(f"http://{host}:{port}/metrics")
        config_nn = {
            "Training": training,
            "Variables_of_interest": {"output_names": ["sum", "x"]},
        }
        train_validate_test(
            trainer, state, *loaders, config_nn, "obs-e2e", verbosity=0,
            writer=writer,
        )

        # -- concurrent scrape returned live epoch/throughput/guard series
        assert writer.scraped is not None, "mid-run scrape never happened"
        mid = writer.scraped
        assert "hydragnn_train_epochs_total" in mid
        assert "hydragnn_train_graphs_per_second" in mid
        assert "hydragnn_train_guard_skips_total 1" in mid
        assert "hydragnn_train_heartbeat_age_seconds" in mid

        # -- end-of-run metrics state
        final = urllib.request.urlopen(
            f"http://{host}:{port}/metrics", timeout=10
        ).read().decode()
        snap = telem.metrics.snapshot()
        assert snap["epochs_total"] == num_epoch
        assert snap["guard_skips_total"] == 1
        assert snap["checkpoints_saved_total"] >= num_epoch
        assert snap["steps_total"] == num_epoch * 4  # 16 samples / bs 4
        assert snap["epoch_seconds"]["count"] == num_epoch
        assert f"hydragnn_train_epoch {float(num_epoch - 1)}" in final

        health = json.loads(
            urllib.request.urlopen(
                f"http://{host}:{port}/healthz", timeout=10
            ).read()
        )
        assert health["status"] == "ok"
        assert health["epoch"] == num_epoch - 1
    finally:
        obs_rt.deactivate()

    # -- the event stream validates against the documented schema
    recs = validate_events(
        str(tmp_path / "logs" / "obs-e2e" / "events.jsonl"),
        require=[
            "run_manifest", "epoch", "checkpoint_saved", "guard_skip",
            "run_end",
        ],
    )
    epochs = [r for r in recs if r["event"] == "epoch"]
    assert [e["epoch"] for e in epochs] == list(range(num_epoch))
    assert all(e["wall_time_s"] > 0 for e in epochs)
    assert all(e["graphs_per_sec"] > 0 for e in epochs)
    assert all(0.0 <= e["padding_waste"] < 1.0 for e in epochs)
    assert all(e["mode"] == "stream" for e in epochs)
    ckpts = [r for r in recs if r["event"] == "checkpoint_saved"]
    assert all(c["kind"] == "primary" and c["resumable"] for c in ckpts)
    guard = [r for r in recs if r["event"] == "guard_skip"]
    assert len(guard) == 1 and guard[0]["scope"] == "step"
    assert recs[-1]["event"] == "run_end"
    assert recs[-1]["status"] == "complete"


def pytest_fit_staged_epochs_report_train_time(tmp_path, monkeypatch):
    """The fit-staged path used to log no train time/throughput at all;
    now each epoch carries chunk_time/n and the chunk emits fit_chunk."""
    from hydragnn_tpu.train.epoch_driver import train_validate_test

    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("HYDRAGNN_DEVICE_RESIDENT", "1")
    monkeypatch.setenv("HYDRAGNN_FIT_CHUNK", "2")
    num_epoch = 4
    trainer, state, loaders, training = _build_tiny_training(num_epoch)
    trainer.guard = None  # guard is epoch-granular on the fit path anyway

    telem = obs_rt.activate(
        obs_rt.RunTelemetry(
            "obs-fit", str(tmp_path / "logs" / "obs-fit"), port=None
        )
    )
    try:
        config_nn = {
            "Training": training,
            "Variables_of_interest": {"output_names": ["sum", "x"]},
        }
        train_validate_test(
            trainer, state, *loaders, config_nn, "obs-fit", verbosity=0,
        )
    finally:
        obs_rt.deactivate()
    recs = validate_events(
        str(tmp_path / "logs" / "obs-fit" / "events.jsonl"),
        require=["fit_chunk", "epoch", "staged"],
    )
    chunks = [r for r in recs if r["event"] == "fit_chunk"]
    assert [c["epoch_start"] for c in chunks] == [0, 2]
    assert all(c["epochs"] == 2 and c["wall_time_s"] > 0 for c in chunks)
    epochs = [r for r in recs if r["event"] == "epoch"]
    assert len(epochs) == num_epoch
    assert all(e["mode"] == "fit" for e in epochs)
    assert all(e["wall_time_s"] > 0 for e in epochs)
    assert all(e["graphs_per_sec"] > 0 for e in epochs)
