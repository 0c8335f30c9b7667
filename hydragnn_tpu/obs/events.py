"""Structured run events: one append-only JSONL stream per training run.

The "what did this run do" half of the telemetry layer (the live
``/metrics`` endpoint is the "what is it doing right now" half — both are
fed from the same recording sites). Every line is one JSON object with a
fixed envelope:

    {"event": <type>, "ts": <unix seconds>, "seq": <per-run monotonic int>, ...}

plus the event-type payload fields listed in :data:`EVENT_FIELDS` (the
documented schema — docs/observability.md mirrors this table). Unknown
event types are allowed (forward compatibility: a newer writer must not
break an older validator), but a KNOWN type missing a required field is a
schema violation.

Writes are line-buffered appends by rank 0 only; a killed job leaves a
valid prefix (every fsync'd line parses), never a torn stream.
"""

import json
import math
import os
import threading
import time
from typing import Dict, List, Optional

SCHEMA_VERSION = 1

# event type -> required payload fields (on top of the envelope)
EVENT_FIELDS: Dict[str, tuple] = {
    "run_manifest": (
        "schema_version", "run", "config_hash", "git_rev", "world_size",
        "device_kind", "device_count", "num_epoch",
    ),
    "epoch": (
        "epoch", "train_loss", "val_loss", "test_loss", "mode",
    ),
    "fit_chunk": ("epoch_start", "epochs", "wall_time_s"),
    "staged": ("num_batches",),
    "checkpoint_saved": ("name", "kind"),
    "checkpoint_restored": ("name", "source"),
    "guard_skip": ("scope", "skipped"),
    "guard_restore": ("restores", "lr"),
    "resume": ("start_epoch",),
    "early_stop": ("epoch",),
    "wallclock_stop": ("epoch",),
    "tracer_totals": ("regions",),
    "run_end": ("status",),
    # XLA introspection (obs/introspect.py): one per novel compiled
    # (program, shape-signature); cost/memory are the normalized
    # cost_analysis()/memory_analysis() dicts ({} on backends without the
    # respective model)
    "compile": ("name", "bucket", "cost", "memory"),
    # flight recorder: a step dispatch exceeded stall_factor x the rolling
    # median of the last K steps
    "stall": ("step", "seconds", "median", "factor"),
    # on-demand trace capture lifecycle (armed -> started -> done)
    "profile": ("status",),
    # device memory report (parallel.distributed.print_peak_memory)
    "device_memory": ("devices",),
    # lock sanitizer watchdog (analysis/guards.py): a lock acquisition
    # blocked past the threshold; threads carries every thread's held
    # locks + stack at the moment of the dump
    "deadlock_suspect": ("lock", "waited_s", "threads"),
    # aggregation reporting (ops/agg_policy.py): source "layout" is the
    # family (segment|dense) the batch layout committed one bucket to
    # (models/base.py); source "operands" is the dense path's neighbour
    # gather or sender sum (ops/dense_agg.py; bucket gather/... or
    # scatter/...): gather = choice = onehot|xla, h the window's halo in
    # blocks
    "agg_choice": ("bucket", "choice", "source"),
    # the input pipeline's host buffers (graph/slots.py), one per
    # train_epoch over a loader that pools: slots acquired in the epoch
    # that had been used before / were made new (collated batches and
    # group stacks alike), and the bytes of every slot alive at its end
    "pool": ("reused", "made", "bytes"),
    # elastic training (train/elastic.py): a peer's heartbeat lease
    # expired — emitted by the detecting watchdog just before it breaks
    # the survivors out of the hung collective
    "host_lost": ("host",),
    # elastic training: the world re-formed at a new size and took its
    # first optimizer step; recovery_s spans loss detection -> first step
    # (teardown + re-bootstrap + checkpoint restore + recompile). 2-D
    # runs also carry mesh_shape=[d, m] (parallel/mesh.py re-derivation)
    "world_resize": ("old_world", "new_world", "gen", "recovery_s"),
    # mesh resolution (parallel/mesh.py): the run's device mesh — axis
    # names, [d, m] shape ([] when running unmeshed on one device), and
    # the visible device count the shape was derived from
    "mesh_shape": ("axes", "shape", "devices"),
    # partition-rule placement summary (parallel/rules.py): how many
    # train-state leaves (and bytes) the rule engine sharded vs
    # replicated — "everything silently replicated" regressions are
    # visible from the event stream alone
    "param_sharding": (
        "total_leaves", "sharded", "replicated", "sharded_bytes",
        "replicated_bytes",
    ),
    # streaming bucket planner (data/stream/planner.py): an auto-tuned
    # bucket plan was built from a streamed size histogram — bounds are
    # the inclusive node-count bucket boundaries, est_waste the simulated
    # padding-waste ratio of the plan over the scanned samples
    "bucket_plan": ("num_buckets", "bounds", "samples_scanned", "est_waste"),
    # HPO trial lifecycle (hpo/launcher.py trials.jsonl): status is
    # completed|failed|killed, reason names the failure/kill cause
    # (garbled_output, heartbeat_timeout, divergence, timeout, exit_<rc>)
    "hpo_trial": ("trial", "status"),
    # serving fleet (serve/fleet.py): a replica's lease expired or its
    # process died — the serving twin of host_lost (reason is
    # exit|lease_expired|killed)
    "replica_lost": ("replica", "reason"),
    # serving fleet: the supervisor respawned a lost replica and its new
    # incarnation reported serving; downtime_s spans detection -> first
    # serving lease (the serving twin of world_resize's recovery_s)
    "replica_respawned": ("replica", "downtime_s"),
    # hot-swap (serve/fleet.py + serve/registry.py): a candidate version
    # was warmed on every live replica (per-bucket, compile-counter
    # verified) and atomically promoted to serve version-less requests
    "model_promoted": ("name", "version"),
    # hot-swap: a candidate was rejected (CRC/strict-load failure, warmup
    # failure, ack timeout) — the old version never stopped serving
    "model_rollback": ("name", "reason"),
    # serving fleet: live replica count dropped below target (the
    # degradation ladder's trigger — the router sheds low-priority lanes
    # while this holds)
    "fleet_degraded": ("live", "target"),
    # closed-loop load generator (benchmarks/serve_bench.py --fleet,
    # tests/_fleet_smoke.py): one measured traffic window — availability
    # = terminally-succeeded / submitted logical requests
    "fleet_report": ("submitted", "succeeded", "availability"),
    # canary channel (serve/registry.py CandidateChannel): rank 0 of the
    # training side published a candidate checkpoint snapshot at
    # end-of-epoch cadence for the canary controller to prove out —
    # `candidate` is the channel sequence number (NOT the envelope seq)
    "candidate_published": ("candidate", "checkpoint"),
    # canary controller (serve/canary.py): a published candidate booted
    # on a dedicated canary replica and entered shadow evaluation —
    # live traffic is mirrored to it, its answers never returned
    "canary_started": ("candidate", "checkpoint"),
    # canary controller: every statistical gate passed over >= the
    # min-sample floor and the PR 15 all-acked hot-swap promoted the
    # candidate to active
    "canary_promoted": ("candidate", "checkpoint", "samples"),
    # canary controller: the candidate was rejected before ever serving
    # a live request — reason names the failed gate (nan_outputs,
    # head_mae, latency, shadow_errors, crash_loop, insufficient_samples,
    # superseded, or the hot-swap's own rollback reason)
    "canary_rejected": ("candidate", "checkpoint", "reason"),
    # goodput ledger (obs/ledger.py): one per epoch window — `seconds`
    # and `fractions` map every CATEGORIES entry (compute/data_stall/
    # collective/checkpoint/compile/guard_recovery/eval/other) to its
    # attributed wall time / fraction (fractions sum to 1 by
    # construction); optional `mfu` carries per-bucket
    # {mfu, flops, steps_per_sec, peak_flops}
    "goodput": ("epoch", "wall_s", "seconds", "fractions",
                "goodput_fraction"),
    # multi-tenant serving (serve/tenants.py): one per spec'd tenant at
    # fleet start — the audit record of who is HBM-packed into the fleet
    # with which model and what admission quota
    "tenant_admitted": ("tenant", "model", "quota"),
    # response cache (serve/cache.py): a measured traffic window's cache
    # counters, appended by the bench/smoke load generators
    "cache_stats": ("hits", "misses", "evictions", "bytes"),
    # predictive autoscaler (serve/autoscale.py) / ServingFleet.resize:
    # the supervised replica target moved (reason names the trigger —
    # slo_pressure, forecast, scale_down, manual)
    "fleet_scaled": ("old_target", "new_target", "reason"),
    # request tracing (obs/trace.py): one span of one request's causal
    # tree — trace/span/parent are the tree ids (parent "" on the root),
    # name is the segment (route/admit/cache_lookup/backoff/attempt on
    # the router; queue_wait/batch_form/dispatch/readback on the
    # replica), start is wall-clock unix seconds, dur_s the span's
    # duration, attrs the per-span labels (tenant, lane, bucket, replica
    # rid, cache hit/miss, retry ordinal, shed reason, ...). Flushed
    # tail-based at the request's terminal outcome
    "span": ("trace", "span", "parent", "name", "start", "dur_s", "attrs"),
    # cost->quota feedback (serve/costs.py, HYDRAGNN_TENANT_COST_QUOTAS):
    # a tenant's admission quota was shaved (reason over_cost) or its
    # base quota restored (reason restored); cost_share is the tenant's
    # share of the window's device time, fair_share its weight-
    # proportional entitlement
    "quota_adjusted": ("tenant", "old_quota", "new_quota", "reason",
                       "cost_share", "fair_share"),
    # tenant cost ledger (serve/costs.py): one per-tenant bill row for a
    # measured window, appended by the bench/smoke load generators —
    # device_s is attributed device wall-time, replica_s the window's
    # fleet integrated replica-seconds the rows (plus idle) sum to
    "tenant_cost": ("tenant", "device_s", "flops", "requests",
                    "replica_s"),
    # model-quality observatory (obs/drift.py DriftDetector): one per
    # completed tumbling window — scores maps "tenant|feature|head" to
    # {psi, ks} vs the version-pinned reference; optional `uncertainty`
    # carries per-"tenant|head" predictive-variance quantiles
    "drift_window": ("version", "window", "scores"),
    # model-quality observatory: a feature's drift score crossed the
    # hysteresis threshold (status raised) or came back under it for
    # clear_after consecutive windows (status cleared) — always scored
    # vs what `version` was vetted on, never a moving baseline
    "drift_alert": (
        "tenant", "feature", "head", "kind", "score", "status",
        "version",
    ),
    # feedback sink (serve/quality.py FeedbackSink): cumulative queue-
    # dir counters at each pack flush — accepted (buffered for
    # labeling), deduped (canonical_graph_key repeats), graphs/packs
    # (persisted shard_store totals)
    "feedback_sink": ("accepted", "deduped", "graphs", "packs"),
    # NaN sentinel (analysis/guards.py nan_sentinel / nan_origin): the
    # runtime half of the numlint numerics suite — a wrapped step or a
    # canary shadow answer produced a non-finite value. scope names the
    # wrapped region (train_step, canary:<candidate>), origin the FIRST
    # non-finite leaf's pytree path, subtree its leading component (the
    # head/param group to blame), leaves/total the non-finite/total leaf
    # counts of the output tree
    "nan_origin": ("scope", "origin", "subtree", "leaves", "total"),
}

_ENVELOPE = ("event", "ts", "seq")


def _jsonable(obj):
    """json.dump default hook: numpy scalars/arrays -> plain python."""
    import numpy as np

    if isinstance(obj, np.integer):
        return int(obj)
    if isinstance(obj, np.floating):
        return float(obj)
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    return str(obj)


def _nullify_nonfinite(obj):
    """Strict JSON has no NaN/Infinity tokens; a diverged epoch's losses
    map to null instead of producing a line jq/JS/Go consumers reject."""
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    if isinstance(obj, dict):
        return {k: _nullify_nonfinite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_nullify_nonfinite(v) for v in obj]
    return obj


def _repair_torn_tail(path: str):
    """A hard kill mid-write can leave a final line with no terminating
    newline; appending to it would merge the partial garbage with the
    resumed run's first event into one corrupt line. The partial line
    never completed — drop it (truncate to the last newline) so the
    stream stays a valid prefix."""
    try:
        size = os.path.getsize(path)
        if size == 0:
            return
        with open(path, "rb+") as f:
            f.seek(max(size - 65536, 0))
            tail = f.read()
            if tail.endswith(b"\n"):
                return
            cut = tail.rfind(b"\n")
            f.truncate(size - len(tail) + (cut + 1 if cut >= 0 else 0))
    except OSError:
        pass


def _next_seq(path: str) -> int:
    """seq the next event appended to ``path`` should carry: last line's
    seq + 1 (0 for a fresh/empty/unreadable stream). Reads only the tail."""
    try:
        size = os.path.getsize(path)
        if size == 0:
            return 0
        with open(path, "rb") as f:
            f.seek(max(size - 65536, 0))
            tail = f.read().decode(errors="replace").strip().splitlines()
        for line in reversed(tail):
            line = line.strip()
            if not line:
                continue
            try:
                return int(json.loads(line).get("seq", -1)) + 1
            except (ValueError, TypeError):
                continue  # unparseable line — walk back to a complete one
        return 0
    except OSError:
        return 0


class RunEventLog:
    """Append-only JSONL event stream for one run (thread-safe)."""

    def __init__(self, path: str):
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self.path = path
        self._lock = threading.Lock()
        # a rerun/resume of the same run name APPENDS to the existing
        # stream — seq must continue where the previous process left off,
        # or the stream reads as torn
        _repair_torn_tail(path)
        self._seq = _next_seq(path)
        self._f = open(path, "a", buffering=1)  # line-buffered: crash-safe

    def emit(self, event: str, **fields):
        """Append one event. Never raises into the training loop — a full
        disk must not kill a run that would otherwise finish."""
        with self._lock:
            if self._f is None:
                return
            rec = {"event": event, "ts": round(time.time(), 6),
                   "seq": self._seq}
            rec.update(fields)
            try:
                try:
                    line = json.dumps(
                        rec, default=_jsonable, allow_nan=False
                    )
                except ValueError:
                    # non-finite floats (a diverged epoch's NaN losses —
                    # exactly what this stream must record): null them
                    # rather than emit a non-standard NaN token or drop
                    # the event
                    line = json.dumps(
                        _nullify_nonfinite(
                            json.loads(json.dumps(rec, default=_jsonable))
                        ),
                        allow_nan=False,
                    )
                # the write must stay in the critical section: seq order
                # ON DISK must match assignment order, and interleaved
                # writes from two emitters would tear the JSONL stream
                # threadlint: disable=blocking-under-lock
                self._f.write(line + "\n")
                self._seq += 1
            except (OSError, ValueError, TypeError):
                pass

    def close(self):
        with self._lock:
            if self._f is not None:
                try:
                    self._f.close()
                finally:
                    self._f = None


def validate_events(
    path: str, require: Optional[List[str]] = None
) -> List[Dict]:
    """Parse + schema-check an ``events.jsonl`` stream.

    Checks every line parses, envelopes are complete, ``seq`` is strictly
    increasing from 0, known event types carry their required fields
    (:data:`EVENT_FIELDS`), and each type in ``require`` appears at least
    once. Returns the parsed records; raises ``ValueError`` on the first
    violation — this is the CI gate's validator as well as the tests'.
    """
    records = []
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                raise ValueError(
                    f"{path}:{lineno}: unparseable event line ({e})"
                ) from e
            for k in _ENVELOPE:
                if k not in rec:
                    raise ValueError(
                        f"{path}:{lineno}: event missing envelope "
                        f"field {k!r}"
                    )
            if rec["seq"] != len(records):
                raise ValueError(
                    f"{path}:{lineno}: seq {rec['seq']} != expected "
                    f"{len(records)} (stream torn or interleaved)"
                )
            needed = EVENT_FIELDS.get(rec["event"], ())
            missing = [k for k in needed if k not in rec]
            if missing:
                raise ValueError(
                    f"{path}:{lineno}: event {rec['event']!r} missing "
                    f"required fields {missing}"
                )
            records.append(rec)
    if require:
        seen = {r["event"] for r in records}
        absent = [t for t in require if t not in seen]
        if absent:
            raise ValueError(
                f"{path}: required event types never emitted: {absent}"
            )
    return records
