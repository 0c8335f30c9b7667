"""Per-bucket aggregation autotuner: measure {segment, dense, fused}, cache
the winner, make every decision observable.

Three aggregation strategies coexist for the message-passing hot path:

- **segment**: ``jax.ops.segment_*`` scatters (XLA fuses them with the
  surrounding elementwise work) — the safe default;
- **dense**: host-built fixed-width neighbor lists, scatter-free masked
  K-axis reductions (``ops/dense_agg.py``) — wins at MXU widths for
  scatter-heavy stacks (measured crossovers below);
- **fused**: single-kernel Pallas gather -> edge-op -> reduce
  (``ops/fused_mp.py``) — wins where the scatter AND the ``[E, D]``
  message materialization dominate and the node table fits VMEM.

Decision order (first match wins), evaluated per bucket layout:

1. ``HYDRAGNN_AGG=segment|dense|fused`` — operator force, everywhere.
2. ``HYDRAGNN_FUSED_MP=1`` — force the fused kernels wherever the VMEM
   guard admits them (``0`` forbids them everywhere, beating the cache).
3. The on-disk cache — one measured choice per (device kind, bucket
   signature), written by :func:`autotune_bucket` at warmup. Cached
   decisions are DETERMINISTIC: no re-timing, same file -> same choices.
4. The measured-crossover static policy (tables promoted here from
   ``data/loaders.py``; bench.py's ``auto_choice`` reports this tier).

Every decision is emitted as an ``agg_choice`` obs event (schema in
``obs/events.py``) and an ``aggregation_kernel`` labeled gauge, so run
reports show which kernel each bucket actually used. That includes the
paths that route a bucket to XLA: the family the batch LAYOUT committed to
(source ``layout``, emitted by ``models/base.py`` at trace time) and a
requested kernel the VMEM guard refused (source ``guard``).
"""

import json
import os
import threading
import time
from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from hydragnn_tpu.ops.pallas_segment import on_tpu

CHOICES = ("segment", "dense", "fused")

# ---------------------------------------------------------------------------
# static policy (promoted from data/loaders.py — the tier bench.py's
# auto_choice column has always reported)
# ---------------------------------------------------------------------------

# Dense/segment crossovers measured on a v5e before the current tree
# (2026-07/08, same-session A/Bs at deg ~12; not re-measured since —
# ROADMAP S4): minimum hidden_dim at which the dense
# scatter-free path beats segment reductions for each model. Scatter-heavy
# models (PNA's 4 aggregators, GAT's edge softmax, MFC's degree banks,
# DimeNet's triplet axis) cross early; GIN/SAGE only win mildly at MXU
# widths; SchNet and EGNN never do (one already-fused scatter per layer).
DENSE_AUTO_MIN_HIDDEN = {
    "PNA": 96,
    "GAT": 96,
    "MFC": 96,
    "DimeNet": 96,
    "GIN": 192,
    "SAGE": 192,
    # CGCNN absent from THIS table: its convs run at input_dim width
    # (constant-width CGConv), so hidden_dim says nothing about where it
    # sits relative to the crossover — it gets its own rule below.
}

# CGCNN's crossover keyed on its TRUE conv width (round-4 verdict item 8,
# measured round 5 at OC20 shape): INVERSE to the hidden-width table —
# dense gathers [N, K, input_dim] blocks, so gather traffic grows with
# input width while the segment scatter cost stays flat. Maximum input_dim
# at which the dense path is picked automatically.
DENSE_AUTO_MAX_INPUT_DIM = {
    "CGCNN": 64,
}


def auto_dense_aggregation(arch_config: dict) -> bool:
    """The measured-crossover policy: dense iff the (model type, width)
    point sits on the dense-winning side of the tables above. Width is
    hidden_dim for most stacks; CGCNN's constant-width convs key on
    input_dim instead — and inversely. Absent/0 input_dim stays
    conservative: segment."""
    mt = arch_config.get("model_type")
    th_in = DENSE_AUTO_MAX_INPUT_DIM.get(mt)
    if th_in is not None:
        dim = int(arch_config.get("input_dim") or 0)
        return 1 <= dim <= th_in
    th = DENSE_AUTO_MIN_HIDDEN.get(mt)
    return th is not None and int(arch_config.get("hidden_dim") or 0) >= th


def static_aggregation_choice(arch_config: dict) -> str:
    """Policy-tier choice for a model config (no cache, no env): what
    bench.py records as ``auto_choice`` when nothing measured overrides."""
    return "dense" if auto_dense_aggregation(arch_config) else "segment"


# ---------------------------------------------------------------------------
# env overrides
# ---------------------------------------------------------------------------


def env_force() -> Optional[str]:
    """``HYDRAGNN_AGG`` when it names a valid choice, else None."""
    v = (os.getenv("HYDRAGNN_AGG") or "").strip().lower()
    return v if v in CHOICES else None


def fused_forbidden() -> bool:
    """``HYDRAGNN_FUSED_MP=0`` is the fused kill switch — it beats the
    cache AND ``HYDRAGNN_AGG=fused`` (the operator's last word when a
    cached decision misbehaves on a new jax/backend)."""
    return (os.getenv("HYDRAGNN_FUSED_MP") or "").strip() == "0"


def fused_forced() -> bool:
    return (os.getenv("HYDRAGNN_FUSED_MP") or "").strip() == "1"


# ---------------------------------------------------------------------------
# bucket signatures + on-disk cache
# ---------------------------------------------------------------------------

_STACK_KEYS = {
    "PNAStack": "PNA",
    "GINStack": "GIN",
    "GATStack": "GAT",
    "MFCStack": "MFC",
    "SAGEStack": "SAGE",
    "CGCNNStack": "CGCNN",
    "SCFStack": "SchNet",
    "EGCLStack": "EGNN",
    "DIMEStack": "DimeNet",
}


def model_key_for(model) -> str:
    """Short model key ("PNA", "SchNet", ...) from a stack instance."""
    name = type(model).__name__
    return _STACK_KEYS.get(name, name.replace("Stack", ""))


def bucket_signature(model_key: str, num_nodes: int, num_edges: int,
                     dim: int) -> str:
    """One bucket layout's identity: padded node/edge counts + feature
    width + model. These are exactly the statics a compiled program is
    specialized on, so one cached choice maps to one XLA program."""
    return f"{model_key}/n{int(num_nodes)}/e{int(num_edges)}/d{int(dim)}"


def device_kind() -> str:
    return jax.devices()[0].device_kind


def cache_path() -> str:
    """``HYDRAGNN_AUTOTUNE_CACHE`` when set, else one fixed file inside the
    checkout — a run is steered only by files in the tree."""
    p = os.getenv("HYDRAGNN_AUTOTUNE_CACHE")
    if p:
        return p
    from hydragnn_tpu.utils.compile_cache import DEFAULT_CACHE_DIR

    return os.path.join(DEFAULT_CACHE_DIR, "autotune.json")


_lock = threading.Lock()
_cache: Optional[Dict] = None
_cache_from: Optional[str] = None


def _load_cache() -> Dict:
    """Lazy singleton keyed on the active cache path (tests repoint it via
    the env var). File I/O happens OUTSIDE the lock; the lock only guards
    the singleton swap (a racing double-read is harmless — last one
    wins with identical content)."""
    global _cache, _cache_from
    path = cache_path()
    with _lock:
        if _cache is not None and _cache_from == path:
            return _cache
    try:
        with open(path) as f:
            data = json.load(f)
        if not isinstance(data.get("devices"), dict):
            raise ValueError("malformed cache")
    except (OSError, ValueError):
        data = {"version": 1, "devices": {}}
    with _lock:
        if _cache is None or _cache_from != path:
            _cache, _cache_from = data, path
        return _cache


def reset_cache_state():
    """Drop the in-process cache singleton (tests; also lets a long-lived
    process pick up an externally rewritten file)."""
    global _cache, _cache_from
    with _lock:
        _cache = None
        _cache_from = None


def cached_choice(signature: str) -> Optional[Dict]:
    return _load_cache()["devices"].get(device_kind(), {}).get(signature)


def cached_model_choice(model_key: str, width: int) -> Optional[str]:
    """Most-recent cached decision for this model AT THIS FEATURE WIDTH
    that ACTUALLY TIMED THE DENSE CANDIDATE — the loader's lookup: the
    dense-vs-segment choice is enacted at LAYOUT time (host-built
    neighbor lists), before bucket shapes exist, so a measured ``dense``
    win is applied on the next layout build. Two qualifiers keep the
    cache honest: records whose measurement never included dense (a
    segment-vs-fused-only probe) say NOTHING about dense-vs-segment, and
    the dense/segment crossover is WIDTH-dependent (CGCNN's is even
    inverse in input width), so only records measured at the config's
    own width apply. Returns None with no qualifying entry."""
    prefix = f"{model_key}/"
    suffix = f"/d{int(width)}"
    dev = _load_cache()["devices"].get(device_kind(), {})
    best = None
    for sig, rec in dev.items():
        if (
            sig.startswith(prefix)
            and sig.endswith(suffix)
            and "dense" in (rec.get("timings_ms") or {})
        ):
            if best is None or rec.get("ts", 0) > best.get("ts", 0):
                best = rec
    return None if best is None else best["choice"]


def cached_choice_same_bucket(model_key: str, num_nodes: int,
                              num_edges: int) -> Optional[Dict]:
    """Width-agnostic fallback lookup: the warmup autotune measures one
    representative width (the model's hidden_dim), while aggregation
    sites see their own table widths (layer-0 input width, EGNN's
    ``hidden+3`` pos-extended table). A decision transfers across widths
    within the same (model, padded-nodes, padded-edges) bucket — the
    scatter-vs-gather economics it measured are set by N/E, not by a few
    columns."""
    prefix = f"{model_key}/n{int(num_nodes)}/e{int(num_edges)}/"
    dev = _load_cache()["devices"].get(device_kind(), {})
    for sig, rec in dev.items():
        if sig.startswith(prefix):
            return rec
    return None


def record_choice(signature: str, choice: str, timings_ms: Optional[Dict],
                  persist: bool = True):
    data = _load_cache()
    with _lock:
        dev = data["devices"].setdefault(device_kind(), {})
        dev[signature] = {
            "choice": choice,
            "timings_ms": timings_ms or {},
            "ts": round(time.time(), 3),
        }
    if persist:
        # serialize UNDER the lock (pure CPU — a concurrent recorder
        # mutating the dict mid-dump would raise RuntimeError, which the
        # OSError guard below would not catch); write the blob outside
        with _lock:
            blob = json.dumps(data, indent=1, sort_keys=True)
        path = cache_path()
        try:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                f.write(blob)
            os.replace(tmp, path)
        except OSError:
            pass  # an unwritable cache dir must not kill training


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------

def emit_choice(signature: str, choice: str, source: str,
                timings_ms: Optional[Dict] = None, **extra):
    """One ``agg_choice`` event + ``aggregation_kernel`` gauge per novel
    (signature, choice, source) PER TELEMETRY RUN — deduplicated so
    per-trace re-decisions don't spam the stream. The dedup set lives ON
    the active RunTelemetry (not process-global, and not keyed by id() —
    a GC'd run's address gets reused), so every run's events.jsonl
    stands alone; with no run active there is nothing to emit. ``extra``
    fields ride on the event (the neighbour gather's ``gather`` / ``h``,
    ``ops/dense_agg.py``); a choice outside ``CHOICES`` names no kernel
    family and sets no gauge."""
    from hydragnn_tpu.obs import runtime as obs_rt

    run = obs_rt.active()
    if run is None:
        return
    emitted = getattr(run, "_agg_choice_emitted", None)
    if emitted is None:
        emitted = set()
        run._agg_choice_emitted = emitted
    key = (signature, choice, source)
    if key in emitted:
        return
    emitted.add(key)
    try:
        fields = {"bucket": signature, "choice": choice, "source": source}
        if timings_ms:
            fields["timings_ms"] = {
                k: round(float(v), 4) for k, v in timings_ms.items()
            }
        obs_rt.emit("agg_choice", **fields, **extra)
        if choice not in CHOICES:
            return
        # exactly ONE choice label reads 1 per bucket: a re-decision
        # (env override after a measured pass, fused->segment VMEM
        # fallback) must zero the previously-active label or dashboards
        # show two kernels live on one bucket
        for c in CHOICES:
            run.metrics.registry.set_labeled(
                "aggregation_kernel",
                1.0 if c == choice else 0.0,
                bucket=signature,
                choice=c,
            )
    except Exception:
        pass


def emit_layout_choice(model, batch):
    """Report the aggregation family the batch LAYOUT committed this
    bucket to: ``dense`` when the loader built neighbor lists (every
    dense-capable conv then takes its scatter-free branch), else
    ``segment``. Called once per traced forward (``models/base.py``), so
    the env/policy/guard decisions enacted at layout time — before any
    telemetry run exists — still show up as the path that ran."""
    dense = "nbr_idx" in (batch.extras or {})
    emit_choice(
        bucket_signature(
            model_key_for(model), batch.x.shape[0], batch.senders.shape[0],
            model.hidden_dim,
        ),
        "dense" if dense else "segment",
        "layout",
    )


# ---------------------------------------------------------------------------
# trace-time decision (the models' entry point)
# ---------------------------------------------------------------------------


def use_fused(model_key: str, num_nodes: int, num_edges: int,
              table_dim: int, out_dim: int,
              num_segments: Optional[int] = None,
              table_dim_b: int = 0) -> bool:
    """Should THIS aggregation site use the fused Pallas kernel?

    Called at trace time from the models' segment branches (shapes are
    static under jit). Applies the decision order from the module
    docstring; "fused" additionally requires the VMEM guard
    (``fused_mp.fused_mp_enabled``) to pass — an env/cache override can
    never select a config that would VMEM-OOM at compile time."""
    from hydragnn_tpu.ops.fused_mp import fused_mp_enabled

    if fused_forbidden():
        return False
    num_segments = num_nodes if num_segments is None else num_segments
    fits = fused_mp_enabled(
        num_nodes, num_segments, table_dim, out_dim, table_dim_b
    )
    sig = bucket_signature(model_key, num_nodes, num_edges, table_dim)
    forced = env_force()
    if forced is not None or fused_forced():
        wanted, source = forced or "fused", "env"
    else:
        rec = cached_choice(sig) or cached_choice_same_bucket(
            model_key, num_nodes, num_edges
        )
        if rec is None:
            # policy tier: fused is opt-in by measurement only; the
            # layout-level event (models/base.py) already reported segment
            return False
        wanted, source = rec["choice"], "cache"
    if wanted == "fused" and not fits:
        # a requested kernel the VMEM guard refuses runs on XLA — say so
        emit_choice(sig, "segment", "guard")
        return False
    # dense is a LAYOUT-time decision (host-built lists, enacted by the
    # loader via cached_model_choice); a segment-laid-out batch reaching
    # this trace-time site runs the segment path whatever the force or
    # the cache says — report what actually runs
    choice = "segment" if wanted == "dense" else wanted
    emit_choice(sig, choice, source)
    return choice == "fused"


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------


def measure_candidates(
    num_nodes: int,
    num_edges: int,
    dim: int,
    candidates: Tuple[str, ...] = ("segment", "fused"),
    iters: int = 10,
    seed: int = 0,
    interpret: Optional[bool] = None,
) -> Dict[str, float]:
    """Time each candidate's representative aggregation microbench at one
    bucket shape (ms per call). The probe is the common denominator of the
    model hot paths: gather sender rows, mask, reduce at receivers.
    On a TPU a candidate that fails to compile or run is an error: the
    compiler's message is logged as an ``agg_probe_failed`` event and the
    exception propagates — a kernel the chip refuses must not quietly lose
    to ``segment``. Off-TPU (the Pallas interpreter, tier-1) a failing
    candidate is disqualified: absent from the result."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((num_nodes, dim)), jnp.float32)
    snd = jnp.asarray(rng.integers(0, num_nodes, num_edges), jnp.int32)
    rcv = jnp.asarray(rng.integers(0, num_nodes, num_edges), jnp.int32)
    mask = jnp.asarray(rng.random(num_edges) > 0.1)

    probes = {}
    if "segment" in candidates:
        probes["segment"] = jax.jit(
            lambda x: jax.ops.segment_sum(
                jnp.where(mask[:, None], x[snd], 0.0),
                rcv,
                num_segments=num_nodes,
            )
        )
    if "fused" in candidates:
        from hydragnn_tpu.ops.fused_mp import fused_gather_sum

        kw = {} if interpret is None else {"interpret": interpret}
        probes["fused"] = jax.jit(
            lambda x: fused_gather_sum(x, snd, rcv, num_nodes, mask, **kw)
        )
    if "dense" in candidates:
        from hydragnn_tpu.ops.dense_agg import (
            build_neighbor_lists,
            dense_sum,
            max_degree,
        )

        k_in, k_out = max_degree(snd, rcv, mask)
        lists = build_neighbor_lists(
            np.asarray(snd), np.asarray(rcv), np.asarray(mask),
            num_nodes, k_in, k_out,
        )
        nbr = jnp.asarray(lists["nbr_idx"])
        nmask = jnp.asarray(lists["nbr_mask"])
        probes["dense"] = jax.jit(lambda x: dense_sum(x[nbr], nmask))

    timings = {}
    for name, fn in probes.items():
        try:
            jax.block_until_ready(fn(x))  # compile + warm
            t0 = time.perf_counter()
            out = None
            for _ in range(iters):
                out = fn(x)
            jax.block_until_ready(out)
            timings[name] = (time.perf_counter() - t0) / iters * 1e3
        except Exception as e:
            if on_tpu():
                from hydragnn_tpu.obs import runtime as obs_rt

                obs_rt.emit(
                    "agg_probe_failed",
                    bucket=f"n{num_nodes}/e{num_edges}/d{dim}",
                    candidate=name,
                    error=f"{type(e).__name__}: {e}"[:2000],
                )
                raise
            continue  # off-TPU: disqualified
    return timings


def autotune_bucket(
    model_key: str,
    num_nodes: int,
    num_edges: int,
    dim: int,
    candidates: Tuple[str, ...] = ("segment", "fused"),
    iters: int = 10,
    persist: bool = True,
    interpret: Optional[bool] = None,
) -> str:
    """Decide one bucket: cached decision if present (deterministic, no
    timing), else measure the candidates, cache and persist the winner.
    Emits the decision as an ``agg_choice`` event either way."""
    sig = bucket_signature(model_key, num_nodes, num_edges, dim)
    forced = env_force()
    if forced is not None:
        emit_choice(sig, forced, "env")
        return forced
    rec = cached_choice(sig)
    if rec is not None:
        emit_choice(sig, rec["choice"], "cache", rec.get("timings_ms"))
        return rec["choice"]
    if interpret is None:
        if not on_tpu():
            # off-TPU the fused probe runs the Pallas INTERPRETER — its
            # timing says nothing about the compiled kernel, and letting
            # emulation win a noisy microbench would flip real runs onto
            # it. Time it only where it compiles natively (or when the
            # caller explicitly asks for interpreter mode, as the CI
            # smoke does to exercise the machinery).
            candidates = tuple(c for c in candidates if c != "fused")
            if not candidates:
                candidates = ("segment",)
    timings = measure_candidates(
        num_nodes, num_edges, dim, candidates, iters=iters,
        interpret=interpret,
    )
    if not timings:
        # every probe failed — reachable off-TPU only (on a TPU
        # measure_candidates raises): the safe path
        choice = "segment"
    else:
        choice = min(timings, key=timings.get)
    record_choice(sig, choice, timings, persist=persist)
    emit_choice(sig, choice, "measured", timings)
    return choice


def maybe_autotune(model, example_batch, training_config: dict) -> Optional[str]:
    """Trainer warmup hook: autotune the example batch's bucket when
    enabled (``HYDRAGNN_AUTOTUNE=1`` or ``Training.autotune_aggregation``)
    — BEFORE the step programs trace, so the models' trace-time
    :func:`use_fused` reads a warm cache. No-op for dense-layout batches
    (the loader already committed to neighbor lists) and partitioned runs
    (per-shard lists are the partitioner's business)."""
    env = os.getenv("HYDRAGNN_AUTOTUNE")
    enabled = (
        env.strip().lower() not in ("", "0", "false", "no", "off")
        if env is not None
        else bool(training_config.get("autotune_aggregation", False))
    )
    if not enabled:
        return None
    extras = getattr(example_batch, "extras", None) or {}
    if "nbr_idx" in extras or getattr(model, "partition_axis", None):
        return None
    try:
        num_nodes = int(example_batch.x.shape[-2])
        num_edges = int(example_batch.senders.shape[-1])
    except Exception:
        return None
    dim = int(getattr(model, "hidden_dim", 0) or example_batch.x.shape[-1])
    # all three candidates: a record that never timed dense says nothing
    # about the layout decision (cached_model_choice skips it), so the
    # warmup measures the complete family — this is the one place a
    # measured "dense" win can enter the cache and steer the next
    # layout build
    return autotune_bucket(
        model_key_for(model), num_nodes, num_edges, dim,
        candidates=("segment", "dense", "fused"),
    )
