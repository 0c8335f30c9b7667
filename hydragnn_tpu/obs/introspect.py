"""Eyes into the compiled program: cost/memory accounting + trace capture.

The telemetry layer (PR 3) reports wall-clock and throughput; jaxlint
(PR 4) catches anti-patterns — but neither can say what XLA actually
*compiled*, which is where "why is this step slow" and "how much HBM does
this bucket cost" live. This module closes that gap with three pieces:

- :func:`instrument` wraps a jitted program so that every NOVEL shape
  signature (= every bucket) gets its compiled executable's
  ``cost_analysis()`` (FLOPs, bytes accessed) and ``memory_analysis()``
  (argument/output/temp/peak bytes) captured once, recorded process-wide
  (:func:`captured`) and — when a telemetry run is active — emitted as a
  ``compile`` event and exported as
  ``hydragnn_train_flops_per_step{bucket=...}`` /
  ``hydragnn_train_hbm_peak_bytes{bucket=...}`` gauges.
- :class:`TraceCapture` arms ``jax.profiler`` device-trace capture for
  the next N steps of a LIVE run — driven by ``/profile?steps=N`` on the
  observability endpoint or ``HYDRAGNN_PROFILE_AT_STEP=<epoch>:<step>``.
- :class:`Profiler` — the wait/warmup/active step schedule absorbed from
  ``utils/profile.py`` (which is now a deprecation shim); the schedule is
  the reference-parity surface, :class:`TraceCapture` the on-demand one.

Cost model: detection of a fresh compile is ONE ``_cache_size()`` read
per dispatch (the same signal ``analysis/guards.CompileSentinel`` uses),
so the steady-state overhead of an instrumented program is a global read
and an int compare. The analysis itself runs the AOT
``lower().compile()`` path once per novel signature — with the
persistent compile cache (``utils/compile_cache``, enabled by every
Trainer front door) the backend compile is absorbed and only tracing is
re-paid, at warmup, never in steady state. When no telemetry is active
and ``HYDRAGNN_INTROSPECT`` does not force it, the wrapper is a pure
passthrough.
"""

import hashlib
import os
import threading
import warnings
from typing import Callable, Dict, List, Optional, Tuple

_FALSY = ("", "0", "false", "no", "off")


def enabled() -> bool:
    """Introspection live? Default: exactly when a telemetry run is
    active. ``HYDRAGNN_INTROSPECT=0`` kills it even then (a hot path that
    cannot afford the per-dispatch cache-size read); ``=1`` forces it on
    with no telemetry run (serving, benchmarks — records still land in
    :func:`captured`)."""
    env = os.getenv("HYDRAGNN_INTROSPECT")
    if env is not None:
        return env.strip().lower() not in _FALSY
    from hydragnn_tpu.obs import runtime as _rt

    return _rt.active() is not None


# ---- compiled-program analysis -------------------------------------------


def normalize_cost_analysis(cost) -> Dict[str, float]:
    """``Compiled.cost_analysis()`` -> a flat, JSON-able dict. jax returns
    a list of one dict on some versions, a plain dict on others, None on
    backends without a cost model; key spellings vary ('bytes accessed').
    """
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    if not cost:
        return {}
    out = {}
    for key, new in (
        ("flops", "flops"),
        ("bytes accessed", "bytes_accessed"),
        ("transcendentals", "transcendentals"),
    ):
        v = cost.get(key)
        if v is not None:
            out[new] = float(v)
    return out


def normalize_memory_analysis(mem) -> Dict[str, float]:
    """``Compiled.memory_analysis()`` -> flat dict with a derived
    ``peak_bytes`` (argument + output + temp + generated code − aliased:
    the executable's worst-case simultaneous HBM footprint, the figure
    the budget ratchet tracks). Returns {} when the backend reports
    nothing."""
    if mem is None:
        return {}
    out = {}
    for attr, new in (
        ("argument_size_in_bytes", "argument_bytes"),
        ("output_size_in_bytes", "output_bytes"),
        ("temp_size_in_bytes", "temp_bytes"),
        ("alias_size_in_bytes", "alias_bytes"),
        ("generated_code_size_in_bytes", "generated_code_bytes"),
    ):
        v = getattr(mem, attr, None)
        if v is not None:
            out[new] = float(v)
    if out:
        out["peak_bytes"] = max(
            out.get("argument_bytes", 0.0)
            + out.get("output_bytes", 0.0)
            + out.get("temp_bytes", 0.0)
            + out.get("generated_code_bytes", 0.0)
            - out.get("alias_bytes", 0.0),
            0.0,
        )
    return out


def analyze_compiled(compiled) -> Tuple[Dict[str, float], Dict[str, float]]:
    """(cost, memory) dicts for one ``jax.stages.Compiled``."""
    try:
        cost = normalize_cost_analysis(compiled.cost_analysis())
    except Exception:
        cost = {}
    try:
        mem = normalize_memory_analysis(compiled.memory_analysis())
    except Exception:
        mem = {}
    return cost, mem


def signature_key(args, kwargs=None) -> Tuple:
    """Hashable (treedef, per-leaf shape/dtype) signature — the same
    notion of "bucket" the jit cache keys on."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs or {}))
    sig = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            sig.append((tuple(shape), str(dtype)))
        else:
            sig.append(repr(leaf))
    return (str(treedef), tuple(sig))


def bucket_label(name: str, key: Tuple) -> str:
    """Stable short id for one (program, shape signature): the gauge's
    ``bucket`` label and the budget ratchet's key. hashlib, not hash() —
    must agree across processes and runs."""
    digest = hashlib.sha1(repr(key).encode()).hexdigest()[:8]
    return f"{name}/{digest}"


# ambient mesh context (parallel/mesh.py registers it): lets the capture
# attribute collective bytes in the compiled HLO to mesh axes
_mesh_axes: Optional[Tuple[str, ...]] = None
_mesh_shape: Optional[Tuple[int, ...]] = None


def set_mesh_context(axes, shape):
    """Register (or clear, with Nones) the active mesh's axis names and
    shape for collective-byte attribution."""
    global _mesh_axes, _mesh_shape
    _mesh_axes = tuple(axes) if axes else None
    _mesh_shape = tuple(int(s) for s in shape) if shape else None


def mesh_context():
    return _mesh_axes, _mesh_shape


def _collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-axis collective result bytes of one compiled module's text ({}
    without a registered mesh or on parse failure — accounting must
    never break a capture)."""
    if _mesh_axes is None or _mesh_shape is None:
        return {}
    try:
        from hydragnn_tpu.parallel.collectives import collective_bytes_by_axis

        return collective_bytes_by_axis(hlo_text, _mesh_axes, _mesh_shape)
    except Exception:
        return {}


# process-global record of every captured compile — serving and benches
# read this even with no telemetry run active
_captured: List[Dict] = []
_captured_lock = threading.Lock()


def captured(name: Optional[str] = None) -> List[Dict]:
    """Compile records captured so far (optionally for one program)."""
    with _captured_lock:
        recs = list(_captured)
    if name is not None:
        recs = [r for r in recs if r["name"] == name]
    return recs


def reset_captured():
    with _captured_lock:
        _captured.clear()


def _record(rec: Dict):
    with _captured_lock:
        _captured.append(rec)
    from hydragnn_tpu.obs import runtime as _rt

    t = _rt.active()
    if t is not None:
        t.record_compile(rec)


class InstrumentedJit:
    """Transparent wrapper over one jitted program.

    Dispatch goes STRAIGHT to the wrapped jit; after each call, if the
    jit's signature cache grew (a fresh trace+compile just happened), the
    executable for THIS call's signature is analyzed once via the AOT
    path and recorded. Attribute access (``.lower``, ``._cache_size``,
    ...) forwards to the wrapped jit, so existing callers — benchmarks'
    ``_train_step.lower(...)``, the recompile sentinel's cache probe —
    see the program they always saw.
    """

    def __init__(self, name: str, fn: Callable,
                 on_capture: Optional[Callable[[Dict], None]] = None):
        self._name = name
        self._fn = fn
        self._on_capture = on_capture
        self._ncached = None  # jit cache size at last capture check
        self._keys_seen = set()
        self._warned = False

    def __call__(self, *args, **kwargs):
        if not enabled():
            return self._fn(*args, **kwargs)
        out = self._fn(*args, **kwargs)
        try:
            n = self._fn._cache_size()
        except Exception:
            n = None
        if n is not None and n != self._ncached:
            self._ncached = n
            self._capture(args, kwargs)
        return out

    def __getattr__(self, attr):
        return getattr(self._fn, attr)

    def _capture(self, args, kwargs):
        """Analyze the executable for this call's signature; never raises
        into the training loop."""
        try:
            key = signature_key(args, kwargs)
            if key in self._keys_seen:
                return
            self._keys_seen.add(key)
            compiled = self._fn.lower(*args, **kwargs).compile()
            cost, mem = analyze_compiled(compiled)
            hlo_text = compiled.as_text()
            rec = {
                "name": self._name,
                "bucket": bucket_label(self._name, key),
                "cost": cost,
                "memory": mem,
                "collectives": _collective_bytes(hlo_text),
                # Pallas/Mosaic kernel call sites in the compiled module:
                # says whether a kernel or its XLA stand-in executed
                "kernels": hlo_text.count('custom_call_target="tpu_custom_call"'),
            }
            _record(rec)
            if self._on_capture is not None:
                self._on_capture(rec)
        except Exception as e:
            if not self._warned:
                self._warned = True
                warnings.warn(
                    f"introspection capture failed for {self._name!r}: {e} "
                    "(further failures for this program are silent)",
                    stacklevel=2,
                )


def instrument(name: str, fn: Callable,
               on_capture: Optional[Callable[[Dict], None]] = None):
    """Wrap a jitted program for compile-time accounting."""
    return InstrumentedJit(name, fn, on_capture=on_capture)


# ---- on-demand trace capture ---------------------------------------------


def _start_device_trace(trace_dir: str):
    """ONE trace-startup sequence for both capture styles (on-demand
    TraceCapture and the scheduled Profiler) — jax.profiler resolved at
    call time so test fakes apply."""
    import jax.profiler

    os.makedirs(trace_dir, exist_ok=True)
    jax.profiler.start_trace(trace_dir)


def _stop_device_trace():
    import jax.profiler

    jax.profiler.stop_trace()


class TraceCapture:
    """Arm ``jax.profiler`` device tracing for the next N steps of a live
    run. ``arm()`` is called from any thread (the ``/profile`` HTTP
    handler); ``tick()`` is called once per step from the training thread
    and owns every profiler start/stop — the jax profiler is
    process-global and must not be driven from two threads."""

    def __init__(self, trace_dir: str):
        self.trace_dir = trace_dir
        self._lock = threading.Lock()
        self._armed_steps = 0
        self._remaining = 0
        self._tracing = False

    def arm(self, steps: int) -> Dict:
        """Request capture of the next ``steps`` steps. Returns the
        ``/profile`` response payload."""
        steps = int(steps)
        if steps <= 0:
            return {"status": "error", "error": "steps must be >= 1"}
        with self._lock:
            if self._tracing or self._armed_steps:
                return {
                    "status": "busy",
                    "remaining_steps": self._remaining or self._armed_steps,
                    "trace_dir": self.trace_dir,
                }
            self._armed_steps = steps
        return {
            "status": "armed",
            "steps": steps,
            "trace_dir": self.trace_dir,
        }

    def tick(self) -> Optional[Dict]:
        """Advance one step; returns a ``profile`` event payload on the
        started/done transitions, else None. Profiler failures (e.g.
        another jax.profiler session already active) surface as an
        ``error`` payload — never as an exception into the training
        loop."""
        with self._lock:
            if self._armed_steps:
                steps, self._armed_steps = self._armed_steps, 0
                try:
                    self._start()
                except Exception as e:
                    return {
                        "status": "error",
                        "error": str(e),
                        "trace_dir": self.trace_dir,
                    }
                self._remaining = steps
                self._tracing = True
                return {
                    "status": "started",
                    "steps": steps,
                    "trace_dir": self.trace_dir,
                }
            if self._tracing:
                self._remaining -= 1
                if self._remaining <= 0:
                    self._tracing = False
                    try:
                        self._stop()
                    except Exception as e:
                        return {
                            "status": "error",
                            "error": str(e),
                            "trace_dir": self.trace_dir,
                        }
                    return {"status": "done", "trace_dir": self.trace_dir}
        return None

    def close(self) -> Optional[Dict]:
        """Stop an open trace (run teardown) so a mid-capture shutdown
        still flushes a loadable trace."""
        with self._lock:
            if not self._tracing:
                return None
            self._tracing = False
            self._remaining = 0
            try:
                self._stop()
            except Exception as e:
                return {
                    "status": "error",
                    "error": str(e),
                    "trace_dir": self.trace_dir,
                }
            return {"status": "done", "trace_dir": self.trace_dir}

    def _start(self):
        _start_device_trace(self.trace_dir)

    def _stop(self):
        _stop_device_trace()


def parse_profile_at_step(value: Optional[str]) -> Optional[Tuple[int, int]]:
    """``HYDRAGNN_PROFILE_AT_STEP`` -> (epoch, step): ``"<epoch>:<step>"``
    or a bare ``"<step>"`` (epoch 0). None/malformed -> None (malformed
    warns — a typo'd arm target silently never firing is the worst
    outcome for a knob you set before a 6-hour run)."""
    if value is None or not value.strip():
        return None
    try:
        parts = value.split(":")
        if len(parts) == 1:
            return (0, int(parts[0]))
        if len(parts) == 2:
            return (int(parts[0]), int(parts[1]))
    except ValueError:
        pass
    warnings.warn(
        f"HYDRAGNN_PROFILE_AT_STEP={value!r} is not '<epoch>:<step>' or "
        "'<step>' — profiling will not arm",
        stacklevel=2,
    )
    return None


# ---- reference-parity step schedule (absorbed from utils/profile.py) -----


class Profiler:
    """Step-scheduled device tracing for TensorBoard.

    Parity with the reference's ``Profiler(torch.profiler.profile)``
    (``hydragnn/utils/profile.py:9-70``): a wait/warmup/active step
    schedule, a target-epoch gate, TensorBoard-consumable output, and a
    no-op object when disabled so call sites stay unconditional. The
    backend is ``jax.profiler`` (XLA device traces, viewable in
    TensorBoard's profile plugin or perfetto).

    Lives here since the introspection PR; ``hydragnn_tpu.utils.profile``
    re-exports it as a deprecation shim. For profiling a LIVE run without
    a pre-planned schedule, use ``/profile?steps=N`` on the observability
    endpoint (:class:`TraceCapture`) instead.
    """

    def __init__(
        self,
        trace_dir: str = "./logs/profile",
        wait: int = 5,
        warmup: int = 3,
        active: int = 3,
        target_epoch: Optional[int] = 1,
    ):
        self.trace_dir = trace_dir
        self.wait = wait
        self.warmup = warmup
        self.active = active
        self.target_epoch = target_epoch
        self.enabled = False
        self._epoch = None
        self._step = 0
        self._tracing = False

    def setup(self, config: dict):
        """Config section ``{"Profile": {"enable": 1, "trace_dir": ...}}``
        (reference reads ``config["Profile"]``, ``profile.py:22-29``)."""
        if not config:
            return
        self.enabled = bool(config.get("enable", 0))
        self.trace_dir = config.get("trace_dir", self.trace_dir)
        self.wait = int(config.get("wait", self.wait))
        self.warmup = int(config.get("warmup", self.warmup))
        self.active = int(config.get("active", self.active))
        self.target_epoch = config.get("target_epoch", self.target_epoch)

    def set_current_epoch(self, epoch: int):
        self._epoch = epoch

    def _armed(self) -> bool:
        if not self.enabled:
            return False
        return self.target_epoch is None or self._epoch == self.target_epoch

    # -- context manager ---------------------------------------------------
    def __enter__(self):
        self._step = 0
        return self

    def __exit__(self, *exc):
        self._stop_trace()
        return False

    def step(self):
        """Advance the schedule; starts/stops the device trace at the
        wait→warmup→active window boundaries."""
        if not self._armed():
            return
        self._step += 1
        # trace through warmup+active, discard-by-convention the warmup part
        if self._step == self.wait + 1:
            self._start_trace()
        elif self._step == self.wait + self.warmup + self.active + 1:
            self._stop_trace()

    def _start_trace(self):
        if self._tracing:
            return
        _start_device_trace(self.trace_dir)
        self._tracing = True

    def _stop_trace(self):
        if not self._tracing:
            return
        _stop_device_trace()
        self._tracing = False


def record_function(name: str):
    """Annotation context (torch.profiler.record_function analog) — shows
    up inside the XLA trace timeline."""
    import jax.profiler

    return jax.profiler.TraceAnnotation(name)
