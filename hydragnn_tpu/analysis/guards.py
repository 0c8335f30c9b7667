"""Runtime correctness guards — what the static pass cannot prove.

Three harnesses, all designed for tests (cheap, no-op-safe, CPU-friendly):

- :class:`CompileSentinel` asserts the XLA compile counter stays FLAT
  across a region: warm a step function up, enter the sentinel, run an
  epoch (or a serve burst) — any recompile means a shape leaked past the
  bucketing/layout machinery, which is this stack's #1 silent perf
  regression. Counts come from the same ``jax.monitoring``
  backend-compile events the ``/metrics`` endpoint exports
  (``obs/runtime.py``), plus each tracked jitted function's own cache
  size as a second, API-stable signal.

- :func:`no_host_syncs` turns IMPLICIT device->host transfers into hard
  errors via ``jax.transfer_guard_device_to_host("disallow")``. The hot
  paths fetch results exactly once per epoch through explicit
  ``jax.device_get`` — which the guard permits — so a reintroduced
  per-batch ``float(metrics[...])`` fails the wrapped test instead of
  silently serializing the dispatch pipeline. :func:`no_implicit_transfers`
  is the stricter all-directions variant for regions that should move no
  data implicitly at all (a fully staged dispatch, a serve batch whose
  inputs are packed host-side).

- :func:`lock_sanitizer` / :class:`InstrumentedLock` — the runtime half
  of the threadlint concurrency suite (``rules_concurrency.py``). The
  static pass sees lock orders the SOURCE nests; only execution sees the
  orders call graphs compose at runtime. Instrumented locks track each
  thread's held-lock set, build the global acquisition-order graph, and
  record a :class:`LockOrderViolation` the moment any thread acquires
  against an order another thread has already established — the deadlock
  is caught on the first interleaving that could EVER deadlock, not the
  unlucky run that does. Per-lock wait/hold-time histograms export
  through a :class:`~hydragnn_tpu.obs.metrics.MetricsRegistry`, and a
  deadlock watchdog dumps every thread's stack + held locks and emits a
  ``deadlock_suspect`` event (``events.jsonl`` schema,
  ``obs/events.py``) when an acquisition blocks past its threshold.

- :func:`nan_sentinel` / :func:`nan_origin` — the runtime half of the
  numlint numerics suite (``rules_numerics.py``). Wraps a step or
  dispatch and, on any non-finite output, localizes the FIRST offending
  leaf to a named head/param subtree, emits a schema-gated
  ``nan_origin`` event, and (in raise mode) fails with the subtree
  named. Opt-in on the train path via ``HYDRAGNN_NAN_SENTINEL``; the
  canary controller's NaN hard-veto uses the report mode so every
  rejection carries an origin.
"""

import contextlib
import re
import sys
import threading
import time
import traceback
from typing import Dict, Iterable, List, Optional, Tuple

from hydragnn_tpu.obs import runtime as _obs_runtime


class RecompileError(AssertionError):
    """A tracked region compiled after its warmup promised it would not."""


class CompileSentinel:
    """Assert zero new XLA compilations across a ``with`` region.

    ``fns``: optional jitted callables; their jit-cache entry counts are
    snapshotted too, catching re-traces even where the monitoring API is
    unavailable (a re-trace that hits the persistent compile cache never
    reaches the backend, but it still inserts a fresh cache entry).

    Usage::

        warmup()                      # compile everything first
        with CompileSentinel(fns=[trainer._train_step]) as sentinel:
            run_two_epochs()
        # exiting asserts flatness; or call sentinel.assert_flat() to
        # check mid-region
    """

    def __init__(self, fns: Iterable = (), check_on_exit: bool = True):
        self.fns = list(fns)
        self.check_on_exit = check_on_exit
        self._events0: Optional[int] = None
        self._cache0: Dict[int, int] = {}

    # ---- signals -------------------------------------------------------
    @staticmethod
    def _cache_size(fn) -> Optional[int]:
        get = getattr(fn, "_cache_size", None)
        if callable(get):
            try:
                return int(get())
            except Exception:
                return None
        return None

    def __enter__(self):
        _obs_runtime.install_compile_listener()
        self._events0 = _obs_runtime.compile_events()
        self._cache0 = {}
        for i, fn in enumerate(self.fns):
            size = self._cache_size(fn)
            if size is not None:
                self._cache0[i] = size
        return self

    def new_compiles(self) -> int:
        """Backend compilations observed since ``__enter__``."""
        if self._events0 is None:
            raise RuntimeError("CompileSentinel used outside its context")
        return _obs_runtime.compile_events() - self._events0

    def new_cache_entries(self) -> int:
        """Fresh jit-cache entries on the tracked fns since entry."""
        grown = 0
        for i, fn in enumerate(self.fns):
            if i not in self._cache0:
                continue
            size = self._cache_size(fn)
            if size is not None:
                grown += max(0, size - self._cache0[i])
        return grown

    def assert_flat(self, what: str = "region"):
        compiles = self.new_compiles()
        entries = self.new_cache_entries()
        if compiles or entries:
            raise RecompileError(
                f"{what}: expected zero recompiles after warmup, saw "
                f"{compiles} backend compilation(s) and {entries} new "
                "jit-cache entr(ies) — a shape or function identity "
                "leaked past setup"
            )

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None and self.check_on_exit:
            self.assert_flat()
        return False


# ---- transfer guards ------------------------------------------------------

@contextlib.contextmanager
def no_host_syncs():
    """Hard-error any IMPLICIT device->host transfer in the region.

    Explicit fetches (``jax.device_get``) pass — they are the documented
    once-per-epoch readback. Host->device input transfers are unaffected,
    so a whole ``train_epoch`` (puts included) runs under this guard.
    """
    import jax

    with jax.transfer_guard_device_to_host("disallow"):
        yield


@contextlib.contextmanager
def no_implicit_transfers():
    """Hard-error implicit transfers in EVERY direction — for regions
    whose inputs are already device-resident (staged epochs) or packed
    host-side (a serve dispatch)."""
    import jax

    with jax.transfer_guard("disallow"):
        yield


# ---- sharding sentinel ----------------------------------------------------


class ShardingViolation(AssertionError):
    """A program output landed at a different sharding than declared."""


def _norm_spec(spec) -> tuple:
    """Canonical PartitionSpec tuple: trailing Nones stripped, so
    ``P('data')`` and ``P('data', None)`` (and a fully-replicated
    ``P()`` vs a spec-less single-device sharding) compare equal."""
    dims = list(tuple(spec))
    while dims and dims[-1] is None:
        dims.pop()
    return tuple(dims)


def _expected_spec(expected):
    """Spec tuple of one expected placement: a NamedSharding, a raw
    PartitionSpec, or anything exposing ``.spec``."""
    spec = getattr(expected, "spec", expected)
    try:
        return _norm_spec(spec)
    except TypeError:
        return None


def tree_sharding_mismatches(tree, expected) -> List[str]:
    """Human-readable mismatches between where ``tree``'s leaves LANDED
    (``leaf.sharding``) and where ``expected`` (a congruent pytree of
    ``NamedSharding``/``PartitionSpec``) declared they should.

    Leaves without a ``.sharding`` (host values) and expected entries of
    None are skipped; a single-device/spec-less sharding reads as
    replicated — declaring ``P()`` on a meshless run passes, declaring
    ``P('data')`` there correctly reports the shard that never happened.
    """
    import jax

    mismatches: List[str] = []

    def chk(path, leaf, exp):
        sh = getattr(leaf, "sharding", None)
        if sh is None or exp is None:
            return leaf
        want = _expected_spec(exp)
        if want is None:
            return leaf
        got = _norm_spec(getattr(sh, "spec", ()))
        if got != want:
            name = jax.tree_util.keystr(path)
            mismatches.append(
                f"{name}: landed at {got or 'replicated'}, "
                f"declared {want or 'replicated'}"
            )
        return leaf

    jax.tree_util.tree_map_with_path(chk, tree, expected)
    return mismatches


class ShardingSentinel:
    """Assert program outputs LAND at their declared shardings — the
    runtime sibling of :class:`CompileSentinel` for the 2-D mesh era and
    of the static ``jit-missing-shardings`` rule: the lint proves the
    contract is *written*, this proves execution *honors* it (a
    ``with_sharding_constraint`` dropped in a refactor still compiles
    and still converges — it just reshards on every consumer).

    Usage::

        state, metrics = trainer._train_step(state, batch, rng)
        with sharding_sentinel() as sen:
            sen.check(state, trainer._state_shardings, what="train_step")
        # or standalone: ShardingSentinel().check(...) raises directly
    """

    def __init__(self):
        self.violations: List[str] = []

    def check(self, tree, expected, what: str = "outputs", defer=False):
        """Compare ``tree``'s landed shardings against ``expected``;
        raises :class:`ShardingViolation` (or records, with
        ``defer=True``, for :meth:`assert_clean` at context exit)."""
        mism = [
            f"{what}: {m}" for m in tree_sharding_mismatches(tree, expected)
        ]
        if not mism:
            return
        self.violations.extend(mism)
        if not defer:
            self._raise()

    def _raise(self):
        raise ShardingViolation(
            f"{len(self.violations)} output(s) landed off their declared "
            "sharding — an implicit reshard every consumer pays for:\n  "
            + "\n  ".join(self.violations)
        )

    def assert_clean(self):
        if self.violations:
            self._raise()


@contextlib.contextmanager
def sharding_sentinel(check_on_exit: bool = True):
    """Context harness: ``check(..., defer=True)`` inside the region,
    one :class:`ShardingViolation` listing everything at exit."""
    sen = ShardingSentinel()
    yield sen
    if check_on_exit:
        sen.assert_clean()


# ---- lock sanitizer -------------------------------------------------------

# lock waits/holds live well below the serving-latency bounds: critical
# sections are microseconds when healthy, and the interesting tail is
# "someone slept under a lock" (ms) through "deadlock suspect" (s)
LOCK_LATENCY_BOUNDS = (
    1e-6, 5e-6, 1e-5, 5e-5, 1e-4, 5e-4, 1e-3, 5e-3,
    0.01, 0.05, 0.1, 0.5, 1.0, 5.0,
)

_METRIC_SAFE_RE = re.compile(r"[^A-Za-z0-9_]")


class LockOrderViolation(AssertionError):
    """Two locks were acquired in opposite orders by live code paths."""


def _call_site() -> str:
    """'file.py:123 in fn' for the first frame outside this module."""
    for frame in reversed(traceback.extract_stack()):
        if frame.filename != __file__:
            return f"{frame.filename}:{frame.lineno} in {frame.name}"
    return "<unknown>"


def _thread_dump(held: Dict[int, List[str]]) -> List[Dict]:
    """One JSON-able record per live thread: name, held locks, stack."""
    frames = sys._current_frames()
    threads = []
    for t in threading.enumerate():
        frame = frames.get(t.ident)
        stack = (
            [
                f"{f.filename}:{f.lineno} in {f.name}"
                for f in traceback.extract_stack(frame)
            ]
            if frame is not None
            else []
        )
        threads.append(
            {
                "name": t.name,
                "ident": t.ident,
                "daemon": t.daemon,
                "held_locks": list(held.get(t.ident, ())),
                "stack": stack,
            }
        )
    return threads


class InstrumentedLock:
    """Drop-in ``threading.Lock``/``RLock`` wrapper reporting to a
    :class:`LockSanitizer`. Same surface as the stdlib lock (``with``,
    ``acquire(blocking=, timeout=)``, ``release``, ``locked``), so
    production classes can take a lock *factory* and tests can inject
    ``sanitizer.lock`` without touching the code under test."""

    def __init__(self, sanitizer: "LockSanitizer", name: str, inner):
        self._san = sanitizer
        self.name = name
        self._inner = inner

    def acquire(self, blocking: bool = True, timeout: float = -1):
        self._san._note_wait(self.name, blocking)
        t0 = time.monotonic()
        if not blocking:
            ok = self._inner.acquire(False)
        else:
            ok = self._acquire_watched(timeout, t0)
        if ok:
            self._san._note_acquired(
                self.name, time.monotonic() - t0, blocking
            )
        return ok

    def _acquire_watched(self, timeout: float, t0: float) -> bool:
        wd = self._san.watchdog_s
        if wd is None:
            return self._inner.acquire(True, timeout)
        # first try inside the watchdog window; on expiry dump + emit,
        # then keep blocking for the remainder — the watchdog REPORTS a
        # suspected deadlock, it does not turn one into a TimeoutError.
        # A caller timeout SHORTER than the threshold can never reach
        # it: timing out there is the caller's normal control flow, not
        # a deadlock suspect
        first = wd if timeout < 0 else min(wd, timeout)
        if self._inner.acquire(True, first):
            return True
        waited = time.monotonic() - t0
        if timeout < 0 or timeout >= wd:
            self._san._fire_watchdog(self.name, waited)
        if timeout < 0:
            return self._inner.acquire(True, -1)
        remaining = timeout - waited
        if remaining <= 0:
            return False
        return self._inner.acquire(True, remaining)

    def release(self):
        self._san._note_release(self.name)
        self._inner.release()

    def locked(self) -> bool:
        return self._inner.locked()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.release()
        return False


class LockSanitizer:
    """Tracks per-thread held-lock sets across every
    :class:`InstrumentedLock` it issued.

    - **order graph**: first acquisition of B while holding A records the
      edge A->B (with its call site). Acquiring A while ANY path B->..->A
      already exists in the graph is an order inversion: two threads
      running the two paths concurrently can deadlock. Recorded into
      :attr:`violations` (and raised on :func:`lock_sanitizer` exit).
    - **metrics**: per-lock wait/hold-time histograms into ``registry``
      (``lock_wait_seconds_<name>`` / ``lock_hold_seconds_<name>``).
    - **watchdog**: an acquisition blocked past ``watchdog_s`` dumps all
      thread stacks + held locks into :attr:`deadlock_suspects` and
      emits a ``deadlock_suspect`` event to ``event_log``.
    """

    def __init__(
        self,
        registry=None,
        watchdog_s: Optional[float] = None,
        event_log=None,
    ):
        self.registry = registry
        self.watchdog_s = watchdog_s
        self.event_log = event_log
        self.violations: List[Dict] = []
        self.deadlock_suspects: List[Dict] = []
        self._mu = threading.Lock()
        self._edges: Dict[Tuple[str, str], str] = {}  # (a, b) -> site
        self._succ: Dict[str, List[str]] = {}  # edge adjacency, cached
        self._held: Dict[int, List[str]] = {}  # ident -> acquisition order
        self._acquired_at: Dict[Tuple[int, str], float] = {}

    # ---- lock factories ------------------------------------------------
    def lock(self, name: str) -> InstrumentedLock:
        return InstrumentedLock(self, name, threading.Lock())

    def rlock(self, name: str) -> InstrumentedLock:
        return InstrumentedLock(self, name, threading.RLock())

    def wrap(self, name: str, inner) -> InstrumentedLock:
        """Instrument an existing lock object (e.g. swap a server's
        ``_pending_lock`` in a test without rebuilding the server)."""
        return InstrumentedLock(self, name, inner)

    # ---- recording (called by InstrumentedLock) ------------------------
    def _note_wait(self, name: str, blocking: bool):
        """Pre-acquire inversion check. Non-blocking attempts are exempt
        by construction: a trylock never waits, so it can never be the
        blocked edge of a deadlock cycle — flagging the standard
        trylock-avoidance idiom would be a false positive. The call site
        is only captured when a violation is actually appended (stack
        extraction is too expensive for every acquire)."""
        if not blocking:
            return
        ident = threading.get_ident()
        with self._mu:
            held = self._held.get(ident, [])
            for h in held:
                if h == name:  # reentrant re-acquire: no new ordering
                    return
            for h in held:
                path = self._find_path(name, h)
                if path is not None:
                    chain = " -> ".join(path)
                    first_site = self._edges.get(
                        (path[0], path[1]), "<unknown>"
                    )
                    self.violations.append(
                        {
                            "thread": threading.current_thread().name,
                            "holding": h,
                            "acquiring": name,
                            "reverse_chain": chain,
                            "site": _call_site(),
                            "first_seen_site": first_site,
                        }
                    )

    def _note_acquired(self, name: str, waited_s: float, blocking: bool):
        """Post-acquire bookkeeping. Order edges are recorded HERE, not
        pre-wait: a timed-out acquire must leave no phantom edge behind,
        and only a blocking nest establishes an ordering another thread
        could deadlock against (trylocks join the held set for dump and
        later-edge purposes, but record no edge of their own)."""
        ident = threading.get_ident()
        with self._mu:
            held = self._held.setdefault(ident, [])
            first_hold = name not in held
            if blocking and first_hold:
                new = [h for h in held if (h, name) not in self._edges]
                if new:
                    site = _call_site()
                    for h in new:
                        self._edges[(h, name)] = site
                        self._succ.setdefault(h, []).append(name)
            held.append(name)
            if first_hold:
                # reentrant re-acquires must NOT reset the clock: the
                # hold histogram measures the OUTERMOST hold
                self._acquired_at[(ident, name)] = time.monotonic()
        self._observe(f"lock_wait_seconds_{self._safe(name)}", waited_s)

    def _note_release(self, name: str):
        ident = threading.get_ident()
        held_s = None
        with self._mu:
            held = self._held.get(ident, [])
            if name in held:
                # remove the LAST occurrence (reentrant locks nest)
                held.reverse()
                held.remove(name)
                held.reverse()
                if name not in held:
                    t0 = self._acquired_at.pop((ident, name), None)
                    if t0 is not None:
                        held_s = time.monotonic() - t0
                if not held:
                    self._held.pop(ident, None)
        if held_s is not None:
            self._observe(
                f"lock_hold_seconds_{self._safe(name)}", held_s
            )

    def _fire_watchdog(self, name: str, waited_s: float):
        with self._mu:
            held_snapshot = {k: list(v) for k, v in self._held.items()}
        payload = {
            "lock": name,
            "waited_s": round(waited_s, 6),
            "threads": _thread_dump(held_snapshot),
        }
        with self._mu:
            self.deadlock_suspects.append(payload)
        if self.event_log is not None:
            self.event_log.emit("deadlock_suspect", **payload)

    # ---- helpers -------------------------------------------------------
    def _find_path(self, src: str, dst: str) -> Optional[List[str]]:
        """BFS path src -> dst through recorded edges (caller holds
        ``_mu``; ``_succ`` is maintained on edge insert)."""
        if src == dst:
            return [src]
        succ = self._succ
        frontier = [[src]]
        seen = {src}
        while frontier:
            path = frontier.pop(0)
            for nxt in succ.get(path[-1], ()):
                if nxt == dst:
                    return path + [nxt]
                if nxt not in seen:
                    seen.add(nxt)
                    frontier.append(path + [nxt])
        return None

    @staticmethod
    def _safe(name: str) -> str:
        return _METRIC_SAFE_RE.sub("_", name)

    def _observe(self, metric: str, seconds: float):
        if self.registry is None:
            return
        try:
            self.registry.observe(metric, seconds)
        except KeyError:
            try:
                self.registry.histogram(
                    metric,
                    "lock sanitizer latency",
                    bounds=LOCK_LATENCY_BOUNDS,
                )
            except ValueError:
                pass  # lost a declare race — the metric exists now
            self.registry.observe(metric, seconds)

    def assert_clean(self):
        """Raise :class:`LockOrderViolation` if any inversion was seen."""
        with self._mu:
            violations = list(self.violations)
        if violations:
            v = violations[0]
            raise LockOrderViolation(
                f"{len(violations)} lock order inversion(s): thread "
                f"{v['thread']!r} acquired `{v['acquiring']}` while "
                f"holding `{v['holding']}` at {v['site']}, but the "
                f"reverse order ({v['reverse_chain']}) was established "
                f"at {v['first_seen_site']}"
            )


# ---- NaN sentinel ---------------------------------------------------------
#
# The runtime half of the numerics suite (rules_numerics.py): the static
# rules prove exp/log/div/gather sites are *written* guarded; this
# localizes the first non-finite value an execution actually produces to
# a named head/param subtree, so a canary NaN veto or a diverged step
# says "pos_MAE head" instead of "somewhere in a 2000-leaf tree".


class NonFiniteError(FloatingPointError):
    """A sentinel-wrapped region produced NaN/Inf; the message and the
    attached :attr:`origin` payload localize the first offending leaf."""

    def __init__(self, message: str, origin: Dict):
        super().__init__(message)
        self.origin = origin


def nonfinite_report(tree) -> List[Tuple[str, int]]:
    """``(keystr_path, nonfinite_count)`` for every leaf of ``tree``
    holding at least one NaN/Inf, in deterministic tree order. Host
    scalars and non-numeric leaves count as finite."""
    import jax
    import numpy as np

    bad: List[Tuple[str, int]] = []

    def visit(path, leaf):
        try:
            arr = np.asarray(leaf)
        except Exception:
            return leaf
        if not np.issubdtype(arr.dtype, np.floating) and not np.issubdtype(
            arr.dtype, np.complexfloating
        ):
            return leaf
        n = int(np.size(arr) - np.sum(np.isfinite(arr)))
        if n:
            bad.append((jax.tree_util.keystr(path) or "<root>", n))
        return leaf

    jax.tree_util.tree_map_with_path(visit, tree)
    return bad


def _subtree_of(keystr_path: str) -> str:
    """First NAMED path component — the head/param group to blame.
    Bare sequence indices (a step's ``(state, metrics)`` tuple) and the
    generic ``params``/``opt_state`` containers are skipped so
    ``"[0].params['encoder_conv_0']['bias']"`` blames ``encoder_conv_0``,
    not ``0``; ``".loss['energy']"`` -> ``loss``."""
    parts = [
        part
        for part in re.split(r"[\[\].']+", keystr_path)
        if part and part != "<root>" and not part.isdigit()
    ]
    for part in parts:
        if part not in ("params", "opt_state", "state"):
            return part
    return parts[0] if parts else keystr_path


def nan_origin(tree, scope: str) -> Optional[Dict]:
    """Localize non-finite leaves of ``tree`` to a ``nan_origin`` event
    payload (``obs/events.py`` schema), or None when all-finite.

    ``origin`` is the FIRST offending leaf's keystr path, ``subtree``
    its leading component, ``leaves``/``total`` the non-finite/total
    leaf counts. Forces a device sync — diagnosis-path only, never on
    the hot path."""
    import jax

    bad = nonfinite_report(tree)
    if not bad:
        return None
    first_path, _ = bad[0]
    return {
        "scope": scope,
        "origin": first_path,
        "subtree": _subtree_of(first_path),
        "leaves": len(bad),
        "total": len(jax.tree_util.tree_leaves(tree)),
    }


def nan_sentinel(fn, *, scope: str, events=None, mode: str = "raise"):
    """Wrap a step/dispatch: when its output tree contains NaN/Inf,
    build the :func:`nan_origin` payload, emit a schema-gated
    ``nan_origin`` event to ``events`` (a
    :class:`~hydragnn_tpu.obs.events.RunEventLog`, optional) and — in
    ``mode="raise"`` — raise :class:`NonFiniteError` naming the subtree.
    ``mode="report"`` returns the output untouched after emitting, for
    paths with their own rejection machinery (the canary veto).

    The finiteness check is a host readback of the outputs, so only wrap
    opt-in (``HYDRAGNN_NAN_SENTINEL=1`` in ``train/steps.py``) or on
    already-host-bound paths."""
    if mode not in ("raise", "report"):
        raise ValueError(f"nan_sentinel mode {mode!r}: raise|report")

    def wrapped(*args, **kwargs):
        out = fn(*args, **kwargs)
        origin = nan_origin(out, scope)
        if origin is not None:
            if events is not None:
                events.emit("nan_origin", **origin)
            if mode == "raise":
                raise NonFiniteError(
                    f"{scope}: non-finite output at {origin['origin']} "
                    f"(subtree `{origin['subtree']}`, "
                    f"{origin['leaves']}/{origin['total']} leaf/leaves "
                    "affected)",
                    origin,
                )
        return out

    wrapped.__name__ = getattr(fn, "__name__", "wrapped")
    # forward the jit surface (lowering/ratchet harnesses, compile
    # sentinel cache signal) so wrapping a jitted step stays transparent
    for attr in ("lower", "_cache_size"):
        inner = getattr(fn, attr, None)
        if inner is not None:
            setattr(wrapped, attr, inner)
    return wrapped


@contextlib.contextmanager
def lock_sanitizer(
    registry=None,
    watchdog_s: Optional[float] = None,
    event_log=None,
    check_on_exit: bool = True,
):
    """Context harness for tests::

        with lock_sanitizer(watchdog_s=0.5) as san:
            server._pending_lock = san.wrap("pending", threading.Lock())
            ... drive the server from several threads ...
        # exit raises LockOrderViolation on any inversion seen

    ``registry`` (a :class:`~hydragnn_tpu.obs.metrics.MetricsRegistry`)
    receives per-lock wait/hold histograms; ``event_log`` (a
    :class:`~hydragnn_tpu.obs.events.RunEventLog`) receives
    ``deadlock_suspect`` events from the watchdog."""
    san = LockSanitizer(
        registry=registry, watchdog_s=watchdog_s, event_log=event_log
    )
    yield san
    if check_on_exit:
        san.assert_clean()
