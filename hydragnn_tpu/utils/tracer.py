"""The span recorder of the training path.

One implementation behind the region facade of the reference
(``hydragnn/utils/tracer.py:18-171``: ``initialize / start / stop / profile
/ save / reset / enable / disable``) and behind ``span(name, **counts)``,
which any thread may use. A :class:`Span` keeps its name, the name of the
thread that opened it, start and end on ``time.perf_counter_ns()``, its own
id, the id of the span that was open ON THE SAME THREAD when it started (0
for a root; every thread has its own stack) and a small dict of counts
given at open or close.

Closed spans go to a bounded in-memory ring and to running totals keyed by
call-tree path (``train/train_step``); nothing is written before
:func:`save`. The ring holds ``RING_SPANS`` = 65,536 records: the PNA cell
of the benchmark closes about 130 spans an epoch of 1.46 s, so 60 s are some
5,400 records and the ring spans about ten minutes of it. Ring and totals
are module state: they outlive a telemetry run and ``jax.clear_caches()``,
and only :func:`reset` empties them.

While the recorder is live every span is also a
``jax.profiler.TraceAnnotation(name, id=..., parent=...)``, so any profiler
session (``TraceCapture``, ``/profile?steps=N``, ``HYDRAGNN_PROFILE_AT_STEP``,
a benchmark's own) holds the program's spans on the device trace's clock
without anyone asking; with no session running that is a flag check (0.6 us
a span, sandbox CPU). The anchor pair ``(time.time_ns(),
time.perf_counter_ns())`` taken at the first :func:`initialize` converts
ring times to the wall clock; a profiler trace counts from its session's
start on that clock.

``HYDRAGNN_TRACE_LEVEL=1``, read once at :func:`initialize`, makes
``start`` / ``stop`` (not ``span``) wait for the device at the region's
boundaries: the ``block_until_ready`` analog of the reference's
cudasync+barrier (``tracer.py:110-131``), for honest attribution of work
dispatched asynchronously. ``span`` never waits: it marks host-side stages,
and a producer thread that waited for the device at each boundary would
serialise the pipeline it measures.

A span always reads the clock, live or not, so that callers can hand its
``seconds`` on (the goodput ledger's ``data_wait`` and ``on_step``) without
a second pair of clock reads.
"""

import collections
import itertools
import json
import os
import threading
import time
from functools import wraps
from typing import Dict, NamedTuple

RING_SPANS = 1 << 16


class SpanLog(NamedTuple):
    """What :func:`spans` returns: ``anchor`` is ``(time.time_ns(),
    time.perf_counter_ns())`` read together, ``records`` the closed spans
    in closing order."""

    anchor: tuple
    records: list


class _State:
    """The recorder's module state (one per process)."""

    def __init__(self, maxlen=RING_SPANS):
        self.enabled = True
        self.sync = False  # HYDRAGNN_TRACE_LEVEL=1 at initialize
        self.anchor = (time.time_ns(), time.perf_counter_ns())
        self.annotate = None  # jax.profiler.TraceAnnotation once initialized
        self.ring = collections.deque(maxlen=maxlen)
        self.totals = {}  # path -> [calls, total_ns, min_ns, max_ns]
        self.lock = threading.Lock()  # ring and totals
        self.ids = itertools.count(1)
        self.local = threading.local()  # .stack, .thread per thread
        self.stacks = {}  # thread ident -> that thread's stack, live threads

    @property
    def live(self):
        """Recording: initialized and not disabled."""
        return self.enabled and self.annotate is not None

    def stack(self):
        local = self.local
        try:
            return local.stack
        except AttributeError:
            local.stack = []
            local.thread = threading.current_thread().name
            with self.lock:
                # once a thread: threads that ended leave the table here
                alive = {t.ident for t in threading.enumerate()}
                self.stacks = {
                    i: s for i, s in self.stacks.items() if i in alive
                }
                self.stacks[threading.get_ident()] = local.stack
            return local.stack

    def close(self, span):
        path = span.path
        dur = span.end_ns - span.start_ns
        with self.lock:
            self.ring.append(span)
            stat = self.totals.get(path)
            if stat is None:
                self.totals[path] = [1, dur, dur, dur]
            else:
                stat[0] += 1
                stat[1] += dur
                if dur < stat[2]:
                    stat[2] = dur
                if dur > stat[3]:
                    stat[3] = dur


_state = _State()


class Span:
    """One timed interval on one thread. A context manager; ``start``
    returns one already open, to be closed by :meth:`stop`."""

    __slots__ = ("name", "thread", "start_ns", "end_ns", "id", "parent",
                 "attrs", "path", "_sync", "_live", "_annotation")

    def __init__(self, name, attrs=None, sync=False):
        self.name = name
        self.attrs = attrs or None
        self._sync = sync
        self.end_ns = None

    def _place(self, st, stack):
        """Its identity in the thread's call tree."""
        self.thread = st.local.thread
        self._live = st.live
        if stack:
            top = stack[-1]
            self.parent = top.id
            self.path = top.path + "/" + self.name
        else:
            self.parent = 0
            self.path = self.name
        self.id = next(st.ids)

    def __enter__(self):
        st = _state
        stack = st.stack()
        self._place(st, stack)
        if self._live:
            self._annotation = st.annotate(
                self.name, id=self.id, parent=self.parent
            )
            self._annotation.__enter__()
        stack.append(self)
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def set(self, **counts):
        """Add counts to the record (any time before the ring is read)."""
        if self.attrs is None:
            self.attrs = counts
        else:
            self.attrs.update(counts)

    def stop(self, **counts):
        """Close the span; spans opened above it on this thread and never
        closed are dropped (a missed stop must not re-parent what follows)."""
        if self.end_ns is not None:
            return self
        if self._sync:
            _device_sync()
        self.end_ns = time.perf_counter_ns()
        if counts:
            self.set(**counts)
        stack = _state.stack()
        if self in stack:
            top = None
            while top is not self:
                top = stack.pop()
                if top._live:
                    top._annotation.__exit__(None, None, None)
                    top._annotation = None
        if self._live:
            _state.close(self)
        return self

    @property
    def seconds(self) -> float:
        end = self.end_ns if self.end_ns is not None else time.perf_counter_ns()
        return (end - self.start_ns) * 1e-9


def initialize(trace_backends=()):
    """Switch the recorder on. ``trace_backends`` is accepted for the
    callers that name the former backends (``("native",)``, ``("timer",)``,
    ``("jax",)``): every call gives the same recorder, and a second call
    keeps ring, totals and anchor."""
    from jax.profiler import TraceAnnotation

    st = _state
    if st.annotate is None:
        st.anchor = (time.time_ns(), time.perf_counter_ns())
        st.annotate = TraceAnnotation
    st.sync = os.getenv("HYDRAGNN_TRACE_LEVEL", "0") == "1"
    return ["spans"]


def enable():
    _state.enabled = True


def disable():
    _state.enabled = False


def reset():
    """Empty ring and totals (open spans stay open)."""
    with _state.lock:
        _state.ring.clear()
        _state.totals.clear()


_sync_fn = None


def _device_sync():
    """Block until in-flight device computation finishes (trace level 1's
    "honest attribution" contract). ``jax.effects_barrier()`` is NOT that —
    it only waits for ordered side effects and returns immediately with
    async compute still in flight; ``jax.device_put(...)`` doesn't help
    either, transfers bypass the execution stream. Dispatching a trivial
    jitted program and blocking on it does: executions are ordered per
    device, so its completion implies everything enqueued before it ran."""
    global _sync_fn
    import jax

    if _sync_fn is None:
        import jax.numpy as jnp

        _sync_fn = jax.jit(lambda: jnp.zeros(()))
    _sync_fn().block_until_ready()


def span(name, **counts) -> Span:
    """``with span("collate", graphs=n) as s: ...; s.set(edges=m)``: a
    host-side stage on whichever thread runs it. Never waits for the
    device."""
    return Span(name, counts)


def open_elsewhere(name) -> bool:
    """Whether a span called ``name`` is open on ANOTHER thread right now:
    what one stage of a pipeline notes about the stage beside it (``put_group``
    carries ``collate_open``). Reads the other threads' stacks as they are;
    recording or not, a span is on its thread's stack while it is open."""
    st = _state
    me = threading.get_ident()
    with st.lock:
        others = [s for i, s in st.stacks.items() if i != me]
    return any(sp.name == name for stack in others for sp in list(stack))


def start(name, **counts) -> Span:
    """Open a region (device sync first at trace level 1) and return it;
    close it with its ``stop()`` or with ``stop(name)``."""
    sync = _state.sync and _state.live
    if sync:
        _device_sync()
    return Span(name, counts, sync).__enter__()


def stop(name, **counts):
    """Close the innermost open region called ``name`` on this thread;
    returns it, or None where there is none (tolerates a missed start, like
    GPTL)."""
    for open_span in reversed(_state.stack()):
        if open_span.name == name:
            return open_span.stop(**counts)
    return None


def record(name, duration_s: float, **counts):
    """A span of ``duration_s`` seconds that ends now, under whatever is
    open on this thread: for work whose duration is reported after the fact (the
    compile listener). Not annotated: a trace cannot be written backwards."""
    st = _state
    if not st.live:
        return None
    s = Span(name, counts)
    s._place(st, st.stack())
    s.end_ns = time.perf_counter_ns()
    s.start_ns = s.end_ns - int(max(float(duration_s), 0.0) * 1e9)
    st.close(s)
    return s


def profile(name):
    """Decorator marking a traced region (``tracer.py:149-164``)."""

    def deco(fn):
        @wraps(fn)
        def wrapper(*args, **kwargs):
            region = start(name)
            try:
                return fn(*args, **kwargs)
            finally:
                region.stop()

        return wrapper

    return deco


def spans() -> SpanLog:
    """The anchor pair and the ring's records (closed spans, oldest
    first)."""
    with _state.lock:
        return SpanLog(_state.anchor, list(_state.ring))


def totals() -> Dict[str, float]:
    """Accumulated seconds per call-tree path ("train/train_step"), over
    every thread, since the last :func:`reset`: running sums, so a run
    longer than the ring loses nothing. Feeds the telemetry layer's
    ``ScalarWriter.add_regions`` / ``tracer_totals`` run event."""
    with _state.lock:
        return {path: stat[1] * 1e-9 for path, stat in _state.totals.items()}


def save(prefix: str = "./logs/trace"):
    """Per-host dump at run end (GPTL ``gp.pr_file`` analog):
    ``<prefix>.<rank>``, the call tree with calls / total / avg / min / max
    per path, and ``<prefix>.<rank>.trace.json``, the ring as
    chrome://tracing "X" events (loadable in perfetto), one ``tid`` per
    thread, times in us since the anchor."""
    from hydragnn_tpu.parallel.distributed import get_comm_size_and_rank

    _, rank = get_comm_size_and_rank()
    os.makedirs(os.path.dirname(prefix) or ".", exist_ok=True)
    with _state.lock:
        stats = sorted((p, list(s)) for p, s in _state.totals.items())
    log = spans()
    with open(f"{prefix}.{rank}", "w") as f:
        f.write(f"{'region':<44} {'calls':>10} {'total_s':>14} {'avg_ms':>12}"
                f" {'min_ms':>12} {'max_ms':>12}\n")
        for path, (calls, total, lo, hi) in stats:
            depth = path.count("/")
            label = "  " * depth + path.rsplit("/", 1)[-1]
            f.write(f"{label:<44} {calls:>10} {total * 1e-9:>14.4f}"
                    f" {total * 1e-6 / calls:>12.3f} {lo * 1e-6:>12.3f}"
                    f" {hi * 1e-6:>12.3f}\n")
    tids, events = {}, []
    for s in log.records:
        tid = tids.setdefault(s.thread, len(tids))
        args = dict(s.attrs or {}, id=s.id, parent=s.parent)
        events.append({
            "name": s.name, "ph": "X", "pid": rank, "tid": tid,
            "ts": (s.start_ns - log.anchor[1]) * 1e-3,
            "dur": (s.end_ns - s.start_ns) * 1e-3, "args": args,
        })
    events.extend(
        {"name": "thread_name", "ph": "M", "pid": rank, "tid": tid,
         "args": {"name": thread}}
        for thread, tid in tids.items()
    )
    with open(f"{prefix}.{rank}.trace.json", "w") as f:
        json.dump(events, f, default=str)
