"""The program's own spans inside the measured window.

The span recorder (``hydragnn_tpu/utils/tracer.py``) keeps every closed span
in a ring that is module state, so a reader that runs after the window, in
the program's process, finds them there. :func:`window_spans` picks the last
``run["window"]["epochs"]`` root ``train`` spans (one per ``train_epoch``)
and takes everything that started, on any thread, between the first one's
start and the last one's end. A program without the recorder (the parent of
the PR that brought it) gives ``None``, and so does every reader built on
this.
"""

import statistics

WAITS = ("queue_put_wait", "queue_get_wait")


def recorded():
    """The ring's records, or None where the program has no recorder."""
    try:
        from hydragnn_tpu.utils import tracer

        return tracer.spans().records
    except (ImportError, AttributeError):
        return None


def window_spans(run):
    """``{"lo", "hi", "seconds", "roots", "loop", "threads"}``: the window's
    bounds on the recorder's clock (ns), its root ``train`` spans, the name
    of the epoch loop's thread and ``{thread: [span, ...]}`` sorted by
    start; None where the ring holds no such window."""
    records = recorded()
    epochs = int(run["window"]["epochs"])
    if not records or epochs <= 0:
        return None
    roots = sorted(
        (s for s in records if s.name == "train" and s.parent == 0),
        key=lambda s: s.start_ns,
    )[-epochs:]
    if len(roots) < epochs:
        return None
    lo, hi = roots[0].start_ns, roots[-1].end_ns
    threads = {}
    for s in sorted(records, key=lambda s: s.start_ns):
        if lo <= s.start_ns < hi:
            threads.setdefault(s.thread, []).append(s)
    return {"lo": lo, "hi": hi, "seconds": (hi - lo) * 1e-9, "roots": roots,
            "loop": roots[0].thread, "threads": threads}


def named(win, name, thread=None):
    """The window's spans called ``name`` (on ``thread`` only, if given)."""
    return [
        s for t, spans in win["threads"].items() if thread in (None, t)
        for s in spans if s.name == name
    ]


def seconds(span):
    return (span.end_ns - span.start_ns) * 1e-9


def median_ms(spans):
    return statistics.median(seconds(s) for s in spans) * 1e3 if spans else None


def covered_ns(spans, lo, hi):
    """Length of the union of the spans' intervals inside [lo, hi)."""
    total, edge = 0, lo
    for s, e in sorted((s.start_ns, min(s.end_ns, hi)) for s in spans):
        if e > edge:
            total += e - max(s, edge)
            edge = e
    return total


def busy_by_thread(win):
    """{producer thread: seconds covered by its spans less its queue
    waits}: every thread of the window but the epoch loop's. Threads that
    share a name (the loader starts one per epoch) count as one."""
    out = {}
    for thread, spans in win["threads"].items():
        if thread == win["loop"]:
            continue
        waits = sum(seconds(s) for s in spans if s.name in WAITS)
        out[thread] = covered_ns(spans, win["lo"], win["hi"]) * 1e-9 - waits
    return out


def summary(win):
    """Where the window's host time went, for PERF.md: seconds per epoch of
    every (thread, span name); the epoch loop's ``train`` self time as a
    share of the window; per producer thread the share of its active time
    (first to last span of each epoch) that spans cover."""
    epochs = len(win["roots"])
    stages = {}
    for thread, spans in win["threads"].items():
        for s in spans:
            key = f"{thread}:{s.name}"
            stages[key] = stages.get(key, 0.0) + seconds(s) / epochs
    root_ids = {r.id for r in win["roots"]}
    children = [s for s in win["threads"][win["loop"]] if s.parent in root_ids]
    self_s = sum(seconds(r) for r in win["roots"]) - covered_ns(
        children, win["lo"], win["hi"]) * 1e-9
    coverage = {}
    for thread, spans in win["threads"].items():
        if thread == win["loop"]:
            continue
        active = cover = 0
        for r in win["roots"]:
            mine = [s for s in spans if r.start_ns <= s.start_ns < r.end_ns]
            if mine:
                first = mine[0].start_ns
                last = max(s.end_ns for s in mine)
                active += last - first
                cover += covered_ns(mine, first, last)
        if active:
            coverage[thread] = 100.0 * cover / active
    return {
        "epochs": epochs, "window_s": win["seconds"],
        "train_self_pct": 100.0 * self_s / win["seconds"],
        "producer_coverage_pct": coverage,
        "seconds_per_epoch": {k: round(v, 6) for k, v in sorted(stages.items())},
    }
