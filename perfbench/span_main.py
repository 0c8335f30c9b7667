"""The program's own spans OUTSIDE the step loop: set-up (everything the
ring holds from before the measured window) and the epoch boundary.

Built on ``span_window.py`` and read the same way: after the window, in the
program's process, from the recorder's ring. :func:`setup_spans` gives the
records that had closed before the window's first ``train`` root; it gives
``None`` where the ring holds no ``load_datasets`` span there, which is how
a program that records its steady loop but not its set-up (the parent of
the PR that brought these spans) is told apart: every reader built on this
then returns ``None`` and its metric is absent from the line.
:func:`boundary` does the same for the epoch boundary, by the ``epoch_open``
span every ``train`` root opens with.
"""

import span_window
from span_window import covered_ns, seconds

# the events of the compile family that are NOT tracing or lowering
BACKEND_COMPILE = "backend_compile_duration"
CACHE_LOAD = "cache_retrieval_time_sec"


def setup_spans(run):
    """``{"lo", "threads"}``: the start of the window on the recorder's
    clock (ns) and ``{thread: [span, ...]}``, sorted by start, of the
    records that had CLOSED by then (a ``compile`` span is filed when its
    duration is reported and dated back by it, so one of the check that
    follows the window can start before the window does); None without a
    recorder, without a window, or where no ``load_datasets`` span lies
    before the window."""
    win = span_window.window_spans(run)
    if win is None:
        return None
    threads, loaded = {}, False
    for s in sorted(span_window.recorded(), key=lambda s: s.start_ns):
        if s.end_ns <= win["lo"]:
            threads.setdefault(s.thread, []).append(s)
            loaded = loaded or s.name == "load_datasets"
    return {"lo": win["lo"], "threads": threads} if loaded else None


def named(setup, *names):
    """Set-up's spans called one of ``names``, on any thread."""
    return [s for spans in setup["threads"].values() for s in spans
            if s.name in names]


def total_s(spans):
    return sum(seconds(s) for s in spans)


def covered_s(spans):
    """Seconds the spans cover, thread by thread: spans that nest (a jit
    traced inside another's trace reports a duration of its own) count
    once."""
    by_thread = {}
    for s in spans:
        by_thread.setdefault(s.thread, []).append(s)
    return sum(
        covered_ns(mine, min(s.start_ns for s in mine),
                   max(s.end_ns for s in mine))
        for mine in by_thread.values()
    ) * 1e-9


def compiles(setup, keep):
    """Set-up's ``compile`` spans whose ``event`` satisfies ``keep``."""
    return [s for s in named(setup, "compile")
            if keep((s.attrs or {}).get("event"))]


def setup_parts(run):
    """``{"loader", "model_init", "first_epochs"}`` in seconds: the three
    stretches of set-up that lie under the program's own top-level spans
    (``load_datasets``, ``init_state``, the ``train`` roots of the warm
    epochs); None where :func:`setup_spans` is."""
    setup = setup_spans(run)
    if setup is None:
        return None
    return {
        "loader": total_s(named(setup, "load_datasets")),
        "model_init": total_s(named(setup, "init_state")),
        "first_epochs": total_s(
            s for s in named(setup, "train") if s.parent == 0),
    }


def boundary(run):
    """The window (``span_window.window_spans``) with the loop thread's
    spans that closed inside it, the roots left out (``"loop_spans"``,
    sorted by start: a span dated back into the window from after it is
    not among them) and, per ``train`` root in order, those under it
    (``"epochs"``: a list of lists); None where a root has no
    ``epoch_open`` child."""
    win = span_window.window_spans(run)
    if win is None:
        return None
    roots = {r.id for r in win["roots"]}
    mine = [s for s in win["threads"].get(win["loop"], [])
            if s.id not in roots and s.end_ns <= win["hi"]]
    epochs = [[s for s in mine if s.parent == r.id] for r in win["roots"]]
    if not all(any(s.name == "epoch_open" for s in e) for e in epochs):
        return None
    return dict(win, loop_spans=mine, epochs=epochs)


# ---- for PERF.md: where set-up and the boundary went, span by span ----------

SETUP_NAMES = ("load_datasets", "read_split", "radius_graph", "finish_split",
               "sample_stats", "compute_layout", "bucket_assignments",
               "batch_plan", "init_state", "train")


def report(run):
    """Not a metric: set-up's spans in order with their counts, its
    ``compile`` seconds by (span they lie under, event), and per epoch of
    the window the loop thread's time by span name, between the roots, and
    in stretches no span names (by the spans before and after, largest
    first, with the median and the largest stretch). None where
    :func:`setup_spans` or :func:`boundary` is."""
    import statistics

    setup, win = setup_spans(run), boundary(run)
    if setup is None or win is None:
        return None
    by_id = {s.id: s for spans in setup["threads"].values() for s in spans}
    first = min(spans[0].start_ns for spans in setup["threads"].values())
    stages = [
        {"span": s.name, "thread": s.thread,
         "under": getattr(by_id.get(s.parent), "name", None),
         "at_s": round((s.start_ns - first) * 1e-9, 3),
         "seconds": round(seconds(s), 4), **(s.attrs or {})}
        for s in sorted(named(setup, *SETUP_NAMES), key=lambda s: s.start_ns)
        if s.name != "train" or s.parent == 0
    ]
    compile_s = {}
    for s in named(setup, "compile"):
        under = getattr(by_id.get(s.parent), "name", None)
        key = f"{under}:{s.attrs['event']}"
        compile_s.setdefault(key, []).append(s)
    compile_s = {k: {"spans": len(v), "sum_s": round(total_s(v), 3),
                     "covered_s": round(covered_s(v), 3)}
                 for k, v in sorted(compile_s.items())}
    epochs = len(win["roots"])
    roots = {r.id for r in win["roots"]}
    loop = win["loop_spans"]
    per_name = {}
    for s in loop:
        if s.parent in roots or s.parent == 0:
            per_name[s.name] = per_name.get(s.name, 0.0) + seconds(s) * 1e3
    unnamed, edge, before = {}, win["lo"], "window_start"
    for s in loop:  # sorted by start
        if s.start_ns > edge:
            unnamed.setdefault(f"{before}>{s.name}", []).append(
                (s.start_ns - edge) * 1e-6)
        if s.end_ns > edge:
            edge, before = s.end_ns, s.name
    between = [(b.start_ns - a.end_ns) * 1e-6
               for a, b in zip(win["roots"], win["roots"][1:])]
    return {
        "setup_at_s": round((setup["lo"] - first) * 1e-9, 3),
        "setup": stages, "setup_compile": compile_s, "epochs": epochs,
        "epoch_ms": round(win["seconds"] * 1e3 / epochs, 3),
        "loop_ms_per_epoch": {k: round(v / epochs, 3)
                              for k, v in sorted(per_name.items())},
        "between_roots_ms": round(statistics.median(between), 3)
        if between else None,
        "unnamed_ms_per_epoch": {
            k: {"stretches": len(v), "ms": round(sum(v) / epochs, 3),
                "median_ms": round(statistics.median(v), 4),
                "largest_ms": round(max(v), 3)}
            for k, v in sorted(unnamed.items(), key=lambda kv: -sum(kv[1]))
        },
    }


def main():
    """``python3 perfbench/span_main.py --workload <cell> --seed <n>``: one
    run along ``--trace 1``'s path; prints the result line's metrics,
    :func:`report`, and ``harness_s``: the seconds of the harness's own
    set-up stages (what ``setup_outside_s.train`` is made of), timed around
    its calls. One JSON line. Not part of a benchmark run."""
    import argparse
    import functools
    import json
    import time

    import run as harness

    parser = argparse.ArgumentParser(description=main.__doc__.split(":")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=6.0)
    args = parser.parse_args()
    seen, load, spent = {}, harness.load_reader, {}

    def spy(kind, name):  # the ``run`` dict the readers are handed
        read = load(kind, name)
        return lambda run: read(seen.setdefault("run", run))

    def timed(owner, name):
        fn = getattr(owner, name)

        @functools.wraps(fn)
        def wrapper(*a, **k):
            t = time.perf_counter()
            spent.setdefault(name + "_at_s", round(t - harness.T_START, 3))
            try:
                return fn(*a, **k)
            finally:
                spent[name] = round(
                    spent.get(name, 0.0) + time.perf_counter() - t, 4)

        setattr(owner, name, wrapper)

    import sys

    sys.path[:0] = [p for p in (harness.HERE, harness.ROOT)
                    if p not in sys.path]
    import build
    import check
    import traffic_gen

    harness.load_reader = spy
    for owner, name in (
        (harness, "find_devices"), (traffic_gen, "make_graphs"),
        (build, "write_dataset"), (build, "build_program"),
        (check, "load_reference"), (harness, "install_weights"),
        (check.Recorder, "fetch"),
    ):
        timed(owner, name)
    result = harness.run_cell(args.workload, args.seed, args.seconds, True)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "correct": result["correct"],
                      "metrics": result["metrics"],
                      "setup_s": seen["run"]["setup_s"],
                      "harness_s": spent,
                      "report": report(seen["run"])}),
          flush=True)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
