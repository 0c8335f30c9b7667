"""The per-layer metrics that read the main thread outside the step loop
(``span_main.py`` and the eleven readers built on it): each on a hand-made
span list gives the value worked out by hand, ``None`` without spans and on
a ring that holds no set-up, the four top-level parts add up to ``setup_s``,
a tiny CPU cell run along ``--trace 1``'s path prints all eleven, and a
program without these spans (the parent's) prints none and still ends."""

import json
import os

import pytest

from test_span_metrics import FIXTURE, MS, ROOT, _span

SETUP = [
    "setup_loader_s.train", "setup_graph_build_s.train",
    "setup_layout_s.train", "setup_model_init_s.train",
    "setup_first_epochs_s.train", "setup_trace_lower_s.train",
    "setup_cache_load_s.train", "setup_outside_s.train",
]
BOUNDARY = [
    "epoch_boundary_ms.train", "epoch_drain_ms.train",
    "loop_unspanned_pct.train",
]
NEW = SETUP + BOUNDARY
MAIN, PUT = "MainThread", "hydragnn-device-prefetch"
HARNESS = {"window": {"epochs": 2}, "setup_s": 9.5}


def _compile(ids, thread, start_ms, end_ms, parent, event):
    return _span(ids, "compile", thread, start_ms, end_ms, parent=parent,
                 event=event, seconds=(end_ms - start_ms) * 1e-3, fun="f")


def _hand_made(set_up=True, boundary=True):
    """Set-up, 0-8,000 ms (the recorder's first span opens 1,500 ms into
    the process, whose window opens at 9,500): ``load_datasets`` 0-4,000
    with two radius graphs (1,500 and 500 ms), the sample statistics (400)
    and the layout (100); ``init_state`` 4,200-5,000 with a trace that
    holds an inner trace, a lowering, a compile; one warm epoch
    6,000-8,000 whose first step traces, lowers and loads from the cache,
    and whose plan the put thread packed (20 ms; the sizes pass 30). The
    window: two epochs of 100 ms, 1,000 ms apart, the second's plan packed
    on the loop thread between the roots (5 ms)."""
    ids, out = [], []
    add = lambda *a, **k: out.append(_span(ids, *a, **k)) or out[-1]  # noqa: E731
    if set_up:
        load = add("load_datasets", MAIN, 0, 4000, splits=3)
        add("read_split", MAIN, 0, 100, parent=load.id, graphs=8)
        add("radius_graph", MAIN, 100, 1600, parent=load.id, graphs=8)
        add("finish_split", MAIN, 1600, 1700, parent=load.id, graphs=8)
        add("radius_graph", MAIN, 1800, 2300, parent=load.id, graphs=2)
        add("sample_stats", MAIN, 3000, 3400, parent=load.id, graphs=10)
        add("compute_layout", MAIN, 3400, 3500, parent=load.id, buckets=2)
        init = add("init_state", MAIN, 4200, 5000, params=4)
        out.append(_compile(ids, MAIN, 4300, 4400, init.id,
                            "jaxpr_trace_duration"))  # inside the next one
        out.append(_compile(ids, MAIN, 4250, 4550, init.id,
                            "jaxpr_trace_duration"))
        out.append(_compile(ids, MAIN, 4550, 4600, init.id,
                            "jaxpr_to_mlir_module_duration"))
        out.append(_compile(ids, MAIN, 4600, 4900, init.id,
                            "backend_compile_duration"))
        add("bucket_assignments", PUT, 6001, 6031, graphs=8)
        add("batch_plan", PUT, 6031, 6051, batches=4, buckets=2)
    warm = add("train", MAIN, 6000, 8000)
    step = add("train_step", MAIN, 6100, 7900, parent=warm.id, steps=1)
    out.append(_compile(ids, MAIN, 6100, 6700, step.id,
                        "jaxpr_trace_duration"))
    out.append(_compile(ids, MAIN, 6700, 7000, step.id,
                        "jaxpr_to_mlir_module_duration"))
    out.append(_compile(ids, MAIN, 7000, 7250, step.id,
                        "cache_retrieval_time_sec"))
    # jax 0.9.0 sends the load inside a backend_compile_duration as well
    out.append(_compile(ids, MAIN, 7000, 7260, step.id,
                        "backend_compile_duration"))
    for epoch, t in enumerate((10000, 11000)):
        if epoch and boundary:
            add("batch_plan", MAIN, t - 10, t - 5, batches=4, buckets=2)
        root = add("train", MAIN, t, t + 100)
        loop = [("dataload", 2, 30, {}), ("train_step", 30, 32, {"steps": 1}),
                ("dataload", 33, 72, {}), ("train_step", 72, 76, {"steps": 2}),
                ("acc_add", 76, 78, {})]
        if boundary:
            loop[:0] = [("epoch_open", 0, 1, {"prefetch": 2})]
            loop += [("settle", 78, 79, {"waited": True})]
        for name, a, b, attrs in loop:
            add(name, MAIN, t + a, t + b, parent=root.id, **attrs)
        back = add("epoch_readback", MAIN, t + 80, t + 96, parent=root.id,
                   dispatches=2)
        if boundary:
            add("drain", MAIN, t + 80, t + 92 + epoch * 2, parent=back.id,
                dispatches=2)
    # an evaluation after the window: its drain is not the epoch's
    back = add("epoch_readback", MAIN, 11200, 11300)
    add("drain", MAIN, 11200, 11290, parent=back.id, dispatches=1)
    # the check that follows the window traces for 2.5 s: filed at 11,500
    # and dated back to before the window, it is neither set-up's nor
    # named time of the loop
    out.append(_compile(ids, MAIN, 9000, 11500, 0, "jaxpr_trace_duration"))
    return out


BY_HAND = {
    "setup_loader_s.train": 4.0,
    "setup_graph_build_s.train": 2.0,  # 1,500 + 500 ms
    "setup_layout_s.train": 0.55,  # 400 + 100 + 30 + 20 ms
    "setup_model_init_s.train": 0.8,
    "setup_first_epochs_s.train": 2.0,
    # covered, not summed: 4,250-4,600 (the inner trace counts once) and
    # 6,100-7,000
    "setup_trace_lower_s.train": 1.25,
    "setup_cache_load_s.train": 0.25,
    "setup_outside_s.train": 9.5 - 4.0 - 0.8 - 2.0,
    # from the first epoch's last step (10,076) to the second's first (11,030)
    "epoch_boundary_ms.train": 954.0,
    "epoch_drain_ms.train": 13.0,  # median of 12 and 14
    # the window is 1,100 ms; named: per epoch 0-1, 2-32, 33-79, 80-96
    # (93 ms), and the plan between the roots (5 ms)
    "loop_unspanned_pct.train": 100.0 * (1100 - 2 * 93 - 5) / 1100,
}


@pytest.mark.parametrize("name", NEW)
def pytest_reader_on_hand_made_spans(name, monkeypatch):
    import run
    import span_window

    read = run.load_reader("layer_metrics", name)
    monkeypatch.setattr(span_window, "recorded", _hand_made)
    assert read(HARNESS) == pytest.approx(BY_HAND[name], rel=1e-9)
    # the ring holds fewer epochs than the window had, nothing at all, or
    # the program has no recorder: nothing to read, and no error
    assert read(dict(HARNESS, window={"epochs": 4})) is None
    monkeypatch.setattr(span_window, "recorded", lambda: [])
    assert read(HARNESS) is None
    monkeypatch.setattr(span_window, "recorded", lambda: None)
    assert read(HARNESS) is None
    # a program that records its steady loop alone (the parent's): its warm
    # epochs and their compile spans are no set-up, its roots no boundary
    monkeypatch.setattr(
        span_window, "recorded",
        lambda: _hand_made(set_up=name in BOUNDARY, boundary=name in SETUP))
    assert read(HARNESS) is None


def pytest_four_parts_add_up_to_setup_s(monkeypatch):
    import run
    import span_window

    monkeypatch.setattr(span_window, "recorded", _hand_made)
    parts = ["setup_loader_s.train", "setup_model_init_s.train",
             "setup_first_epochs_s.train", "setup_outside_s.train"]
    for setup_s in (9.5, 63.40123456789, 7.000000001):
        harness = dict(HARNESS, setup_s=setup_s)
        values = [run.load_reader("layer_metrics", n)(harness) for n in parts]
        assert sum(values) == pytest.approx(setup_s, rel=1e-12, abs=0)


def pytest_report_names_the_stretches(monkeypatch):
    import span_main
    import span_window

    monkeypatch.setattr(span_window, "recorded", _hand_made)
    r = span_main.report(HARNESS)
    assert r["setup_at_s"] == pytest.approx(10.0) and r["epochs"] == 2
    assert [s["span"] for s in r["setup"]][:3] == [
        "load_datasets", "read_split", "radius_graph"]
    assert r["setup"][2]["under"] == "load_datasets"
    assert r["setup_compile"]["init_state:jaxpr_trace_duration"] == {
        "spans": 2, "sum_s": 0.4, "covered_s": 0.3}
    assert r["loop_ms_per_epoch"]["dataload"] == pytest.approx(67.0)
    assert r["between_roots_ms"] == pytest.approx(900.0)
    # largest first: the gap between the roots, then the epochs' own
    first, *rest = r["unnamed_ms_per_epoch"].items()
    assert first == ("epoch_readback>batch_plan", {
        "stretches": 1, "ms": pytest.approx(447.0),
        "median_ms": pytest.approx(894.0), "largest_ms": pytest.approx(894.0)})
    assert dict(rest)["epoch_open>dataload"] == {
        "stretches": 2, "ms": pytest.approx(1.0),
        "median_ms": pytest.approx(1.0), "largest_ms": pytest.approx(1.0)}
    monkeypatch.setattr(span_window, "recorded",
                        lambda: _hand_made(set_up=False))
    assert span_main.report(HARNESS) is None


def _tiny_cells(tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    with open(os.path.join(FIXTURE, "cells.json")) as f:
        cells = json.load(f)
    cells["per_layer"] = [m for m in real["per_layer"] if m["name"] in NEW] + [
        m for m in cells["per_layer"] if m["name"] == "input_wait_pct.train"]
    assert [m["name"] for m in cells["per_layer"]][:-1] == NEW
    assert all("workloads" not in m and m["source"] == "program_span"
               for m in cells["per_layer"][:-1])
    benchmark_file = tmp_path / "cells.json"
    benchmark_file.write_text(json.dumps(cells))
    return str(benchmark_file)


def _traced_tiny_run(tmp_path, monkeypatch):
    import run

    def stand_in(trace_dir, step_modules, collective_ops, keep_trace):
        return {"steps": 6, "busy_s": 1.0, "window_s": 2.0, "device_ops": [],
                "busiest_busy_s": 1.0, "step_busy_s": 1.0, "intervals_s": [],
                "idle_gaps": []}

    monkeypatch.setattr(run, "reduce_trace", stand_in)
    here = os.getcwd()
    try:
        return run.run_cell(
            "tiny_egnn_train", 2**31 + 11, 0.3, True, require_chip=False,
            benchmark_file=_tiny_cells(tmp_path), files=FIXTURE,
            out_dir=str(tmp_path / "out"),
        )
    finally:
        os.chdir(here)


def pytest_tiny_traced_cell_prints_all_eleven(tmp_path, monkeypatch):
    """``--trace 1``'s path on the CPU at tiny size: every new metric is in
    the result, from the spans of this process's set-up and window."""
    from hydragnn_tpu.utils import tracer

    tracer.reset()  # this process's earlier cells are not this run's set-up
    r = _traced_tiny_run(tmp_path, monkeypatch)
    assert r["correct"] is True, r["compared"]
    value = {k: v["value"] for k, v in r["metrics"].items()}
    assert set(NEW) <= set(value)
    assert all(value[name] > 0 for name in NEW
               if name != "setup_cache_load_s.train"), value
    assert value["setup_cache_load_s.train"] >= 0
    assert {r["metrics"][n]["unit"] for n in SETUP} == {"s"}
    # the nesting PERF.md states
    assert (value["setup_graph_build_s.train"]
            < value["setup_loader_s.train"])
    assert value["loop_unspanned_pct.train"] < 100
    parts = ["setup_loader_s.train", "setup_model_init_s.train",
             "setup_first_epochs_s.train", "setup_outside_s.train"]
    # the run's own setup_s is not in a traced line: the parts are all
    # positive and the first three lie inside this process's life
    assert sum(value[n] for n in parts[:3]) < sum(value[n] for n in parts)


def pytest_program_without_the_spans_prints_none_and_ends(tmp_path, monkeypatch):
    """The benchmark as this PR leaves it over a program that lacks what
    the PR adds to the program (the parent's): spans called by the new
    names never reach the ring; the run ends, ``correct``, with the metrics
    the parent printed and none of the eleven."""
    from hydragnn_tpu.utils import tracer

    added = {"load_datasets", "read_split", "radius_graph", "finish_split",
             "sample_stats", "compute_layout", "bucket_assignments",
             "batch_plan", "init_state", "epoch_open", "split_rng", "settle",
             "drain"}
    close = tracer._State.close

    def parents_close(self, span):
        if span.name not in added:
            close(self, span)

    monkeypatch.setattr(tracer._State, "close", parents_close)
    tracer.reset()
    r = _traced_tiny_run(tmp_path, monkeypatch)
    assert r["correct"] is True, r["compared"]
    assert not set(NEW) & set(r["metrics"])
    assert "input_wait_pct.train" in r["metrics"]
