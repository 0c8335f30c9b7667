"""The per-layer metrics that read the program's own spans
(``span_window.py`` and the nine readers built on it): each on a hand-made
span list gives the value worked out by hand, ``None`` without spans, and a
tiny CPU cell run along ``--trace 1``'s path prints all nine."""

import json
import os
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixture")
PERFBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(PERFBENCH)

NEW = [
    "data_wait_pct.train", "collate_ms_per_batch.train",
    "neighbor_lists_ms_per_batch.train", "put_ms_per_batch.train",
    "producer_busy_pct.train", "edge_padding_waste_pct.train",
    "epoch_refill_ms.train", "epoch_readback_ms.train",
    "dispatch_host_ms.train",
]
MS = 1_000_000  # ns


def _span(ids, name, thread, start_ms, end_ms, parent=0, **attrs):
    ids.append(len(ids) + 1)
    return SimpleNamespace(
        name=name, thread=thread, start_ns=start_ms * MS, end_ns=end_ms * MS,
        id=ids[-1], parent=parent, attrs=attrs)


def _hand_made():
    """A warm epoch (left out: the window holds two) and two epochs of 100
    ms, 1,000 ms apart. Per epoch at offset t: the loop waits 30 ms, steps
    2 ms, waits 40 ms, steps 4 ms, reads back 10 ms; the collate thread
    works 0-50 (its second batch with a 5 ms neighbour-list part) and is
    blocked on a full queue 50-60 in the second epoch only; the put thread
    starves 0-25, puts 25-30 (one batch) and 60-70 (a group of two)."""
    ids, out = [], []
    out.append(_span(ids, "train", "MainThread", 0, 50))
    out.append(_span(ids, "dataload", "MainThread", 0, 45, parent=1))
    for epoch, t in enumerate((1000, 2000)):
        root = _span(ids, "train", "MainThread", t, t + 100)
        out.append(root)
        loop = [("dataload", 0, 30, {}), ("train_step", 30, 32, {"steps": 1}),
                ("dataload", 32, 72, {}), ("train_step", 72, 76, {"steps": 2}),
                ("epoch_readback", 80, 90, {"dispatches": 2})]
        for name, a, b, attrs in loop:
            out.append(_span(ids, name, "MainThread", t + a, t + b,
                             parent=root.id, **attrs))
        c1 = _span(ids, "collate", "graphloader-prefetch", t, t + 20,
                   graphs=4, nodes=40, edges=300, bucket=64, e_pad=400)
        c2 = _span(ids, "collate", "graphloader-prefetch", t + 20, t + 50,
                   graphs=4, nodes=50, edges=500, bucket=64, e_pad=600)
        out += [c1, c2]
        out.append(_span(ids, "neighbor_lists", "graphloader-prefetch",
                         t + 10, t + 13, parent=c1.id, k_in=12, k_out=12))
        out.append(_span(ids, "neighbor_lists", "graphloader-prefetch",
                         t + 40, t + 45, parent=c2.id, k_in=12, k_out=12))
        if epoch == 1:
            out.append(_span(ids, "queue_put_wait", "graphloader-prefetch",
                             t + 50, t + 60, depth=2))
        out.append(_span(ids, "queue_get_wait", "hydragnn-device-prefetch",
                         t, t + 25, queue="graphloader-prefetch"))
        out.append(_span(ids, "put_group", "hydragnn-device-prefetch",
                         t + 25, t + 30, batches=1, bytes=1000))
        out.append(_span(ids, "put_group", "hydragnn-device-prefetch",
                         t + 60, t + 70, batches=2, bytes=2000))
    # an evaluation's readback after the window: not the epoch's
    out.append(_span(ids, "epoch_readback", "MainThread", 2200, 2300))
    return out


# the window runs from 1,000 to 2,100 ms: 1.1 s
BY_HAND = {
    "data_wait_pct.train": 100.0 * (2 * 0.070) / 1.1,
    "collate_ms_per_batch.train": 25.0,  # median of 20, 30, 20, 30
    "neighbor_lists_ms_per_batch.train": 4.0,  # median of 3, 5, 3, 5
    "put_ms_per_batch.train": (2 * 15.0) / 6,  # 30 ms of puts, 6 batches
    # collate thread: covers 50 + 60 ms, waits 10 -> 100 ms busy; the put
    # thread covers 2 x 40, waits 2 x 25 -> 30 ms
    "producer_busy_pct.train": 100.0 * 0.100 / 1.1,
    "edge_padding_waste_pct.train": 100.0 * (1 - 1600 / 2000),
    "epoch_refill_ms.train": 30.0,
    "epoch_readback_ms.train": 10.0,
    "dispatch_host_ms.train": 3.0,  # median of 2, 4, 2, 4
}


@pytest.mark.parametrize("name", NEW)
def pytest_reader_on_hand_made_spans(name, monkeypatch):
    import run
    import span_window

    read = run.load_reader("layer_metrics", name)
    harness = {"window": {"epochs": 2, "window_s": 1.2}}
    monkeypatch.setattr(span_window, "recorded", _hand_made)
    assert read(harness) == pytest.approx(BY_HAND[name], rel=1e-9)
    # the ring holds fewer epochs than the window had, or nothing at all,
    # or the program has no recorder: nothing to read, and no error
    assert read({"window": {"epochs": 4}}) is None
    monkeypatch.setattr(span_window, "recorded", lambda: [])
    assert read(harness) is None
    monkeypatch.setattr(span_window, "recorded", lambda: None)
    assert read(harness) is None


def pytest_summary_names_dark_time_and_coverage(monkeypatch):
    import span_window

    monkeypatch.setattr(span_window, "recorded", _hand_made)
    win = span_window.window_spans({"window": {"epochs": 2}})
    assert win["loop"] == "MainThread" and win["seconds"] == pytest.approx(1.1)
    s = span_window.summary(win)
    # train's children cover 86 of each epoch's 100 ms
    assert s["train_self_pct"] == pytest.approx(100.0 * 0.028 / 1.1)
    # the collate thread is covered from first to last span; the put thread
    # has 30 dark ms (30-60) of its 70 active ms an epoch
    assert s["producer_coverage_pct"] == {
        "graphloader-prefetch": pytest.approx(100.0),
        "hydragnn-device-prefetch": pytest.approx(100.0 * 40 / 70),
    }
    per_epoch = s["seconds_per_epoch"]
    assert per_epoch["MainThread:dataload"] == pytest.approx(0.070)
    assert per_epoch["graphloader-prefetch:queue_put_wait"] == pytest.approx(0.005)


def pytest_no_recorder_no_metric(monkeypatch):
    """Laid over a program that has no ``tracer.spans`` (the parent of the
    PR that brought the recorder) every reader returns None."""
    import run
    import span_window

    from hydragnn_tpu.utils import tracer

    monkeypatch.delattr(tracer, "spans")
    assert span_window.recorded() is None
    for name in NEW:
        assert run.load_reader("layer_metrics", name)(
            {"window": {"epochs": 2, "window_s": 1.0}}) is None


def pytest_tiny_traced_cell_prints_all_nine(tmp_path, monkeypatch):
    """``--trace 1``'s path on the CPU at tiny size: the profiler runs, the
    device-trace reduction is stood in for (the CPU has no device plane),
    and every new metric is in the result, from the spans of the window."""
    import run

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    with open(os.path.join(FIXTURE, "cells.json")) as f:
        cells = json.load(f)
    with open(os.path.join(ROOT, cells["configs"][0]["file"])) as f:
        config = json.load(f)
    # neighbour lists are the dense path's: ask for it by name on the CPU
    config["NeuralNetwork"]["Architecture"]["dense_aggregation"] = True
    config_file = tmp_path / "tiny_pna_dense.json"
    config_file.write_text(json.dumps(config))
    cells["configs"][0]["file"] = str(config_file)
    cells["per_layer"] = [
        {k: v for k, v in m.items() if k != "workloads"}
        for m in real["per_layer"] if m["name"] in NEW
    ] + [m for m in cells["per_layer"] if m["name"] == "input_wait_pct.train"]
    assert [m["name"] for m in cells["per_layer"]][:-1] == NEW
    benchmark_file = tmp_path / "cells.json"
    benchmark_file.write_text(json.dumps(cells))

    def stand_in(trace_dir, step_modules, collective_ops, keep_trace):
        import trace_reduce

        assert trace_reduce.find_xplane(trace_dir)  # the profiler did run
        return {"steps": 6, "busy_s": 1.0, "window_s": 2.0, "device_ops": [],
                "busiest_busy_s": 1.0, "step_busy_s": 1.0, "intervals_s": [],
                "idle_gaps": []}

    monkeypatch.setattr(run, "reduce_trace", stand_in)
    here = os.getcwd()
    try:
        r = run.run_cell(
            "tiny_pna_train", 2**31 + 7, 0.3, True, require_chip=False,
            benchmark_file=str(benchmark_file), files=FIXTURE,
            out_dir=str(tmp_path / "out"),
        )
    finally:
        os.chdir(here)
    assert r["correct"] is True, r["compared"]
    metrics = r["metrics"]
    assert set(NEW) <= set(metrics)
    assert all(metrics[name]["value"] > 0 for name in NEW), metrics
    # one clock, read twice: the ledger was told the dataload spans' seconds
    assert metrics["data_wait_pct.train"]["value"] == pytest.approx(
        metrics["input_wait_pct.train"]["value"], abs=1.0)
