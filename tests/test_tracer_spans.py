"""The span recorder (``hydragnn_tpu/utils/tracer.py``) and the spans of the
training hot path: per-thread parents, the bounded ring, the files of
``save()``, compile spans, the stages of one tiny ``train_epoch`` with
counts that add up, the ledger fed from the spans' own clock, and the
device-side names in the lowered step."""

import json
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hydragnn_tpu.data.loaders import GraphLoader, compute_layout
from hydragnn_tpu.models import create_model_config
from hydragnn_tpu.obs import runtime as obs
from hydragnn_tpu.train.trainer import Trainer
from hydragnn_tpu.utils import tracer as tr

from test_models_forward import arch_config
from test_prefetch_loader import _dataset


@pytest.fixture
def recorder(monkeypatch):
    """A fresh recorder in the module's place, switched on."""
    monkeypatch.delenv("HYDRAGNN_TRACE_LEVEL", raising=False)
    monkeypatch.setattr(tr, "_state", tr._State())
    tr.initialize()
    return tr


def _by_name(records):
    out = {}
    for s in records:
        out.setdefault(s.name, []).append(s)
    return out


def pytest_two_threads_keep_their_own_parents(recorder):
    """Spans open on two threads at once nest under the span open on their
    OWN thread, whatever the other thread has open."""
    inner_open = threading.Event()
    release = threading.Event()

    def producer():
        with tr.span("collate", graphs=3):
            with tr.span("fetch"):
                inner_open.set()
                assert release.wait(10)

    t = threading.Thread(target=producer, name="producer-under-test")
    outer = tr.start("train")
    t.start()
    assert inner_open.wait(10)
    # the producer holds collate > fetch open while this thread nests
    step = tr.start("train_step", steps=1)
    step.stop()
    release.set()
    t.join(10)
    assert not t.is_alive()
    tr.stop("train")
    spans = _by_name(tr.spans().records)
    (train,), (step,) = spans["train"], spans["train_step"]
    (collate,), (fetch,) = spans["collate"], spans["fetch"]
    assert train.parent == 0 and step.parent == train.id
    assert collate.parent == 0 and fetch.parent == collate.id
    assert train.thread == step.thread == threading.current_thread().name
    assert collate.thread == fetch.thread == "producer-under-test"
    assert collate.attrs == {"graphs": 3} and step.attrs == {"steps": 1}
    assert train.start_ns <= collate.start_ns and fetch.end_ns <= train.end_ns
    assert set(tr.totals()) == {
        "train", "train/train_step", "collate", "collate/fetch"
    }
    assert len({s.id for s in tr.spans().records}) == 4


def pytest_ring_is_bounded_and_totals_are_not(monkeypatch):
    monkeypatch.setattr(tr, "_state", tr._State(maxlen=8))
    tr.initialize()
    for i in range(50):
        with tr.span("tick", i=i):
            pass
    log = tr.spans()
    assert len(log.records) == 8
    assert [s.attrs["i"] for s in log.records] == list(range(42, 50))
    # running sums: what the ring forgot still counts
    with tr._state.lock:
        assert tr._state.totals["tick"][0] == 50
    wall_ns, perf_ns = log.anchor
    assert wall_ns > 1_600_000_000 * 10**9 and perf_ns <= log.records[0].start_ns
    tr.reset()
    assert tr.spans().records == [] and tr.totals() == {}


def pytest_off_recorder_still_times_and_records_nothing(monkeypatch):
    """Before ``initialize`` and after ``disable`` a span is a pair of clock
    reads its caller can still hand on; nothing reaches ring or totals."""
    monkeypatch.setattr(tr, "_state", tr._State())
    with tr.span("dark") as s:
        pass
    assert s.seconds >= 0.0 and tr.spans().records == []
    tr.initialize()
    tr.disable()
    region = tr.start("dark")
    assert tr.stop("dark") is region and region.seconds >= 0.0
    assert tr.totals() == {}
    tr.enable()
    tr.start("lit")
    tr.start("never_stopped")
    tr.stop("lit")  # a missed stop is dropped, not re-parented
    assert tr.stop("nothing_open") is None
    assert list(tr.totals()) == ["lit"]

    @tr.profile("decorated")
    def work():
        return 7

    assert work() == 7 and "decorated" in tr.totals()


def pytest_save_writes_table_and_chrome_trace(recorder, tmp_path):
    def producer():
        with tr.span("collate", graphs=np.int64(2)):
            pass

    tr.start("train")
    t = threading.Thread(target=producer, name="p0")
    t.start()
    t.join(10)
    tr.start("train_step")
    tr.stop("train_step")
    tr.stop("train")
    tr.save(str(tmp_path / "trace"))
    table = (tmp_path / "trace.0").read_text().splitlines()
    assert table[0].split() == [
        "region", "calls", "total_s", "avg_ms", "min_ms", "max_ms"]
    assert [line.split()[0] for line in table[1:]] == [
        "collate", "train", "train_step"]
    assert table[3].startswith("  train_step")  # the call tree, indented
    events = json.loads((tmp_path / "trace.0.trace.json").read_text())
    spans = {e["name"]: e for e in events if e["ph"] == "X"}
    assert set(spans) == {"collate", "train", "train_step"}
    assert spans["train"]["tid"] == spans["train_step"]["tid"] != spans["collate"]["tid"]
    assert spans["train_step"]["args"]["parent"] == spans["train"]["args"]["id"]
    names = {e["tid"]: e["args"]["name"] for e in events if e["ph"] == "M"}
    assert names[spans["collate"]["tid"]] == "p0"
    assert spans["train"]["ts"] <= spans["train_step"]["ts"]


def pytest_compile_span_lands_under_the_open_span(recorder):
    """Every duration event of the compile family closes a ``compile``
    span under whatever is open on the compiling thread; the counters stay
    backend compiles only."""
    assert obs.install_compile_listener()
    before = obs.compile_events()

    shape = jax.ShapeDtypeStruct((3,), jnp.float32)

    def compile_under(name, scale):
        with tr.span(name) as outer:
            jax.jit(lambda a: a * scale + 1.0).lower(shape).compile()
        return outer

    main = compile_under("warm", 3.25)
    result = []
    t = threading.Thread(
        target=lambda: result.append(compile_under("warm_other", 4.5)),
        name="compiler-thread",
    )
    t.start()
    t.join(60)
    assert not t.is_alive()
    compiles = _by_name(tr.spans().records)["compile"]
    for outer, thread in ((main, threading.current_thread().name),
                          (result[0], "compiler-thread")):
        mine = [s for s in compiles if s.parent == outer.id]
        assert {s.thread for s in mine} == {thread}
        events = {s.attrs["event"] for s in mine}
        assert "backend_compile_duration" in events
        assert "jaxpr_trace_duration" in events
        assert all(s.attrs["seconds"] >= 0 and s.end_ns <= outer.end_ns
                   for s in mine)
    backend = [s for s in compiles
               if s.attrs["event"] == "backend_compile_duration"]
    assert obs.compile_events() - before == len(backend) == 2
    assert "warm/compile" in tr.totals()


# ---- the hot path ----------------------------------------------------------


def _tiny_run(prefetch, device_prefetch, steps_per_dispatch=2):
    ds = _dataset(26)
    layout = compute_layout([ds], batch_size=4, need_triplets=False)
    loader = GraphLoader(ds, 4, layout, shuffle=True, prefetch=prefetch)
    cfg = dict(arch_config("SAGE"), input_dim=2)
    trainer = Trainer(
        create_model_config(cfg),
        {"Optimizer": {"type": "AdamW", "learning_rate": 1e-3},
         "steps_per_dispatch": steps_per_dispatch,
         "device_prefetch": device_prefetch},
    )
    state = trainer.init_state(next(iter(loader)))
    return ds, layout, loader, trainer, state


@pytest.mark.parametrize("prefetch,device_prefetch", [(2, 2), (0, 0)])
def pytest_train_epoch_spans_and_counts_add_up(
    recorder, tmp_path, prefetch, device_prefetch
):
    ds, layout, loader, trainer, state = _tiny_run(prefetch, device_prefetch)
    rng = jax.random.PRNGKey(0)
    state, rng, _, _ = trainer.train_epoch(state, loader, rng)  # compiles
    telem = obs.init_run_telemetry(
        {"NeuralNetwork": {"Training": {"num_epoch": 1}}}, "spans",
        path=str(tmp_path),
    )
    try:
        tr.reset()
        obs.epoch_start(0)
        loader.set_epoch(1)
        state, rng, loss, _ = trainer.train_epoch(state, loader, rng)
        ledger = telem.ledger
        stall_s, step_s = ledger._data_stall_s, ledger._step_s
    finally:
        obs.deactivate()
    assert np.isfinite(loss)
    records = tr.spans().records
    spans = _by_name(records)
    main = threading.current_thread().name
    nbatch = len(loader)

    # one root ``train`` on the epoch loop; its children are the loop's
    (train,) = spans["train"]
    assert train.parent == 0 and train.thread == main
    for name in ("dataload", "train_step", "acc_add", "epoch_readback"):
        assert all(s.parent == train.id and s.thread == main
                   for s in spans[name]), name
    (readback,) = spans["epoch_readback"]
    assert readback.attrs["dispatches"] == len(spans["train_step"])
    assert len(spans["acc_add"]) == len(spans["train_step"])
    assert sum(s.attrs["steps"] for s in spans["train_step"]) == nbatch
    assert {s.attrs["program"] for s in spans["train_step"]} <= {
        "train_step", "train_multi"}
    assert all(s.attrs["bucket"] == layout.n_pad for s in spans["train_step"])

    # collate: one per batch, counts equal to the loader's own accounting
    collates = spans["collate"]
    assert len(collates) == nbatch
    assert sum(s.attrs["graphs"] for s in collates) == len(ds)
    real, padded = loader.epoch_padding_stats()
    assert sum(s.attrs["nodes"] for s in collates) == real
    assert sum(s.attrs["bucket"] for s in collates) == padded
    assert sum(s.attrs["edges"] for s in collates) == sum(
        d.num_edges for d in ds)
    assert all(s.attrs["e_pad"] == layout.e_pad for s in collates)
    # the trainer holds the release end, so every batch is written into a
    # slot of the loader's pool; one epoch has run: this one makes none
    assert {s.attrs["slot"] for s in collates} <= {"reused", "made"}
    if not prefetch:
        assert {s.attrs["slot"] for s in collates} == {"reused"}
    by_id = {s.id: s for s in records}
    for child in ("fetch", "collate_graphs"):
        assert len(spans[child]) == nbatch
        assert all(by_id[s.parent].name == "collate" for s in spans[child])
    assert "neighbor_lists" not in spans and "triplets" not in spans

    # the transfer stage: every batch goes through one put_group
    puts = spans["put_group"]
    assert sum(s.attrs["batches"] for s in puts) == nbatch
    assert all(s.attrs["bytes"] > 0 for s in puts)
    assert len(spans["h2d"]) == len(puts)
    assert all(by_id[s.parent].name == "put_group" for s in spans["h2d"])
    # ... and gives a put's slots back once its transfer has completed,
    # waited for after the NEXT put's enqueue; the last one's by the epoch
    # loop, under its ``settle``
    waits = spans["h2d_wait"]
    assert len(waits) == len(puts)
    assert [by_id[s.parent].name for s in waits] == (
        ["put_group"] * (len(puts) - 1) + ["settle"])
    assert {s.attrs["slot"] for s in puts} <= {"reused", "made"}
    # a group's batches are stacked at the put, or, where the loader states
    # its plan (GraphLoader does), one by one as they arrive
    grouped = sum(s.attrs["batches"] for s in puts if s.attrs["batches"] > 1)
    assert "stack_batches" not in spans
    assert len(spans.get("stack_batch", ())) == grouped
    assert all(s.thread == puts[0].thread for s in spans.get("stack_batch", ()))
    assert {s.attrs["slot"] for s in spans.get("stack_batch", ())} <= {
        "reused", "made"}

    if prefetch:
        assert {s.thread for s in collates} == {"graphloader-prefetch"}
        assert {s.thread for s in puts} == {"hydragnn-device-prefetch"}
        assert all(s.parent == 0 for s in collates + puts)
        assert all("queue_depth" in s.attrs for s in spans["dataload"])
        waits = spans.get("queue_get_wait", []) + spans.get("queue_put_wait", [])
        assert {s.name: s.attrs for s in waits}  # some stage waited
        assert {s.attrs["queue"] for s in spans.get("queue_get_wait", [])} <= {
            "graphloader-prefetch", "hydragnn-device-prefetch"}
    else:
        # no thread: the epoch loop collates and puts under its dataload
        assert {s.thread for s in collates + puts} == {main}
        assert all(by_id[s.parent].name == "dataload" for s in puts)

    # one clock: the ledger's figures are the spans' seconds
    assert stall_s == pytest.approx(
        sum(s.seconds for s in spans["dataload"]), rel=1e-9)
    assert step_s == pytest.approx(
        sum(s.seconds for s in spans["train_step"]), rel=1e-9)
    totals = tr.totals()
    assert totals["train/train_step"] == pytest.approx(step_s, rel=1e-9)
    assert totals["train/dataload"] == pytest.approx(stall_s, rel=1e-9)


def pytest_evaluate_reads_back_under_its_own_span(recorder):
    ds, layout, loader, trainer, state = _tiny_run(0, 0, steps_per_dispatch=1)
    trainer.evaluate(state, loader)
    (readback,) = _by_name(tr.spans().records)["epoch_readback"]
    assert readback.parent == 0 and readback.attrs["dispatches"] == len(loader)


@pytest.mark.parametrize("model_type,dense,agg", [
    ("PNA", True, "agg_dense"), ("EGNN", False, "agg_segment")])
def pytest_lowered_step_names_its_parts(model_type, dense, agg):
    """``jax.named_scope`` names reach the lowered ``train_step``: the
    aggregation of the path taken, heads, loss, optimizer, and Flax's own
    module scope for each conv layer."""
    ds = _dataset(6)
    cfg = dict(arch_config(model_type), input_dim=2, dense_aggregation=dense)
    layout = compute_layout(
        [ds], batch_size=3, need_triplets=False, need_neighbors=dense)
    batch = next(iter(GraphLoader(ds, 3, layout, shuffle=False, prefetch=0)))
    trainer = Trainer(
        create_model_config(cfg),
        {"Optimizer": {"type": "AdamW", "learning_rate": 1e-3}},
    )
    state = trainer.init_state(batch)
    text = trainer._train_step.lower(
        state, trainer.put_batch(batch), jax.random.PRNGKey(0)
    ).as_text(debug_info=True)
    other = "agg_segment" if dense else "agg_dense"
    for name in (agg, "heads", "loss", "optimizer",
                 "encoder_conv_0", "encoder_conv_1"):
        assert name in text, name
    if not dense:
        assert other not in text
