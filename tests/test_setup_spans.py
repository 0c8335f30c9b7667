"""The main thread outside the step loop under the recorder's spans
(``docs/observability.md``, "Training spans"): set-up through the entry
points ``run_training`` uses (``dataset_loading_and_splitting``,
``_build_model_and_trainer``) with counts equal to what the data holds, the
epoch boundary of ``train_epoch`` / ``evaluate`` (``epoch_open``, ``settle``,
``drain``), the epoch's ``batch_plan`` computed once, a ``prefetch_iter``
that says when its worker is up, and nothing recorded while the recorder is
off."""

import os
import threading

import pytest

import jax

import chip_smoke as cs
from hydragnn_tpu.data.loaders import (
    GraphLoader,
    compute_layout,
    dataset_loading_and_splitting,
    prefetch_iter,
)
from hydragnn_tpu.obs import runtime as obs
from hydragnn_tpu.train.driver import _build_model_and_trainer
from hydragnn_tpu.utils import tracer as tr
from hydragnn_tpu.utils.config import update_config

from test_prefetch_loader import _dataset
from test_tracer_spans import _by_name, _tiny_run, recorder  # noqa: F401

SIZES = dict(hidden=8, conv_layers=1, batch=4, atoms=(10, 16), train_graphs=12,
             eval_graphs=4, epochs=1, steps_per_dispatch=2)
SPLITS = ("train", "validate", "test")
SETUP = ("load_datasets", "read_split", "radius_graph", "finish_split",
         "sample_stats", "compute_layout", "init_state")
BOUNDARY = ("epoch_open", "split_rng", "settle", "drain")


def _set_up(tmp_path, monkeypatch, periodic, dense):
    """(paths, loaders, trainer, state): chip_smoke's slabs at a tiny size
    through the driver's own calls, read with or without their cell."""
    monkeypatch.setattr(cs, "OUT_DIR", str(tmp_path))
    monkeypatch.chdir(tmp_path)
    sz = dict(cs.FULL, **SIZES)
    paths = cs.write_dataset(sz, "spans")
    config = cs.make_config(sz, "spans", paths)
    arch = config["NeuralNetwork"]["Architecture"]
    arch["periodic_boundary_conditions"] = periodic
    arch["dense_aggregation"] = dense
    loaders = dataset_loading_and_splitting(config)
    config = update_config(config, *loaders)
    _, trainer, state = _build_model_and_trainer(config, loaders[0], 0)
    return paths, loaders, trainer, state


@pytest.mark.parametrize("periodic,dense", [(True, True), (False, False)],
                         ids=["periodic-dense", "open-edges"])
def pytest_set_up_spans_count_what_the_data_holds(
    recorder, tmp_path, monkeypatch, periodic, dense
):
    assert obs.install_compile_listener()
    paths, loaders, trainer, state = _set_up(
        tmp_path, monkeypatch, periodic, dense)
    spans = _by_name(tr.spans().records)
    main = threading.current_thread().name

    (load,) = spans["load_datasets"]
    assert load.parent == 0 and load.attrs == {"splits": 3}
    for name in ("read_split", "radius_graph", "finish_split"):
        assert len(spans[name]) == 3, name
    for name in ("sample_stats", "compute_layout"):
        assert len(spans[name]) == 1, name
    inside = [s for name in SETUP[1:6] for s in spans[name]]
    assert all(s.parent == load.id and s.thread == main for s in inside)
    assert all(load.start_ns <= s.start_ns and s.end_ns <= load.end_ns
               for s in inside)
    # one stage after the other, split by split, then the layout
    order = sorted(inside, key=lambda s: s.start_ns)
    assert [s.name for s in order] == (
        ["read_split", "radius_graph", "finish_split"] * 3
        + ["sample_stats", "compute_layout"])
    assert all(a.end_ns <= b.start_ns for a, b in zip(order, order[1:]))

    for split, loader, read, graph, finish in zip(
        SPLITS, loaders, spans["read_split"], spans["radius_graph"],
        spans["finish_split"],
    ):
        data = loader.dataset
        assert read.attrs == {
            "split": os.path.basename(paths[split]), "graphs": len(data),
            "bytes": os.path.getsize(paths[split])}
        assert graph.attrs == {
            "graphs": len(data), "periodic": periodic,
            "max_neighbours": cs.MAX_NEIGHBOURS,
            "atoms": sum(d.num_nodes for d in data),
            "edges": sum(d.num_edges for d in data)}
        assert graph.attrs["edges"] > 0
        assert finish.attrs == {"graphs": len(data)}

    total = sum(len(loader.dataset) for loader in loaders)
    (stats,) = spans["sample_stats"]
    assert stats.attrs == {
        "graphs": total, "need_neighbors": dense, "need_triplets": False,
        "slots_built": total if dense else 0}
    (layout,) = spans["compute_layout"]
    layouts = loaders[0].layout.layouts
    assert len(layouts) == 2
    assert layout.attrs == {
        "buckets": 2,
        "n_pad": [lay.n_pad for lay in layouts],
        "e_pad": [lay.e_pad for lay in layouts]}

    (init,) = spans["init_state"]
    leaves = jax.tree_util.tree_leaves(state.params)
    assert init.parent == 0 and init.start_ns >= load.end_ns
    assert init.attrs == {
        "params": len(leaves),
        "param_bytes": sum(int(a.nbytes) for a in leaves)}
    # what the initial state traced and compiled lies under it
    mine = [s for s in spans["compile"] if s.parent == init.id]
    assert {"jaxpr_trace_duration", "backend_compile_duration"} <= {
        s.attrs["event"] for s in mine}
    # the example batch asked the loader for its first plan: once
    assert len(spans["bucket_assignments"]) == 1
    assert spans["bucket_assignments"][0].attrs == {
        "graphs": len(loaders[0].dataset)}
    assert len(spans["batch_plan"]) == 1


@pytest.mark.parametrize("prefetch,device_prefetch", [(2, 2), (0, 0)])
def pytest_each_train_root_opens_settles_and_drains(
    recorder, prefetch, device_prefetch
):
    ds, layout, loader, trainer, state = _tiny_run(prefetch, device_prefetch)
    rng = jax.random.PRNGKey(0)
    for epoch in range(2):
        loader.set_epoch(epoch)
        state, rng, _, _ = trainer.train_epoch(state, loader, rng)
    spans = _by_name(tr.spans().records)
    roots = spans["train"]
    assert len(roots) == 2 and all(r.parent == 0 for r in roots)
    for root in roots:
        mine = {name: [s for s in found if s.parent == root.id]
                for name, found in spans.items()}
        (opening,) = mine["epoch_open"]
        (settle,) = mine["settle"]
        (readback,) = mine["epoch_readback"]
        steps = mine["train_step"]
        # the root opens with it, and the first wait follows it
        first_wait = min(mine["dataload"], key=lambda s: s.start_ns)
        assert root.start_ns <= opening.start_ns
        assert opening.end_ns <= first_wait.start_ns
        assert opening.attrs == {
            "prefetch": device_prefetch, "batches": len(loader),
            "groups": len(steps)}
        assert sum(s.attrs["steps"] for s in steps) == len(loader)
        # every dispatch's keys are split under a span of its own, so the
        # loop thread goes from a wait to a step under a name
        keys = sorted(mine["split_rng"], key=lambda s: s.start_ns)
        assert [s.attrs for s in keys] == [
            {"steps": s.attrs["steps"]} for s in steps]
        assert all(k.end_ns <= s.start_ns for k, s in zip(keys, steps))
        # the last pooled put was still the trainer's to give back
        assert settle.attrs == {"waited": True}
        assert max(s.end_ns for s in steps) <= settle.start_ns
        assert settle.end_ns <= readback.start_ns
        (drain,) = [s for s in spans["drain"] if s.parent == readback.id]
        assert drain.attrs == {"dispatches": readback.attrs["dispatches"]}
        assert readback.start_ns <= drain.start_ns
        assert drain.end_ns <= readback.end_ns <= root.end_ns
    assert len(spans["drain"]) == len(spans["settle"]) == 2
    assert {s.thread for name in BOUNDARY for s in spans[name]} == {
        threading.current_thread().name}


def pytest_evaluate_settles_and_drains_as_roots(recorder):
    ds, layout, loader, trainer, state = _tiny_run(0, 0, steps_per_dispatch=1)
    trainer.evaluate(state, loader)
    spans = _by_name(tr.spans().records)
    (settle,), (readback,), (drain,) = (
        spans["settle"], spans["epoch_readback"], spans["drain"])
    assert settle.parent == readback.parent == 0
    assert drain.parent == readback.id
    assert drain.attrs["dispatches"] == len(loader)
    assert "epoch_open" not in spans and "train" not in spans


def pytest_cached_batch_plan_leaves_no_second_span(recorder):
    ds = _dataset(26)
    layout = compute_layout([ds], batch_size=4, num_buckets=2)
    tr.reset()
    loader = GraphLoader(ds, 4, layout, shuffle=True, prefetch=0)
    assert len(list(loader)) == len(loader) > 0
    list(loader)  # the epoch's plan is cached: iteration packs nothing
    spans = _by_name(tr.spans().records)
    (sizes,), (plan,) = spans["bucket_assignments"], spans["batch_plan"]
    assert sizes.attrs == {"graphs": len(ds)}
    assert plan.attrs == {"batches": len(loader), "buckets": 2}
    # siblings: the sizes pass is not part of the first plan's time
    assert sizes.parent == plan.parent == 0 and sizes.end_ns <= plan.start_ns
    loader.set_epoch(1)
    list(loader)
    spans = _by_name(tr.spans().records)
    assert len(spans["batch_plan"]) == 2
    assert len(spans["bucket_assignments"]) == 1


@pytest.mark.parametrize("switched", ["never_on", "disabled"])
def pytest_recorder_off_none_of_the_sites_records(
    monkeypatch, tmp_path, switched
):
    monkeypatch.setattr(tr, "_state", tr._State())
    if switched == "disabled":
        tr.initialize()
        tr.disable()
    paths, loaders, trainer, state = _set_up(
        tmp_path, monkeypatch, periodic=True, dense=True)
    state, _, loss, _ = trainer.train_epoch(
        state, loaders[0], jax.random.PRNGKey(0))
    trainer.evaluate(state, loaders[1])
    assert loss == loss  # the epoch ran
    assert tr.spans().records == [] and tr.totals() == {}
    assert tr._state.stack() == []  # and left nothing open


def pytest_primed_prefetch_iter_says_when_its_worker_is_up():
    started = threading.Event()
    release = threading.Event()

    def source():
        started.set()
        assert release.wait(10)
        yield from range(3)

    it = prefetch_iter(source(), depth=2, name="primed-test", primed=True)
    assert next(it) is None  # before any item, without waiting for one
    assert started.wait(10)  # the worker runs
    assert any(t.name == "primed-test" for t in threading.enumerate())
    release.set()
    assert list(it) == [0, 1, 2]
    # a consumer that leaves right after the start-up still reaps the worker
    it = prefetch_iter(iter(range(100)), depth=1, name="primed-left",
                       primed=True)
    assert next(it) is None
    it.close()
    assert not any(t.name == "primed-left" for t in threading.enumerate())
