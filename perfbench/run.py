#!/usr/bin/env python3
"""The benchmark's entry: one run of one cell.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell as ``hydragnn_tpu.train.driver.run_training_impl`` does
(seeded graphs in the serialized-pickle format -> the program's loaders ->
``update_config`` -> ``_build_model_and_trainer``, telemetry on, the
persistent compile cache on, no ``HYDRAGNN_*`` variable set), gives the
model the benchmark's own seeded weights, runs untimed epochs until every
program is compiled, then loops ``loader.set_epoch(e);
trainer.train_epoch(state, loader, rng)`` until ``--seconds`` have passed
and the epoch in flight has been read back. The last stdout line is the
result; a run off-TPU, on a device missing from ``peaks.json``, or with a
compilation inside the window, fails and prints none.
"""

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(HERE, "out")
TRACE_SECONDS = 6.0  # a traced window stops at the first epoch end past this
READERS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}  # directories
# The window replays the mix's ``epoch_cycle`` shuffles in turn (each
# re-collated on the host every time) and set-up runs each once, so no epoch
# of the window has a batch count or a bucket order that is new: a bucket
# that packs one batch more compiles a step program in mid-run (PERF.md
# section 7). A mix without ``epoch_cycle`` reshuffles afresh every epoch.
# The first shuffle opens with a single-step dispatch, so the check sees the
# first gradient alone, and holds a ``train_multi`` group for it to follow.


def log(**fields):
    print(json.dumps(fields, default=str), file=sys.stderr, flush=True)


def scrub_environment():
    """No ``HYDRAGNN_*`` knob reaches the program: precision, aggregation
    path and kernels are what its policy picks. ``BENCH_RUN`` is ignored."""
    for key in [k for k in os.environ if k.startswith("HYDRAGNN_")]:
        del os.environ[key]


def find_devices(chips, require_chip, peaks_table):
    import jax

    devices = jax.devices()
    info = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if require_chip:
        if info["platform"] != "tpu":
            raise SystemExit(f"no accelerator: JAX reports {info}")
        if info["count"] != chips:
            raise SystemExit(f"the cell needs {chips} chip(s): JAX reports {info}")
        if info["kind"] not in peaks_table:
            raise SystemExit(f"device kind {info['kind']!r} is not in peaks.json")
    peaks = peaks_table.get(info["kind"]) or next(iter(peaks_table.values()))
    return info, peaks


def seeded_key(seed):
    """A PRNG key from any whole number (seeds pass 2**31)."""
    import jax

    return jax.random.fold_in(
        jax.random.PRNGKey(seed >> 31), seed & 0x7FFFFFFF
    )


def load_reader(kind, name):
    """``read(run)`` of the metric ``name``: ``<kind>/<name>.py``."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        kind + "_" + name.replace(".", "_").replace("-", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def metrics_for(benchmark, cell_name, kind):
    """The metrics of ``kind`` ('end_to_end' / 'per_layer') this cell
    reports: those without a ``workloads`` key, or that list the cell."""
    return [
        m for m in benchmark[kind]
        if "workloads" not in m or cell_name in m["workloads"]
    ]


def install_weights(trainer, state, ref, arch, dims, seed):
    """The benchmark's own weights, made on the device in one jitted call
    from the seed, put in place of the program's initial ones. The trees
    must agree leaf for leaf."""
    import jax

    make = jax.jit(
        lambda key: ref.init_params(key, arch, dims["input_dim"], dims["out_dims"])
    )
    ref_params = make(seeded_key(seed))
    ours = ref.to_program(ref_params)
    want = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), state.params)
    have = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), ours)
    if want != have:
        raise RuntimeError(
            "the reference's parameter tree is not the program's:\n"
            f"program: {want}\nreference: {have}"
        )
    state = trainer.place_state(state.replace(params=ours))
    return state, jax.device_get(ref_params)


class DataWait:
    """Reads the seconds ``Trainer._prefetch_put`` reports to the goodput
    ledger (``ledger.data_wait``) while still passing them on."""

    def __init__(self, telemetry):
        self.seconds = 0.0
        self.ledger = getattr(telemetry, "ledger", None)
        if self.ledger is not None:
            self._orig = self.ledger.data_wait
            self.ledger.data_wait = self._note

    def _note(self, seconds):
        self.seconds += max(float(seconds), 0.0)
        self._orig(seconds)


def run_window(trainer, state, loader, rng, first_epoch, seconds, n_graphs,
               cycle):
    """Whole epochs of the program's own loop until ``seconds`` have
    passed; the clock stops after the last epoch's readback. Epoch ``e``
    takes the loader's shuffle ``cycle[e % len(cycle)]``, or ``e`` itself
    where the mix has no cycle."""
    import jax.profiler
    import numpy as np

    from hydragnn_tpu.obs import runtime as obs

    epoch, steps, graphs, losses = first_epoch, 0, 0, []
    real_rows = padded_rows = 0
    t0 = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("perfbench.set_epoch"):
            obs.epoch_start(epoch)
            loader.set_epoch(cycle[epoch % len(cycle)] if cycle else epoch)
        with jax.profiler.TraceAnnotation("perfbench.train_epoch"):
            state, rng, loss, _ = trainer.train_epoch(state, loader, rng)
        steps_now = len(loader)
        steps += steps_now
        graphs += n_graphs
        losses.append((float(loss), steps_now))
        stats = loader.epoch_padding_stats()
        if stats is not None:
            real_rows += stats[0]
            padded_rows += stats[1]
        epoch += 1
        if time.perf_counter() - t0 >= seconds:
            break
    t1 = time.perf_counter()
    failed = sum(n for loss, n in losses if not np.isfinite(loss))
    return state, rng, {
        "window_s": t1 - t0, "epochs": epoch - first_epoch, "steps": steps,
        "graphs": graphs, "failed": failed, "real_rows": real_rows,
        "padded_rows": padded_rows, "next_epoch": epoch,
        "losses": [l for l, _ in losses],
    }


def program_events(out_dir):
    """What the program's own event stream says it compiled and which
    aggregation path each bucket took (printed, not metrics)."""
    import glob

    compiled, choices = [], []
    for path in glob.glob(os.path.join(out_dir, "logs", "*", "events.jsonl")):
        with open(path) as f:
            for line in f:
                e = json.loads(line)
                if e.get("event") == "compile":
                    compiled.append({
                        "name": e.get("name"), "bucket": e.get("bucket"),
                        "kernels": e.get("kernels"),
                        "peak_bytes": (e.get("memory") or {}).get("peak_bytes"),
                    })
                elif e.get("event") == "agg_choice":
                    choices.append({k: e.get(k) for k in ("bucket", "choice", "source")})
    return {"compiled": compiled, "agg_choice": choices}


def memory_peak():
    """(peak bytes, limit) of the fullest device, as the runtime reports
    them. This runtime counts a running program's temporaries under
    ``peak_bytes_reserved`` and everything else under
    ``peak_bytes_in_use`` (PERF.md section 4), so the peak is their sum."""
    import jax

    peak, limit = 0, 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        used = int(stats.get("peak_bytes_in_use", 0)) + int(
            stats.get("peak_bytes_reserved", 0)
        )
        if used >= peak:
            peak, limit = used, int(stats.get("bytes_limit", 0))
    return peak, limit


def decide_correct(check, ref, config, workload, files, graphs, batch_size,
                   dispatches, ref_params, window_numbers, control):
    """The reference follows the recorded steps over the RAW graphs; returns
    (correct, {name: value and limit}, control readings, the training set's
    in-degrees). Runs after the window, with the program freed."""
    import jax

    from reference import common as ref_common

    t = time.perf_counter()
    arch = config["NeuralNetwork"]["Architecture"]
    lr = float(config["NeuralNetwork"]["Training"]["Optimizer"]["learning_rate"])
    program, marks = check.program_side(dispatches)
    step_graphs = [g for d in dispatches for g in check.graphs_of(d, graphs)]
    degrees = ref_common.in_degrees(graphs, arch["radius"], arch["max_neighbours"])
    degrees_s = time.perf_counter() - t
    stats = ref.prepare(arch, degrees)
    shape = check.largest_batch(graphs, degrees, batch_size)
    start_params = jax.device_get(ref.to_program(ref_params))

    def side(**kw):
        return check.follow(
            ref, ref_params, arch, stats, step_graphs, lr, shape, **kw
        )

    reference = side()
    numbers, notes = check.compare(program, reference, start_params, marks)
    numbers.update(window_numbers)
    ok, report = check.verdict(numbers, check.load_limits(workload, files))
    log(phase="check", seconds=time.perf_counter() - t, degrees_s=degrees_s,
        steps_followed=len(step_graphs), marks=marks,
        program_losses=program["losses"].tolist(),
        reference_losses=reference["losses"].tolist(),
        **dict(notes, numbers=numbers))
    controls = {}
    for name in control or ():
        kw = {"drop_half": True} if name == "half_batch" else {"rounding": name}
        controls[name], _ = check.compare(side(**kw), reference, start_params, marks)
    return ok, report, controls, degrees


def reduce_trace(trace_dir, step_modules, collective_ops, keep_trace):
    """The traced window's summary (``trace_reduce.reduce``); a trace with
    no device operation is an error."""
    import trace_reduce

    t = time.perf_counter()
    spans = {"perfbench.set_epoch", "perfbench.train_epoch", "train",
             "dataload", "train_step"}
    loaded = trace_reduce.load_xplane(
        trace_reduce.find_xplane(trace_dir), keep_host=spans.__contains__
    )
    summary = trace_reduce.reduce(
        loaded, step_modules, collective_ops,
        window=trace_reduce.span_window(loaded, "perfbench."),
    )
    if summary is None or summary["busy_s"] <= 0:
        raise SystemExit("the trace holds no device operation")
    log(phase="trace", seconds=time.perf_counter() - t,
        steps_in_trace=summary["steps"], devices=summary["devices"])
    if not keep_trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return summary


def run_cell(workload, seed, seconds, trace, require_chip=True, control=None,
             out_dir=None, benchmark_file=None, files=HERE, rung=None,
             keep_trace=False):
    """One run; returns the result dict (the last stdout line).

    For ``calibrate.py`` and the self-checks only: ``control`` names
    controls or planted faults whose readings are added, each computed by
    the reference in the program's place ('fp8', 'bf16', 'half_batch');
    ``rung`` another batch size; ``benchmark_file`` / ``files`` the tests'
    tiny cells, run with ``require_chip=False``."""
    scrub_environment()
    sys.path[:0] = [p for p in (HERE, ROOT) if p not in sys.path]
    import build
    import check

    with open(benchmark_file or os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    cell, config, mix = build.load_cell(workload, benchmark, files)
    peaks_table = build.load_json("peaks.json")
    device, peaks = find_devices(cell["chips"], require_chip, peaks_table)
    program_names = build.load_json("program.json")

    import jax

    from hydragnn_tpu.obs import runtime as obs

    obs.install_compile_listener()
    out_dir = out_dir or os.path.join(OUT, workload)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    os.chdir(out_dir)  # the program writes ./logs relative to the cwd

    # ---- set-up: data, program, weights, warm epochs --------------------
    import traffic_gen

    batch_size = rung or build.batch_size_for(mix, cell["chips"])
    n_train = batch_size * mix["dataset_batches"]
    t = time.perf_counter()
    graphs = traffic_gen.make_graphs(mix, n_train, seed)
    evals = traffic_gen.make_graphs(
        mix, mix["eval_graphs"], seed + 1, first=n_train
    )
    paths = build.write_dataset(out_dir, graphs, evals)
    log(phase="data", seconds=time.perf_counter() - t, graphs=n_train,
        atoms=int(sum(len(g["pos"]) for g in graphs)))

    t = time.perf_counter()
    cfg = build.hydragnn_config(config, mix, cell, paths, batch_size)
    if trace:
        # the program's own region spans, written into the profiler's trace
        from hydragnn_tpu.utils import tracer as tr

        tr.initialize(("jax",))
    cfg, loader, model, trainer, state, telemetry, timings = build.build_program(cfg)
    arch = cfg["NeuralNetwork"]["Architecture"]
    training = cfg["NeuralNetwork"]["Training"]
    dims = {"input_dim": int(arch["input_dim"]),
            "out_dims": [int(d) for d in arch["output_dim"]]}
    ref = check.load_reference(config["model_type"])
    state, ref_params = install_weights(trainer, state, ref, arch, dims, seed)
    log(phase="build", seconds=time.perf_counter() - t, **timings,
        dense_aggregation=arch.get("dense_aggregation"),
        batches_per_epoch=len(loader), batch_size=batch_size)

    t = time.perf_counter()
    wait = DataWait(telemetry)
    recorder = check.Recorder(trainer)
    cycle = tuple(mix.get("epoch_cycle") or ())
    rng = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    state, rng, warm = run_window(
        trainer, state, loader, rng, 0, 0.0, n_train, cycle
    )
    dispatches = recorder.fetch()
    if recorder.covered < check.STEPS:
        raise RuntimeError("the first epoch made fewer than 3 optimizer steps")
    warm2 = warm
    # one more epoch for each further shuffle of the cycle (one without a cycle)
    for _ in cycle[1:] or (None,):
        state, rng, warm2 = run_window(
            trainer, state, loader, rng, warm2["next_epoch"], 0.0, n_train,
            cycle,
        )
    compile_s = obs.compile_seconds()
    compiles_before = obs.compile_events()
    wait.seconds = 0.0
    recorder.count_window()
    log(phase="warm", seconds=time.perf_counter() - t, compile_s=compile_s,
        compiles=compiles_before, epoch_s=[warm["window_s"], warm2["window_s"]])

    # ---- the measured window --------------------------------------------
    trace_dir = os.path.join(out_dir, "trace")
    if trace:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        seconds = min(seconds, TRACE_SECONDS)
    setup_s = time.perf_counter() - T_START
    state, rng, window = run_window(
        trainer, state, loader, rng, warm2["next_epoch"], seconds, n_train,
        cycle,
    )
    if trace:
        jax.profiler.stop_trace()
    compiled_inside = obs.compile_events() - compiles_before
    peak_bytes, bytes_limit = memory_peak()
    log(phase="window", **{k: v for k, v in window.items() if k != "losses"},
        loss_first=window["losses"][0], loss_last=window["losses"][-1],
        compiled_inside=compiled_inside, data_wait_s=wait.seconds)
    log(phase="programs", **program_events(out_dir))
    if compiled_inside:
        raise SystemExit(
            f"{compiled_inside} compilation(s) inside the measured window"
        )

    # ---- correct: the reference follows the recorded steps ---------------
    trained = recorder.window_graphs()
    window_numbers = {
        "window_graphs_gap": abs(trained - window["graphs"]) / window["graphs"],
    }
    log(phase="counted", graphs_in_rate=window["graphs"], graphs_trained=trained)
    obs.deactivate(status="complete")
    del trainer, state, loader, model, recorder
    gc.collect()
    jax.clear_caches()
    ok, report, controls, degrees = decide_correct(
        check, ref, config, workload, files, graphs, batch_size, dispatches,
        ref_params, window_numbers, control,
    )
    ok = ok and window["failed"] == 0

    # ---- metrics -----------------------------------------------------------
    run = {
        "cell": cell, "device": device, "peaks": peaks, "setup_s": setup_s,
        "compile_s": compile_s, "window": window, "data_wait_s": wait.seconds,
        "peak_bytes": peak_bytes, "bytes_limit": bytes_limit,
        "trace": None, "work": None,
    }
    device_out = dict(device, memory_peak_bytes=peak_bytes)
    result = {"correct": bool(ok), "attempted": window["steps"],
              "failed": window["failed"]}
    breakdown = None
    if trace:
        step_modules = {
            name: per if isinstance(per, int) else int(training[per])
            for name, per in program_names["step_modules"].items()
        }
        summary = reduce_trace(
            trace_dir, step_modules, program_names["collective_ops"], keep_trace
        )
        work_mod = importlib.import_module("work." + config["model_type"])
        per_epoch = work_mod.required(
            config["NeuralNetwork"]["Architecture"], dims["input_dim"],
            dims["out_dims"], sum(len(g["pos"]) for g in graphs),
            int(sum(d.sum() for d in degrees)), n_train,
            window["steps"] // window["epochs"],
        )
        # the required work of the steps the TRACE holds (all of the window's)
        share = window["epochs"] * summary["steps"] / window["steps"]
        run["trace"] = summary
        run["work"] = {k: v * share for k, v in per_epoch.items()}
        device_out["busy_s"] = summary["busy_s"]
        device_out["window_s"] = summary["window_s"]
        breakdown = {"device_ops": summary["device_ops"],
                     "idle_gaps": summary["idle_gaps"]}
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metrics_for(benchmark, workload, kind):
        value = load_reader(READERS[kind], m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device_out
    if breakdown is not None:
        result["breakdown"] = breakdown
    if controls:
        result["control"] = controls
    result["compared"] = report
    for name, pair in report.items():
        print(f"compared {name} {pair['value']} limit {pair['limit']}",
              file=sys.stderr)
    print(f"correct {result['correct']} failed_steps {window['failed']}",
          file=sys.stderr, flush=True)
    return result


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
