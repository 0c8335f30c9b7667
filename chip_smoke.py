#!/usr/bin/env python3
"""Quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls, at the full
width of the headline model (PNA multi-head, hidden 256, 3 conv layers, bf16
by ``Training.mixed_precision: "auto"``, batches of 64 OC20-shaped slabs of
80-90 atoms at degree ~12):

  ``run_training`` (loaders, bucket layout, put_batch, multi-step dispatch,
  checkpoint save) -> ``run_prediction`` on the saved run -> the in-process
  serving surface (``ModelRegistry.load_checkpoint``, ``plan_from_samples``,
  ``InferenceServer.predict``) checked against ``PredictMixin.predict``.

With no arguments it needs ONE chip and runs everything in this one process
(a chip belongs to one process at a time). ``--chips 4`` runs ONLY the
data-parallel path on a 4-device ``data`` mesh and the same global batch and
seed on one device for comparison.

Output: one JSON object per line. The LAST stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``;
any failure ends the run with ``"ok": false`` and a non-zero exit code — with
``JAX_PLATFORMS=cpu`` that is the platform assertion, before any phase.
Data and weights are made from ``SEED``; run artefacts go under
``chip_smoke_out/`` in the checkout (git-ignored) and nowhere else.
"""

import argparse
import contextlib
import importlib.metadata
import json
import os
import pickle
import shutil
import sys
import time
import traceback
import warnings

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chip_smoke_out")
SEED = 0

# the headline cell (bench.py MXU_HEADLINE): never cut in width
FULL = dict(
    hidden=256,
    conv_layers=3,
    batch=64,
    atoms=(80, 90),
    train_graphs=768,  # 12 batches an epoch
    eval_graphs=128,  # validate and test each
    epochs=3,
    steps_per_dispatch=4,
    serve_requests=12,
    serve_batch=8,
)
# the four-chip comparison: same widths, a few steps
FULL_4 = dict(FULL, train_graphs=384, eval_graphs=64, epochs=2)

LATTICE = 2.5  # Angstrom; 6 x 5 x 3 cubic slab sites
RADIUS = 4.0
MAX_NEIGHBOURS = 12
# serve vs PredictMixin.predict run the same f32 weights through two
# differently batched programs; on the TPU an f32 matmul rounds its inputs
# to bf16 (eps 2^-8), so agreement is judged at that grain
SERVE_TOL = 2e-2
# 4-device vs 1-device epoch losses: same global batch, seed and math; only
# the reduction order and bf16 rounding of partial sums differ
MESH_TOL = 2e-2


def say(**fields):
    print(json.dumps(fields, default=str), flush=True)


@contextlib.contextmanager
def phase(name):
    """Time one phase (wall and XLA compile seconds) and print the report
    dict it yields; any exception propagates — no phase is caught and
    survived."""
    from hydragnn_tpu.obs import runtime as obs_rt

    c0, n0, t0 = obs_rt.compile_seconds(), obs_rt.compile_events(), time.time()
    report = {}
    yield report
    say(
        phase=name,
        wall_s=round(time.time() - t0, 2),
        compile_s=round(obs_rt.compile_seconds() - c0, 2),
        compiles=obs_rt.compile_events() - n0,
        **report,
    )


# ---- data -------------------------------------------------------------------


def make_graphs(num, seed, atoms):
    """OC20-shaped periodic slabs from a seed: a jittered 6x5x3 cubic lattice
    with 80-90 of its 90 sites occupied by three species, 15 A of vacuum.
    Targets are smooth functions of the observed geometry: per-atom
    species-weighted coordination (node head) and its mean (graph head)."""
    import numpy as np

    from hydragnn_tpu.data.dataobj import GraphData

    rng = np.random.default_rng(seed)
    sites = np.stack(
        np.meshgrid(np.arange(6), np.arange(5), np.arange(3), indexing="ij"),
        -1,
    ).reshape(-1, 3).astype(np.float64)
    cell = np.diag([6 * LATTICE, 5 * LATTICE, 3 * LATTICE + 15.0])
    period = np.diag(cell)
    out = []
    for _ in range(num):
        n = int(rng.integers(atoms[0], atoms[1] + 1))
        keep = np.sort(rng.permutation(len(sites))[:n])
        pos = sites[keep] * LATTICE + rng.normal(0, 0.08 * LATTICE, (n, 3))
        species = rng.integers(0, 3, n)
        dvec = pos[:, None, :] - pos[None, :, :]
        dvec -= np.round(dvec / period) * period  # minimum image
        dist = np.linalg.norm(dvec, axis=-1)
        np.fill_diagonal(dist, np.inf)
        weight = 1.0 + 0.3 * species[None, :]
        coord = (np.exp(-((dist / RADIUS) ** 2) * 4.0) * weight).sum(1)
        coord = (coord - 4.0) / 2.0
        g = GraphData(
            x=np.stack([species / 2.0, coord], 1).astype(np.float32),
            pos=pos.astype(np.float32),
            y=np.asarray([coord.mean()], np.float32),
            supercell_size=cell,
        )
        out.append(g)
    return out


def write_dataset(sz, tag):
    """{split: path} of pickles in the serialized-dataset format
    ``run_training`` loads (minmax tables, then the sample list)."""
    import numpy as np

    paths = {}
    for i, (split, num) in enumerate(
        (
            ("train", sz["train_graphs"]),
            ("validate", sz["eval_graphs"]),
            ("test", sz["eval_graphs"]),
        )
    ):
        paths[split] = os.path.join(OUT_DIR, f"{tag}_{split}.pkl")
        graphs = make_graphs(num, SEED + i, sz["atoms"])
        with open(paths[split], "wb") as f:
            pickle.dump(np.zeros((2, 2)), f)  # node minmax (unused)
            pickle.dump(np.zeros((2, 1)), f)  # graph minmax (unused)
            pickle.dump(graphs, f)
    return paths


def make_config(sz, name, paths, mesh_shape=None):
    shared = max(32, sz["hidden"] // 4)
    training = {
        "num_epoch": sz["epochs"],
        "perc_train": 0.8,
        "batch_size": sz["batch"],
        "batch_buckets": 2,
        "contiguous_buckets": True,
        "steps_per_dispatch": sz["steps_per_dispatch"],
        "device_prefetch": 2,
        "mixed_precision": "auto",
        "loss_function_type": "mse",
        "Checkpoint": True,
        "checkpoint_warmup": 0,
        "Optimizer": {"type": "AdamW", "learning_rate": 1e-3},
    }
    if mesh_shape is not None:
        training["mesh_shape"] = list(mesh_shape)
    return {
        "Verbosity": {"level": 0},
        "Dataset": {
            "name": name,
            "format": "pickle",
            "compositional_stratified_splitting": False,
            "rotational_invariance": False,
            "path": dict(paths),
            "node_features": {
                "name": ["species", "coordination"],
                "dim": [1, 1],
                "column_index": [0, 1],
            },
            "graph_features": {
                "name": ["mean_coordination"],
                "dim": [1],
                "column_index": [0],
            },
        },
        "NeuralNetwork": {
            "Architecture": {
                "model_type": "PNA",
                "radius": RADIUS,
                "max_neighbours": MAX_NEIGHBOURS,
                "periodic_boundary_conditions": True,
                "hidden_dim": sz["hidden"],
                "num_conv_layers": sz["conv_layers"],
                "output_heads": {
                    "graph": {
                        "num_sharedlayers": 2,
                        "dim_sharedlayers": shared,
                        "num_headlayers": 2,
                        "dim_headlayers": [shared, shared],
                    },
                    "node": {
                        "num_headlayers": 2,
                        "dim_headlayers": [shared, shared],
                        "type": "mlp",
                    },
                },
                "task_weights": [1.0, 1.0],
            },
            "Variables_of_interest": {
                "input_node_features": [0],
                "output_names": ["mean_coordination", "coordination"],
                "output_index": [0, 1],
                "type": ["graph", "node"],
                "denormalize_output": False,
            },
            "Training": training,
        },
        "Visualization": {"create_plots": False},
    }


def read_events(log_name):
    """The run's event stream through the repo's own schema validator,
    which also refuses a run that recorded no compiled program, no
    aggregation choice or no checkpoint."""
    from hydragnn_tpu.obs.events import validate_events

    events = validate_events(
        os.path.join(OUT_DIR, "logs", log_name, "events.jsonl"),
        require=["run_manifest", "epoch", "compile", "agg_choice",
                 "checkpoint_saved", "run_end"],
    )
    if events[-1].get("status") != "complete":
        raise AssertionError(f"run did not end complete: {events[-1]}")
    return events


# ---- phases -----------------------------------------------------------------


def phase_build():
    """The native runtime libraries are built from the tree (their file
    names carry the source hash); a missing g++ fails here, by name."""
    from hydragnn_tpu.data import distdataset
    from hydragnn_tpu.native import graphpack

    with phase("build") as r:
        for mod in (graphpack, distdataset):
            mod._load()
        r["gxx"] = shutil.which("g++")
        r["native"] = sorted(
            os.listdir(os.path.join(ROOT, "hydragnn_tpu", "native", "_build"))
        )


def phase_train(sz, config):
    """``run_training`` end to end; returns (log_name, per-epoch losses)."""
    import copy

    import jax
    import numpy as np

    import hydragnn_tpu
    from hydragnn_tpu.obs import ledger
    from hydragnn_tpu.utils.config import get_log_name_config

    with phase("train") as r:
        hydragnn_tpu.run_training(copy.deepcopy(config))
        log_name = get_log_name_config(config)
        events = read_events(log_name)
        epochs = [e for e in events if e["event"] == "epoch"]
        losses = [float(e["train_loss"]) for e in epochs]
        r.update(
            log_name=log_name,
            optimizer_steps=sum(
                int(e.get("steps", 0)) for e in events
                if e["event"] == "goodput"
            ),
            precision=ledger.current_precision(),
            train_loss=[round(v, 6) for v in losses],
            val_loss=[round(float(e["val_loss"]), 6) for e in epochs],
            # which aggregation path each bucket took, and why
            agg_choice=[
                {k: e[k] for k in ("bucket", "choice", "source")}
                for e in events
                if e["event"] == "agg_choice"
            ],
            # every compiled step program: Pallas kernel call sites in it
            # (0 = XLA ran the whole step) and its compiled peak bytes
            programs=[
                {
                    "name": e["name"],
                    "bucket": e["bucket"],
                    "kernels": e["kernels"],
                    "peak_bytes": int(e["memory"].get("peak_bytes", 0)),
                    **(
                        {"collectives": e["collectives"]}
                        if "collectives" in e
                        else {}
                    ),
                }
                for e in events
                if e["event"] == "compile"
            ],
            mesh=next(
                (
                    {k: e[k] for k in ("axes", "shape", "devices")}
                    for e in events
                    if e["event"] == "mesh_shape"
                ),
                None,
            ),
            checkpoints=sum(
                e["event"] == "checkpoint_saved" for e in events
            ),
        )
        stats = jax.devices()[0].memory_stats() or {}
        r["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
        if len(losses) != sz["epochs"] or not np.isfinite(losses).all():
            raise AssertionError(f"train losses not finite: {losses}")
        if not losses[-1] < losses[0]:
            raise AssertionError(f"loss did not fall: {losses}")
        if not os.path.exists(
            os.path.join(OUT_DIR, "logs", log_name, log_name + ".pk")
        ):
            raise AssertionError("run_training left no checkpoint")
        if not any(p["name"].startswith("train") for p in r["programs"]):
            raise AssertionError("no compiled train program was recorded")
    return log_name, events


def phase_predict(sz, config, events):
    """``run_prediction`` reloads the saved run; its test error must be the
    error the trained weights had on the same test set in the last epoch."""
    import copy

    import numpy as np

    import hydragnn_tpu

    with phase("predict") as r:
        error, tasks, true_v, pred_v = hydragnn_tpu.run_prediction(
            copy.deepcopy(config)
        )
        last_test = float(
            [e for e in events if e["event"] == "epoch"][-1]["test_loss"]
        )
        r.update(
            error=round(float(error), 6),
            tasks=[round(float(t), 6) for t in tasks],
            last_epoch_test_loss=round(last_test, 6),
            rows=[int(np.asarray(p).shape[0]) for p in pred_v],
        )
        if not all(np.isfinite(np.asarray(p)).all() for p in pred_v):
            raise AssertionError("run_prediction produced non-finite values")
        if r["rows"][0] != sz["eval_graphs"]:
            raise AssertionError(
                f"graph head rows {r['rows'][0]} != {sz['eval_graphs']}"
            )
        if abs(float(error) - last_test) > 0.05 * last_test + 1e-6:
            raise AssertionError(
                f"run_prediction error {error} != last test loss {last_test}"
            )


def phase_serve(sz, config, log_name):
    """The in-process serving surface answers a handful of requests whose
    per-head outputs match ``PredictMixin.predict`` for the same graphs."""
    import numpy as np

    from hydragnn_tpu.data.loaders import GraphLoader, compute_layout
    from hydragnn_tpu.data.serialized import SerializedGraphLoader
    from hydragnn_tpu.serve import (
        InferenceServer,
        ModelRegistry,
        plan_from_samples,
    )
    from hydragnn_tpu.train.trainer import Trainer

    with phase("serve") as r:
        registry = ModelRegistry()
        entry = registry.load_checkpoint(log_name)
        with open(os.path.join("logs", log_name, "config.json")) as f:
            saved = json.load(f)
        dense = bool(
            saved["NeuralNetwork"]["Architecture"].get("dense_aggregation")
        )
        # the same graphs a client would send: the test split, through the
        # same edge construction the training data took
        samples = SerializedGraphLoader(saved).load_serialized_data(
            config["Dataset"]["path"]["test"]
        )[: sz["serve_requests"]]
        plan = plan_from_samples(
            samples,
            max_batch_graphs=sz["serve_batch"],
            num_buckets=2,
            need_neighbors=dense,
        )

        # offline reference: PredictMixin.predict, sample order
        trainer = Trainer(entry.model, saved["NeuralNetwork"]["Training"])
        layout = compute_layout(
            [samples], batch_size=sz["serve_batch"], need_neighbors=dense
        )
        loader = GraphLoader(
            samples, sz["serve_batch"], layout, shuffle=False,
            num_shards=1, shard_id=0,
        )
        state = trainer.init_state(next(iter(loader)))
        state = state.replace(
            params=entry.params, batch_stats=entry.batch_stats
        )
        _, _, _, offline = trainer.predict(state, loader)

        with InferenceServer(registry, plan) as server:
            if not server.is_warm():
                raise AssertionError("server did not warm its buckets")
            results = [server.predict(g, timeout=120) for g in samples]
            snap = server.metrics.snapshot()
        max_diff = []
        for ihead in range(len(offline)):
            served = np.concatenate(
                [np.asarray(res[ihead]).reshape(-1, 1) for res in results]
            )
            if served.shape != offline[ihead].shape:
                raise AssertionError(
                    f"head {ihead}: served {served.shape} vs "
                    f"offline {offline[ihead].shape}"
                )
            if not np.isfinite(served).all():
                raise AssertionError(f"head {ihead}: non-finite output")
            max_diff.append(float(np.abs(served - offline[ihead]).max()))
        r.update(
            requests=len(samples),
            buckets=plan.num_buckets,
            compiles_total=int(snap["compiles_total"]),
            errors_total=int(snap["errors_total"]),
            dense_aggregation=dense,
            max_abs_diff_vs_predict=[round(d, 6) for d in max_diff],
            tolerance=SERVE_TOL,
        )
        if snap["compiles_total"] != plan.num_buckets:
            raise AssertionError(
                f"compiles_total {snap['compiles_total']} != "
                f"{plan.num_buckets} buckets"
            )
        if snap["errors_total"]:
            raise AssertionError(f"{snap['errors_total']} serve errors")
        if max(max_diff) > SERVE_TOL:
            raise AssertionError(
                f"serve differs from PredictMixin.predict by {max_diff}"
            )


def phase_fence():
    """Does ``jax.block_until_ready`` block here? One fixed program of
    ~0.1 s device time, timed three ways: dispatch only, block_until_ready,
    and a host fetch of one result byte (which cannot return early)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def chain(x):
        return jax.lax.fori_loop(
            0, 96, lambda _, a: (a @ a) * jnp.bfloat16(2.0**-12), x
        )  # ones stay ones: 96 dependent 4096^3 matmuls, ~13 TFLOP

    with phase("fence") as r:
        x = jnp.ones((4096, 4096), jnp.bfloat16)
        np.asarray(chain(x)[0, 0])  # compile + warm
        t0 = time.perf_counter()
        y = chain(x)
        t1 = time.perf_counter()
        jax.block_until_ready(y)
        t2 = time.perf_counter()
        y = chain(x)
        np.asarray(y[0, 0])
        t3 = time.perf_counter()
        r.update(
            dispatch_ms=round((t1 - t0) * 1e3, 3),
            block_until_ready_ms=round((t2 - t0) * 1e3, 3),
            host_fetch_ms=round((t3 - t2) * 1e3, 3),
        )
        # a block_until_ready that returned with the work still running
        # would read like the dispatch, far below the fetch
        if (t2 - t0) < 0.5 * (t3 - t2):
            raise AssertionError(f"block_until_ready did not block: {r}")


def run_one_chip(sz):
    phase_build()
    with phase("data") as r:
        paths = write_dataset(sz, "smoke")
        r["graphs"] = {
            k: sz["train_graphs" if k == "train" else "eval_graphs"]
            for k in paths
        }
    config = make_config(sz, "smoke", paths)
    log_name, events = phase_train(sz, config)
    phase_predict(sz, config, events)
    phase_serve(sz, config, log_name)
    phase_fence()


def run_four_chips(sz):
    """ONLY the data-parallel path and what it is compared with: the same
    training on a 4-device ``data`` mesh and on one device, same global
    batch and seed, in this one process."""
    import copy

    import jax
    import numpy as np

    from hydragnn_tpu.data.loaders import dataset_loading_and_splitting
    from hydragnn_tpu.parallel.collectives import parse_collectives
    from hydragnn_tpu.parallel.mesh import DATA_AXIS, resolve_mesh
    from hydragnn_tpu.train.driver import _build_model_and_trainer
    from hydragnn_tpu.utils.config import update_config

    # resolve_mesh quietly shrinks a requested width to the devices present
    if len(jax.devices()) != 4:
        raise AssertionError(f"need 4 devices, have {len(jax.devices())}")
    with phase("data"):
        paths = write_dataset(sz, "smoke4")
    cfg4 = make_config(sz, "smoke4", paths, mesh_shape=(4, 1))
    cfg1 = make_config(sz, "smoke1", paths, mesh_shape=(1, 1))
    _, ev4 = phase_train(sz, cfg4)
    _, ev1 = phase_train(sz, cfg1)

    with phase("compare") as r:
        mesh_ev = next(e for e in ev4 if e["event"] == "mesh_shape")
        if mesh_ev["shape"] != [4, 1] or mesh_ev["devices"] != 4:
            raise AssertionError(f"4-chip run got mesh {mesh_ev}")
        loss4 = [float(e["train_loss"]) for e in ev4 if e["event"] == "epoch"]
        loss1 = [float(e["train_loss"]) for e in ev1 if e["event"] == "epoch"]
        r.update(loss_4dev=loss4, loss_1dev=loss1, tolerance=MESH_TOL)
        np.testing.assert_allclose(loss4, loss1, rtol=MESH_TOL, atol=0)

        # the batch as the driver places it: every device holds a shard,
        # the global batch pads to the mesh multiple, and the compiled
        # step all-reduces the gradients over the data axis
        mesh = resolve_mesh(cfg4["NeuralNetwork"]["Training"])
        if mesh is None or mesh.devices.size != 4:
            raise AssertionError(f"resolve_mesh gave {mesh}")
        cfg = copy.deepcopy(cfg4)
        loaders = dataset_loading_and_splitting(cfg)
        cfg = update_config(cfg, *loaders)
        _, trainer, state = _build_model_and_trainer(cfg, loaders[0], 0)
        batch = next(iter(loaders[0]))
        dev = trainer.put_batch(batch)
        shards = dev.x.addressable_shards
        r["x_shape"] = list(dev.x.shape)
        r["shard_devices"] = sorted(s.device.id for s in shards)
        r["shard_rows"] = sorted({int(s.data.shape[0]) for s in shards})
        if len({s.device for s in shards}) != 4:
            raise AssertionError(f"batch lives on {r['shard_devices']}")
        if r["shard_rows"] != [dev.x.shape[0] // 4]:
            raise AssertionError(f"uneven shards {r['shard_rows']}")
        for leaf in jax.tree_util.tree_leaves(batch):
            if leaf.shape[0] % 4:
                raise AssertionError(
                    f"batch axis {leaf.shape} not a mesh multiple"
                )
        text = (
            trainer._train_step.lower(state, dev, jax.random.PRNGKey(0))
            .compile()
            .as_text()
        )
        by_op = {}
        for c in parse_collectives(
            text, tuple(mesh.axis_names), tuple(mesh.devices.shape)
        ):
            if c["axis"] == DATA_AXIS:
                n, nbytes = by_op.get(c["op"], (0, 0))
                by_op[c["op"]] = (n + 1, nbytes + int(c["bytes"]))
        # {op: [count, result bytes per device per step]} over the data axis
        r["data_axis_collectives"] = by_op
        if "all-reduce" not in by_op:
            raise AssertionError("no gradient all-reduce in the train step")


# ---- entry ------------------------------------------------------------------


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = parser.parse_args(argv)

    device = None
    try:
        import jax

        devices = jax.devices()
        device = {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        }
        # before anything else: this script measures nothing on a CPU
        if device["platform"] != "tpu":
            raise AssertionError(f"no TPU: JAX reports {device}")
        if device["count"] != args.chips:
            raise AssertionError(
                f"--chips {args.chips} but JAX reports {device['count']}"
            )
        # code for the one installation there is: a deprecated JAX call
        # fails the run instead of scrolling by
        warnings.filterwarnings("error", category=DeprecationWarning)

        from hydragnn_tpu.obs import runtime as obs_rt
        from hydragnn_tpu.utils import compile_cache

        obs_rt.install_compile_listener()
        say(
            versions={
                p: importlib.metadata.version(p)
                for p in ("jax", "jaxlib", "libtpu", "flax", "optax")
            },
            python=sys.version.split()[0],
            devices=[str(d) for d in devices],
            compile_cache_dir=os.getenv("JAX_COMPILATION_CACHE_DIR")
            or compile_cache.DEFAULT_CACHE_DIR,
        )
        shutil.rmtree(OUT_DIR, ignore_errors=True)
        os.makedirs(OUT_DIR)
        os.chdir(OUT_DIR)  # run artefacts (./logs, datasets) land here only
        t0 = time.time()
        if args.chips == 4:
            run_four_chips(FULL_4)
        else:
            run_one_chip(FULL)
        say(total_wall_s=round(time.time() - t0, 2))
    except BaseException as e:
        traceback.print_exc()
        sys.stderr.flush()
        say(ok=False, device=device, error=f"{type(e).__name__}: {e}"[:2000])
        return 1
    say(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
