"""End-to-end orchestration behind ``run_training`` / ``run_prediction``.

Parity with ``hydragnn/run_training.py:49-182`` and
``hydragnn/run_prediction.py:48-107``: distributed setup -> data loading &
splitting -> config derivation -> model + optimizer -> epoch driver ->
checkpoint, and the prediction path that reloads the trained model and
returns (error, per-task error, true values, predictions) with optional
denormalization.
"""

import os

import numpy as np

from hydragnn_tpu.data.loaders import dataset_loading_and_splitting
from hydragnn_tpu.models.create import create_model_config, needs_edge_offsets
from hydragnn_tpu.parallel.distributed import setup_distributed
from hydragnn_tpu.parallel.mesh import announce_mesh, resolve_mesh
from hydragnn_tpu.train.checkpoint import (
    checkpoint_exists,
    load_state_dict,
    pop_train_meta,
    restore_into,
    rolling_checkpoints,
    save_model,
)
from hydragnn_tpu.obs import runtime as obs
from hydragnn_tpu.train.trainer import Trainer, train_validate_test
from hydragnn_tpu.utils import tracer as tr
from hydragnn_tpu.utils.config import (
    get_log_name_config,
    save_config,
    update_config,
)
from hydragnn_tpu.utils.compile_cache import enable_compile_cache
from hydragnn_tpu.utils.print_utils import setup_log
from hydragnn_tpu.utils.timers import Timer, print_timers


def _arch_for_factory(config) -> dict:
    arch = dict(config["NeuralNetwork"]["Architecture"])
    training = config["NeuralNetwork"]["Training"]
    arch["loss_function_type"] = training.get("loss_function_type", "mse")
    arch["conv_checkpointing"] = training.get("conv_checkpointing", False)
    return arch


def _get_summary_writer(log_name):
    """Rank-0 scalar writer. Historically this returned a bare TensorBoard
    ``SummaryWriter`` — or silently None when torch was missing, i.e. no
    scalars at all. Now it is the :class:`~hydragnn_tpu.obs.scalars.
    ScalarWriter` fan-out: an always-on JSONL/CSV backend plus TensorBoard
    when importable (its absence warned exactly once, on rank 0)."""
    from hydragnn_tpu.obs.scalars import ScalarWriter

    return ScalarWriter.for_run(log_name)


def _build_model_and_trainer(config, train_loader, verbosity):
    import jax

    arch = _arch_for_factory(config)
    with tr.span("init_state") as span:
        if arch.get("partition_axis"):
            model, trainer, state = _build_partitioned(
                config, arch, train_loader, verbosity
            )
        else:
            model = create_model_config(arch, verbosity)
            # 2-D ("data", "model") when Training.model_parallel /
            # HYDRAGNN_MESH asks for it, the historical 1-D data mesh
            # otherwise; a shape that no longer fits the visible devices
            # re-derives (parallel/mesh.py)
            mesh = resolve_mesh(config["NeuralNetwork"]["Training"])
            trainer = Trainer(
                model,
                config["NeuralNetwork"]["Training"],
                mesh=mesh,
                verbosity=verbosity,
                freeze_conv=arch.get("freeze_conv_layers", False),
            )
            example_batch = next(iter(train_loader))
            state = trainer.init_state(example_batch, seed=0)
            from hydragnn_tpu.models.create import print_model

            print_model(model, {"params": state.params}, verbosity)
        leaves = jax.tree_util.tree_leaves(state.params)
        span.set(
            params=len(leaves),
            param_bytes=sum(int(a.nbytes) for a in leaves),
        )
    return model, trainer, state


def _partition_geometry(config) -> tuple:
    """``(num_parts, axis)`` for graph-partition mode. With model
    parallelism configured (``Training.model_parallel`` / HYDRAGNN_MESH),
    node/edge ownership lives on the 2-D mesh's ``model`` axis and each
    graph splits into one model group's worth of shards; otherwise the
    legacy 1-D partition mesh spans every device under the config's
    ``partition_axis`` name."""
    import jax

    from hydragnn_tpu.parallel.mesh import (
        GRAPH_AXIS,
        MODEL_AXIS,
        best_mesh_shape,
        requested_mesh,
    )

    arch = config["NeuralNetwork"]["Architecture"]
    training = config["NeuralNetwork"].get("Training", {})
    _, m_req = requested_mesh(training)
    if m_req > 1:
        _, m = best_mesh_shape(len(jax.devices()), m_req)
        return m, MODEL_AXIS
    return len(jax.devices()), arch.get("partition_axis") or GRAPH_AXIS


def _build_partitioned(config, arch, train_loader, verbosity):
    """Giant-graph mode: every sample is ONE graph sharded node-wise over
    the partition axis — the ``model`` axis of the 2-D mesh when model
    parallelism is configured (``_partition_geometry``), else the legacy
    1-D mesh over every device named by ``Architecture.partition_axis``."""
    import jax

    from hydragnn_tpu.parallel.mesh import (
        MODEL_AXIS,
        best_mesh_shape,
        make_mesh,
        make_mesh2d,
        set_active_mesh,
    )
    from hydragnn_tpu.train.partitioned import PartitionedTrainer

    parts, axis = _partition_geometry(config)
    ref_arch = dict(arch)
    ref_arch.pop("partition_axis")
    arch = dict(arch)
    arch["partition_axis"] = axis
    model = create_model_config(arch, verbosity)
    ref_model = create_model_config(ref_arch, verbosity)
    if axis == MODEL_AXIS:
        d, m = best_mesh_shape(len(jax.devices()), parts)
        mesh = make_mesh2d(d, m)
        if d > 1:
            import warnings

            warnings.warn(
                f"graph-partition mode on a {d}x{m} mesh: each graph "
                f"splits across the {m}-wide model axis and the {d} data "
                "rows run REPLICATED work (one giant graph per step has "
                "no batch to shard). If the graph fits fewer shards than "
                "devices, prefer the 1-D partition mesh "
                "(model_parallel unset) to split it over every device",
                stacklevel=2,
            )
    else:
        mesh = make_mesh(None, axis)  # every device
    set_active_mesh(mesh)
    trainer = PartitionedTrainer(
        model,
        ref_model,
        config["NeuralNetwork"]["Training"],
        mesh=mesh,
        axis=axis,
        verbosity=verbosity,
        freeze_conv=arch.get("freeze_conv_layers", False),
    )
    state = trainer.init_state(train_loader.dataset[0], seed=0)
    return model, trainer, state


def make_partitioned_loaders(config, train_loader, val_loader, test_loader):
    """Swap the padded-batch GraphLoaders for PartitionedLoaders when the
    config asks for partition mode (post-``update_config``, so output
    types/dims are derived)."""
    arch = config["NeuralNetwork"]["Architecture"]
    if not arch.get("partition_axis"):
        return train_loader, val_loader, test_loader
    from hydragnn_tpu.train.partitioned import PartitionedLoader, scan_budgets

    head_types = tuple(arch["output_type"])
    head_dims = tuple(arch["output_dim"])
    need_triplets = arch["model_type"] == "DimeNet"
    need_neighbors = bool(arch.get("dense_aggregation"))
    need_offsets = needs_edge_offsets(arch)
    # shards-per-graph = the partition axis size (the 2-D mesh's model
    # axis under model parallelism, every device on the legacy 1-D mesh)
    n_dev, part_axis = _partition_geometry(config)
    # ONE budget union across splits -> one compiled executable for all
    budgets = scan_budgets(
        [train_loader.dataset, val_loader.dataset, test_loader.dataset],
        n_dev,
        head_types,
        head_dims,
        need_triplets,
        need_neighbors,
    )
    out = []
    for loader, shuffle in (
        (train_loader, True),
        (val_loader, False),
        (test_loader, False),
    ):
        out.append(
            PartitionedLoader(
                loader.dataset,
                n_dev,
                head_types,
                head_dims,
                need_triplets=need_triplets,
                need_neighbors=need_neighbors,
                shuffle=shuffle,
                axis=part_axis,
                budgets=budgets,
                need_offsets=need_offsets,
            )
        )
    return tuple(out)


def run_training_impl(config):
    import time as _time

    started_ts = _time.monotonic()
    timer = Timer("run_training")
    timer.start()
    enable_compile_cache()
    setup_distributed()
    # resolve the mesh BEFORE data loading: the loaders' leading-axis
    # padding must divide the mesh's DATA axis (parallel/mesh.py
    # data_axis_multiple), which on a 2-D mesh is smaller than the raw
    # device count. _build_model_and_trainer re-resolves the same shape.
    resolve_mesh(config["NeuralNetwork"]["Training"])
    # elastic/heartbeat runtime (train/elastic.py): started right after
    # the distributed bootstrap so the lease exists before the long
    # data-load/compile phases — None unless HYDRAGNN_ELASTIC_DIR or
    # HYDRAGNN_HEARTBEAT_FILE opts in
    from hydragnn_tpu.train import elastic

    elastic_rt = elastic.maybe_elastic()
    tr.initialize()
    verbosity = config.get("Verbosity", {}).get("level", 0)

    from hydragnn_tpu.data.stream import (
        build_stream_loaders,
        streaming_requested,
    )

    probe_loader = None
    if streaming_requested(config):
        # streaming data plane (docs/data.md): the train split never
        # materializes — config derivation (output dims, PNA degrees,
        # graph-size variability) runs over a cursor-neutral probe window
        # instead of the whole dataset
        train_loader, val_loader, test_loader, probe_loader = (
            build_stream_loaders(config)
        )
        config = update_config(config, probe_loader, val_loader, test_loader)
    else:
        train_loader, val_loader, test_loader = (
            dataset_loading_and_splitting(config)
        )
        config = update_config(config, train_loader, val_loader, test_loader)
        train_loader, val_loader, test_loader = make_partitioned_loaders(
            config, train_loader, val_loader, test_loader
        )
    log_name = get_log_name_config(config)
    setup_log(log_name)
    save_config(config, log_name)
    # unified telemetry (rank 0): events.jsonl + training metrics, plus the
    # live /metrics+/healthz endpoint when HYDRAGNN_OBS_PORT or
    # config["Telemetry"]["port"] opts in; HYDRAGNN_TELEMETRY=0 disables
    telemetry = obs.init_run_telemetry(config, log_name)
    if getattr(train_loader, "plan_event", None):
        # the bucket plan was built before telemetry existed; land its
        # record now that the event stream is live
        obs.emit("bucket_plan", **train_loader.plan_event)

    writer = None
    try:
        # the streaming train loader's __iter__ advances the mix cursor —
        # the probe loader (same layout, materialized window) feeds
        # init_state's example batch instead
        model, trainer, state = _build_model_and_trainer(
            config, probe_loader or train_loader, verbosity
        )

        training = config["NeuralNetwork"]["Training"]
        resume_meta = None
        if "continue" in training and training["continue"]:
            model_name = training.get("startfrom", log_name)
            # a lost/deleted primary with intact rolling copies is still
            # resumable — load_state_dict walks back to the newest good one
            if checkpoint_exists(model_name) or rolling_checkpoints(model_name):
                restored = load_state_dict(model_name)
                # v2 checkpoints carry the training-loop state — honored ONLY
                # when continuing THIS run (preemption resume). A 'startfrom'
                # of some other run is a warm start: its epoch counter must
                # not eat this run's training budget, so the meta is stripped
                # and training runs from epoch 0 on the restored weights.
                meta = pop_train_meta(restored)
                if model_name == log_name:
                    resume_meta = meta
                state = trainer.place_state(restore_into(state, restored))

        # mesh_shape + param_sharding run events; when the resumed
        # checkpoint recorded a DIFFERENT mesh (elastic shrink: the
        # surviving world re-derived the largest fitting (d, m)), this
        # also emits the world_resize with the new shape — the 2-D
        # analog of PR 8's 1-D re-shard
        announce_mesh(
            trainer.mesh, trainer=trainer, resume_meta=resume_meta,
            started_ts=started_ts,
        )

        writer = _get_summary_writer(log_name)
        vis_cfg = config.get("Visualization", {})
        state = train_validate_test(
            trainer,
            state,
            train_loader,
            val_loader,
            test_loader,
            config["NeuralNetwork"],
            log_name,
            verbosity,
            writer=writer,
            create_plots=vis_cfg.get("create_plots", False),
            plot_init_solution=vis_cfg.get("plot_init_solution", False),
            resume_meta=resume_meta,
        )
        # the epoch driver saves a resumable checkpoint at the final epoch
        # on its own; repeating the (collective-heavy) consolidation here
        # would only rewrite identical bytes
        if not getattr(trainer, "final_state_saved", False):
            save_model(
                state,
                log_name,
                train_meta=getattr(trainer, "final_train_meta", None),
            )
        timer.stop()
        print_timers(verbosity)
        tr.save(f"./logs/{log_name}/trace")
        # end-of-run region attribution: the scalar fan-out is ALWAYS-ON
        # (it must not depend on the event/metrics telemetry being
        # enabled), the event-stream copy rides along when telemetry is on
        regions = tr.totals()
        if regions:
            if writer is not None:
                num_epoch = config["NeuralNetwork"]["Training"]["num_epoch"]
                writer.add_regions(regions, step=num_epoch)
            if telemetry is not None:
                telemetry.emit(
                    "tracer_totals",
                    regions={k: round(v, 6) for k, v in regions.items()},
                )
    except BaseException:
        # the event stream must record that the run died — a log that only
        # ever says "complete" is useless for postmortems. The whole
        # post-init span is covered: a failure in the final save / tracer
        # dump must not leave /healthz reporting ok with no run_end.
        try:
            try:
                # pending async checkpoint writes are the run's last
                # durable progress — land them even on the failure path
                from hydragnn_tpu.train.checkpoint import drain_async

                drain_async(timeout=60.0)
            except Exception:
                pass  # the original failure is the one to surface
            if writer is not None:
                writer.close()
        finally:
            if elastic_rt is not None:
                elastic_rt.stop()
            obs.deactivate(status="failed")
        raise
    try:
        if writer is not None:
            writer.close()
    finally:
        # run_end must land even if a scalar backend fails to close
        if elastic_rt is not None:
            elastic_rt.stop()
        obs.deactivate(status="complete")
    return state


def run_prediction_impl(config):
    enable_compile_cache()
    setup_distributed()
    verbosity = config.get("Verbosity", {}).get("level", 0)

    train_loader, val_loader, test_loader = dataset_loading_and_splitting(config)
    config = update_config(config, train_loader, val_loader, test_loader)
    train_loader, val_loader, test_loader = make_partitioned_loaders(
        config, train_loader, val_loader, test_loader
    )
    log_name = get_log_name_config(config)

    model, trainer, state = _build_model_and_trainer(
        config, train_loader, verbosity
    )
    # an explicit error, not an assert: asserts vanish under ``python -O``
    # and a prediction run silently using random weights is the worst
    # possible failure mode
    if not checkpoint_exists(log_name):
        raise FileNotFoundError(f"No trained model found: {log_name}")
    # fallback=False: rolling last-good recovery is for RESUMING training;
    # a prediction must never silently report results from older weights
    state = trainer.place_state(
        restore_into(state, load_state_dict(log_name, fallback=False))
    )

    error, tasks_error, true_values, predicted_values = trainer.predict(
        state, test_loader
    )

    voi = config["NeuralNetwork"]["Variables_of_interest"]
    if voi.get("denormalize_output") and "y_minmax" in voi:
        from hydragnn_tpu.postprocess.postprocess import output_denormalize

        true_values, predicted_values = output_denormalize(
            voi["y_minmax"], true_values, predicted_values
        )

    return error, list(np.atleast_1d(tasks_error)), true_values, predicted_values
