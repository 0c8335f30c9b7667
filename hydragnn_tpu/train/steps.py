"""Jitted training-program builder.

Split out of ``trainer.py`` (round-3 verdict item 10): everything that
gets traced/compiled lives here — the fused train step (forward + masked
multi-task loss + backward + optimizer + BN stats in ONE XLA program,
replacing the reference's per-op hot loop
``train_validate_test.py:437-540``), the multi-step scan, the staged
epoch scan, the whole-training ``fit_scan`` with on-device plateau-LR /
early-stop / best-state tracking, and the eval/predict scans.

:func:`build_steps` returns a :class:`CompiledSteps` namespace; the
``Trainer`` stores it and exposes the same ``_train_step`` etc.
attributes it always had.
"""

import os

import jax
import jax.numpy as jnp
import optax

from hydragnn_tpu.obs.introspect import instrument
from hydragnn_tpu.train.common import SchedState
from hydragnn_tpu.train.transfer import _decompact_traced


class CompiledSteps:
    """Plain namespace of the jitted programs for one (model, tx) pair."""

    __slots__ = (
        "train_step",
        "train_multi",
        "epoch_scan",
        "eval_epoch",
        "predict_scan",
        "fit_scan",
        "eval_step",
        "eval_multi",
    )


def _sharding_plan(mesh, state_shardings):
    """Explicit in/out shardings for every compiled program on a mesh.

    The programs used to ASSUME replicated params (no shardings: XLA
    inherited whatever placement the committed inputs carried). On the
    2-D mesh that assumption is wrong — params split over ``model`` per
    the rule engine — so every program declares its contract: state at
    the rule-engine placement, batches sharded over ``data`` (leading
    axis; the scan axis of stacked data stays unsharded), scalars/rngs/
    metrics replicated. Donated buffers keep identical in/out shardings,
    so donation survives the declarations (the jaxlint missing-donate
    gate stays clean by construction)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from hydragnn_tpu.parallel.mesh import DATA_AXIS

    rep = NamedSharding(mesh, P())
    batch = NamedSharding(mesh, P(DATA_AXIS))
    stacked = NamedSharding(mesh, P(None, DATA_AXIS))
    st = state_shardings
    return {
        "train_step": dict(
            in_shardings=(st, batch, rep), out_shardings=(st, rep)
        ),
        "train_multi": dict(
            in_shardings=(st, stacked, rep), out_shardings=(st, rep)
        ),
        "epoch_scan": dict(
            in_shardings=(st, stacked, rep, rep), out_shardings=(st, rep)
        ),
        "eval_epoch": dict(
            in_shardings=(st.params, st.batch_stats, stacked),
            out_shardings=rep,
        ),
        "predict_scan": dict(
            in_shardings=(st.params, st.batch_stats, stacked),
            out_shardings=rep,
        ),
        "fit_scan": dict(
            in_shardings=(
                st, st, rep, stacked, stacked, stacked, rep, rep, rep
            ),
            out_shardings=(st, st, rep, rep),
        ),
        "eval_step": dict(
            in_shardings=(st.params, st.batch_stats, batch),
            out_shardings=rep,
        ),
        "eval_multi": dict(
            in_shardings=(st.params, st.batch_stats, stacked),
            out_shardings=rep,
        ),
    }


def build_steps(
    model, tx, training_config: dict, mesh=None, state_shardings=None
) -> CompiledSteps:
    # mixed precision (no reference counterpart — HydraGNN trains pure
    # f32): master params stay f32 for the optimizer; forward/backward
    # runs in bfloat16. Positions stay f32 (geometry — distances/angles
    # — is precision-critical), BatchNorm statistics and loss reductions
    # are forced to f32 in models/common.py, and segment scatters upcast
    # to f32 (graph/segment.py). The QM9-scale step is scatter/
    # op-latency-bound, not matmul-bound, so bf16 buys little there;
    # expect wins on matmul-bound configurations (wide hidden dims,
    # dense-mode batches). Enablement is the param-precision policy in
    # models/create.py (HYDRAGNN_MIXED_PRECISION env > explicit bool >
    # "auto" per-model width table); accuracy-validated
    # (tests/test_mixed_precision.py).
    from hydragnn_tpu.models.create import resolve_precision

    precision = resolve_precision(model, training_config)
    mixed = precision["mixed"]
    # the goodput/MFU ledger judges achieved FLOPs against the precision-
    # matched peak (bf16 vs f32 column of obs/ledger.PEAK_FLOPS)
    from hydragnn_tpu.obs import ledger as _ledger

    _ledger.note_precision(mixed, source=precision["source"])
    # divergence guard (train/guard.py): when on, every train step also
    # reports a device-computed "finite" scalar — loss AND all gradient
    # leaves finite — so the host can skip a poisoned update without
    # reading back whole tensors. Compiled in only when enabled: the
    # reduction over every gradient leaf is not free.
    from hydragnn_tpu.train.guard import guard_enabled

    guarded = guard_enabled(training_config)

    # leaves a stack computes in f32 whatever the run's precision (by
    # their name: DimeNet's Bessel frequencies)
    keep_f32 = tuple(getattr(model, "f32_params", ()))

    def _cast_bf16(tree):
        return jax.tree_util.tree_map_with_path(
            lambda path, a: a.astype(jnp.bfloat16)
            if hasattr(a, "dtype") and a.dtype == jnp.float32
            and getattr(path[-1], "key", None) not in keep_f32
            else a,
            tree,
        )

    def train_step(state, batch, rng):
        batch = _decompact_traced(batch)
        if mixed:
            batch = batch.replace(
                x=batch.x.astype(jnp.bfloat16),
                edge_attr=None
                if batch.edge_attr is None
                else batch.edge_attr.astype(jnp.bfloat16),
            )

        def loss_fn(params):
            if mixed:
                params = _cast_bf16(params)
            variables = {"params": params}
            if state.batch_stats:
                variables["batch_stats"] = state.batch_stats
                outputs, mut = model.apply(
                    variables,
                    batch,
                    train=True,
                    mutable=["batch_stats"],
                    rngs={"dropout": rng},
                )
                new_bs = mut["batch_stats"]
            else:
                outputs = model.apply(
                    variables, batch, train=True, rngs={"dropout": rng}
                )
                new_bs = state.batch_stats
            tot, tasks = model.loss(outputs, batch)
            return tot, (tuple(tasks), new_bs)

        (loss, (tasks, new_bs)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(state.params)
        with jax.named_scope("optimizer"):
            updates, new_opt = tx.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
        new_state = state.replace(
            params=new_params,
            batch_stats=new_bs,
            opt_state=new_opt,
            step=state.step + 1,
        )
        metrics = {
            "loss": loss,
            "tasks": jnp.stack(tasks) if tasks else jnp.zeros((0,)),
            "num_graphs": batch.graph_mask.sum(),
        }
        if guarded:
            metrics["finite"] = jax.tree_util.tree_reduce(
                lambda ok, g: ok & jnp.isfinite(g).all(),
                grads,
                jnp.isfinite(loss),
            )
        return new_state, metrics

    def eval_step(params, batch_stats, batch):
        batch = _decompact_traced(batch)
        variables = {"params": params}
        if batch_stats:
            variables["batch_stats"] = batch_stats
        outputs = model.apply(variables, batch, train=False)
        tot, tasks = model.loss(outputs, batch)
        return {
            "loss": tot,
            "tasks": jnp.stack(tasks) if tasks else jnp.zeros((0,)),
            "num_graphs": batch.graph_mask.sum(),
            "outputs": outputs,
        }

    def _microbatch(data, idx):
        """Gather microbatch ``idx`` out of an HBM-staged stack."""
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, idx, keepdims=False),
            data,
        )

    def epoch_scan(state, data, perm, rngs):
        """A whole epoch in ONE XLA program over an HBM-staged dataset.

        ``data`` is a ``stack_batches`` result living in device memory
        (see ``Trainer.stage_batches``); ``perm`` reorders the microbatches
        each epoch. Each scan step gathers one microbatch out of HBM and
        runs the fused train step — zero host round-trips inside the
        epoch. This is the TPU answer to datasets that fit in HBM
        (QM9-scale and below): stage once, then epochs are pure compute."""

        def body(s, inp):
            idx, r = inp
            return train_step(s, _microbatch(data, idx), r)

        return jax.lax.scan(body, state, (perm, rngs))

    sch_cfg = training_config.get("scheduler", {})
    plateau_factor = float(sch_cfg.get("factor", 0.5))
    plateau_patience = int(sch_cfg.get("patience", 5))
    plateau_threshold = float(sch_cfg.get("threshold", 1e-4))
    plateau_min_lr = float(sch_cfg.get("min_lr", 1e-5))
    early_enabled = bool(training_config.get("EarlyStopping", False))
    early_patience = int(training_config.get("patience", 5))
    # best-state tracking starts after this many epochs (the reference
    # BestCheckpoint warmup, ``utils/model.py:207-248``; default 10 when
    # checkpointing is on, else track from the start)
    best_warmup = int(
        training_config.get(
            "checkpoint_warmup",
            10 if training_config.get("Checkpoint", False) else 0,
        )
    )

    def eval_multi(params, batch_stats, data, nb=None):
        """Scan ``eval_step`` over a stacked batch: metrics stacked per
        microbatch ([K]/[K, T] — `_acc_add(multi=True)` format). The eval
        counterpart of ``multi_train_step``: streaming validation/test
        was still paying one dispatch RPC per batch after training
        learned to stack (at-scale QM9, evals cost as much wall as the
        whole stacked train epoch). The ONE scan-eval implementation —
        ``eval_epoch`` is a reduction over it."""

        def body(_, idx):
            m = eval_step(params, batch_stats, _microbatch(data, idx))
            return _, (m["loss"], m["tasks"], m["num_graphs"])

        if nb is None:
            nb = jax.tree_util.tree_leaves(data)[0].shape[0]
        _, (loss, tasks, g) = jax.lax.scan(body, None, jnp.arange(nb))
        return {"loss": loss, "tasks": tasks, "num_graphs": g}

    def eval_epoch(params, batch_stats, data):
        """Mean loss/tasks over a staged (stacked) eval set, no outputs.
        Honors ``HYDRAGNN_MAX_NUM_BATCH`` like every other eval path."""
        nb = jax.tree_util.tree_leaves(data)[0].shape[0]
        cap = os.getenv("HYDRAGNN_MAX_NUM_BATCH")
        if cap is not None:
            nb = min(nb, int(cap))
        m = eval_multi(params, batch_stats, data, nb=nb)
        g = m["num_graphs"].astype(jnp.float32)
        denom = jnp.maximum(g.sum(), 1.0)
        return (
            (m["loss"] * g).sum() / denom,
            (m["tasks"] * g[:, None]).sum(0) / denom,
        )

    num_tasks = len(model.output_type)

    def fit_scan(
        state, best_state, sched, train_data, val_data, test_data,
        perms, rngs, active,
    ):
        """Whole-training dispatch: scan over epochs, each epoch a scan
        over HBM-staged microbatches; plateau LR, early stopping and
        best-state tracking run on device (``SchedState``). One D2H
        readback per CALL, not per epoch.

        ``val_data``/``test_data`` may be the train set (the reference's
        ``HYDRAGNN_VALTEST=0`` semantics are handled by the caller).
        Epochs after the early stop fire — and epochs whose ``active``
        flag is False (scan-length padding so every chunk reuses one
        compiled program) — are skipped via ``lax.cond`` (their metric
        slots return NaN)."""

        def epoch_body(carry, inp):
            state, best_state, sched = carry
            perm, erngs, act = inp

            def run(args):
                state, best_state, sched = args
                state, m = epoch_scan(state, train_data, perm, erngs)
                g = m["num_graphs"].astype(jnp.float32)
                denom = jnp.maximum(g.sum(), 1.0)
                train_loss = (m["loss"] * g).sum() / denom
                train_tasks = (m["tasks"] * g[:, None]).sum(0) / denom
                # None val/test = the reference's HYDRAGNN_VALTEST=0
                # semantics: reuse the train loss, skip the eval pass
                if val_data is None:
                    val_loss = train_loss
                else:
                    val_loss, _ = eval_epoch(
                        state.params, state.batch_stats, val_data
                    )
                if test_data is None:
                    test_loss = val_loss
                else:
                    test_loss, _ = eval_epoch(
                        state.params, state.batch_stats, test_data
                    )
                # ---- ReduceLROnPlateau (scheduler.py semantics)
                is_better = val_loss < sched.plateau_best * (
                    1.0 - plateau_threshold
                )
                pbest = jnp.where(is_better, val_loss, sched.plateau_best)
                pbad = jnp.where(is_better, 0, sched.plateau_bad + 1)
                hp = state.opt_state.hyperparams
                lr = hp["learning_rate"]
                drop = pbad > plateau_patience
                new_lr = jnp.where(
                    drop,
                    jnp.maximum(lr * plateau_factor, plateau_min_lr),
                    lr,
                )
                pbad = jnp.where(drop, 0, pbad)
                opt_state = state.opt_state._replace(
                    hyperparams={**hp, "learning_rate": new_lr}
                )
                state = state.replace(opt_state=opt_state)
                # ---- EarlyStopping (utils/model.py:189-204 semantics)
                e_better = val_loss < sched.early_best
                e_best = jnp.where(e_better, val_loss, sched.early_best)
                e_count = jnp.where(e_better, 0, sched.early_count + 1)
                stopped = (
                    (e_count >= early_patience)
                    if early_enabled
                    else jnp.zeros((), bool)
                )
                # ---- best-state snapshot (Checkpoint-on-best analog,
                # warmup-gated like utils/model.py:207-248)
                improved = (val_loss < sched.best_val) & (
                    sched.epoch >= best_warmup
                )
                new_best_val = jnp.where(improved, val_loss, sched.best_val)
                best_state = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(improved, new, old),
                    state,
                    best_state,
                )
                sched = SchedState(
                    plateau_best=pbest,
                    plateau_bad=pbad,
                    early_best=e_best,
                    early_count=e_count,
                    stopped=stopped,
                    epoch=sched.epoch + 1,
                    best_val=new_best_val,
                )
                # one packed row per epoch so the whole series is ONE
                # D2H array: [train, val, test, lr, stopped, tasks...]
                row = jnp.concatenate(
                    [
                        jnp.stack(
                            [train_loss, val_loss, test_loss,
                             new_lr.astype(jnp.float32),
                             stopped.astype(jnp.float32)]
                        ),
                        train_tasks.astype(jnp.float32),
                    ]
                )
                return (state, best_state, sched), row

            def skip(args):
                state, best_state, sched = args
                nan = jnp.asarray(jnp.nan, jnp.float32)
                lr = state.opt_state.hyperparams["learning_rate"]
                row = jnp.concatenate(
                    [
                        jnp.stack(
                            [nan, nan, nan, lr.astype(jnp.float32),
                             sched.stopped.astype(jnp.float32)]
                        ),
                        jnp.full((num_tasks,), jnp.nan, jnp.float32),
                    ]
                )
                return (state, best_state, sched), row

            return jax.lax.cond(
                jnp.logical_or(sched.stopped, jnp.logical_not(act)),
                skip,
                run,
                (state, best_state, sched),
            )

        (state, best_state, sched), series = jax.lax.scan(
            epoch_body, (state, best_state, sched), (perms, rngs, active)
        )
        return state, best_state, sched, series

    def multi_train_step(state, batches, rngs):
        """K optimizer steps in ONE XLA program (``lax.scan`` over a
        stacked batch). Amortizes dispatch latency: at QM9 scale a single
        step's device time is well under the host's per-dispatch cost, so
        the eager-style loop is launch-bound (measured ~2.3 ms/step wall
        vs ~0.6 ms device on v5e). Metrics come back stacked ``[K, ...]``
        so epoch accumulation stays exact."""

        def body(s, inp):
            b, r = inp
            return train_step(s, b, r)

        return jax.lax.scan(body, state, (batches, rngs))

    def predict_scan(params, batch_stats, data):
        """Full-set prediction in one program: stacked per-microbatch
        (loss, tasks, num_graphs, outputs) — callers do ONE readback."""

        def body(_, idx):
            m = eval_step(params, batch_stats, _microbatch(data, idx))
            return _, (
                m["loss"], m["tasks"], m["num_graphs"], m["outputs"]
            )

        nb = jax.tree_util.tree_leaves(data)[0].shape[0]
        return jax.lax.scan(body, None, jnp.arange(nb))[1]

    # every hot-path program is wrapped for XLA introspection
    # (obs/introspect.py): when telemetry is live, each novel compiled
    # shape signature has its cost_analysis()/memory_analysis() captured
    # once as a `compile` event + per-bucket gauges; otherwise the
    # wrappers are pure passthroughs (.lower() etc. still forward, so
    # benchmarks and the recompile sentinel see the jit they always saw)
    plan = (
        _sharding_plan(mesh, state_shardings)
        if mesh is not None and state_shardings is not None
        else {}
    )

    def _jit(name, fn, **kwargs):
        return instrument(name, jax.jit(fn, **plan.get(name, {}), **kwargs))

    steps = CompiledSteps()
    steps.train_step = _jit("train_step", train_step, donate_argnums=(0,))
    steps.train_multi = _jit(
        "train_multi", multi_train_step, donate_argnums=(0,)
    )
    # opt-in NaN sentinel (the numlint suite's runtime half): wrap the
    # per-step train programs so a diverged step fails IMMEDIATELY with
    # the first non-finite head/param subtree named, instead of epochs
    # later as a NaN loss curve. Opt-in because localization reads the
    # outputs back per step — a debug harness, not a production default
    from hydragnn_tpu.utils.envparse import env_int

    if env_int("HYDRAGNN_NAN_SENTINEL", 0):
        from hydragnn_tpu.analysis.guards import nan_sentinel

        steps.train_step = nan_sentinel(
            steps.train_step, scope="train_step"
        )
        steps.train_multi = nan_sentinel(
            steps.train_multi, scope="train_multi"
        )
    steps.epoch_scan = _jit("epoch_scan", epoch_scan, donate_argnums=(0,))
    steps.eval_epoch = _jit("eval_epoch", eval_epoch)
    steps.predict_scan = _jit("predict_scan", predict_scan)
    # donate state + sched; best_state is NOT donated (its initial value
    # may alias state's buffers)
    steps.fit_scan = _jit("fit_scan", fit_scan, donate_argnums=(0, 2))
    steps.eval_step = _jit("eval_step", eval_step)
    steps.eval_multi = _jit("eval_multi", eval_multi)
    return steps
