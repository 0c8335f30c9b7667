"""Prediction / test-pass paths (the reference's ``test()`` with sample
collection, ``train_validate_test.py:588-698``).

Split out of ``trainer.py`` (round-3 verdict item 10) as a mixin: the
``Trainer`` composes it, so ``trainer.predict(...)`` is unchanged.
"""

import os

import jax
import numpy as np

from hydragnn_tpu.obs.introspect import record_function
from hydragnn_tpu.train.common import _env_flag, _is_oom, _nbatch


class PredictMixin:
    # allow roughly half a v5e HBM for (staged test set + stacked outputs);
    # beyond that the streaming path is the safe default. Best-effort only:
    # it cannot see HBM already held by staged training data / params — the
    # caller additionally catches the device's own RESOURCE_EXHAUSTED.
    # Default only: HYDRAGNN_PREDICT_STAGE_BUDGET / the training config's
    # ``predict_stage_budget_bytes`` override it (_predict_stage_budget).
    _PREDICT_STAGE_BUDGET_BYTES = 8 * 1024**3

    def _predict_stage_budget(self) -> int:
        """Staging budget in bytes: ``HYDRAGNN_PREDICT_STAGE_BUDGET`` env
        (accepts scientific notation, e.g. ``4e9``) > training config
        ``predict_stage_budget_bytes`` > the 8 GiB class default. Chips
        are not all v5e-sized — a v4 host wants a bigger stage, a CPU CI
        host a far smaller one."""
        env = os.getenv("HYDRAGNN_PREDICT_STAGE_BUDGET")
        if env is not None:
            try:
                return int(float(env))
            except ValueError:
                raise ValueError(
                    "HYDRAGNN_PREDICT_STAGE_BUDGET must be a byte count, "
                    f"got {env!r}"
                ) from None
        cfg = self.training_config.get("predict_stage_budget_bytes")
        if cfg is not None:
            return int(cfg)
        return self._PREDICT_STAGE_BUDGET_BYTES

    def predict(self, state, loader):
        """Full test pass with sample collection — the reference's ``test()``
        with return_samples (``train_validate_test.py:588-698``). Returns
        (avg loss, per-task avg, true_values, predicted_values) with per-head
        flattened [num_values, 1] arrays."""
        num_heads = self.model.num_heads
        acc = None
        true_values = [[] for _ in range(num_heads)]
        predicted_values = [[] for _ in range(num_heads)]
        nbatch = _nbatch(loader)

        # device-resident fast path (single-process): run the whole test
        # set as ONE scan and do ONE readback in place of a blocking
        # output fetch per batch. Own knob
        # (default: follows the training-set flag) because the TEST set +
        # stacked outputs have their own HBM footprint; non-uniform batch
        # shapes or an over-budget stage fall back to streaming.
        device_resident = _env_flag(
            "HYDRAGNN_PREDICT_DEVICE_RESIDENT",
            self.training_config,
            "predict_device_resident",
            default=_env_flag(
                "HYDRAGNN_DEVICE_RESIDENT",
                self.training_config,
                "device_resident_dataset",
            ),
        )
        if device_resident and (self.mesh is None or jax.process_count() == 1):
            # resolve the budget OUTSIDE the fallback try: a malformed
            # HYDRAGNN_PREDICT_STAGE_BUDGET must fail loudly here, not be
            # swallowed as a "ragged shapes" fallback below
            budget = self._predict_stage_budget()
            host_batches = []
            for ibatch, batch in enumerate(loader):
                if ibatch >= nbatch:
                    break
                host_batches.append(batch)
            try:
                # only the two documented failure modes trigger the
                # fallback: ragged shapes (stack raises ValueError) and the
                # host-side budget estimate (MemoryError)
                stacked = self._stack_for_predict(host_batches, budget)
            except (ValueError, MemoryError):
                loader = host_batches
            else:
                try:
                    return self._predict_device_resident(
                        state, host_batches, stacked
                    )
                except Exception as e:
                    # memory exhaustion (host or device) falls back to
                    # streaming; anything else is a genuine bug
                    if _is_oom(e):
                        loader = host_batches
                    else:
                        raise
                finally:
                    # don't hold the second full host copy of the test set
                    # through a (memory-pressured) streaming fallback
                    del stacked

        for ibatch, batch in enumerate(loader):
            if ibatch >= nbatch:
                break
            dev_batch = self.put_batch(batch)
            # annotated so an on-demand device trace (/profile?steps=N)
            # shows predict dispatches as a named region, not anonymous
            # XLA launches
            with record_function("hydragnn.predict_batch"):
                metrics = self._eval_step(
                    state.params, state.batch_stats, dev_batch
                )
            # loss/tasks/num_graphs accumulate ON DEVICE as one packed
            # vector per batch (Trainer._acc_add) — the per-batch
            # float()/np.asarray() fetches this replaces each cost a full
            # host round trip and serialized the dispatch pipeline
            # (jaxlint: host-sync-in-hot-loop)
            acc = self._acc_add(acc, metrics, multi=False)
            outputs = metrics["outputs"]
            if self.mesh is not None and jax.process_count() > 1:
                # global data-sharded arrays span non-addressable devices;
                # bring back THIS process's shard — rows then line up with
                # the local host batch masks (per-rank collection, like the
                # reference's per-rank test() loop)
                from jax.experimental import multihost_utils
                from jax.sharding import PartitionSpec as P

                from hydragnn_tpu.parallel.mesh import DATA_AXIS

                outputs = multihost_utils.global_array_to_host_local_array(
                    outputs, self.mesh, jax.tree_util.tree_map(
                        lambda _: P(DATA_AXIS), outputs
                    )
                )
            outputs = jax.device_get(outputs)
            self._collect_head_values(
                batch, outputs, true_values, predicted_values
            )
        loss, tasks = self._acc_read(acc)  # the pass's ONE metric readback
        return self._predict_finish(loss, tasks, true_values, predicted_values)

    def _collect_head_values(
        self, batch, outputs, true_values, predicted_values
    ):
        """Append one batch's masked per-head (true, pred) rows — shared by
        the streaming and device-resident predict paths."""
        graph_mask = np.asarray(batch.graph_mask)
        node_mask = np.asarray(batch.node_mask)
        for ihead in range(self.model.num_heads):
            mask = (
                graph_mask
                if self.model.output_type[ihead] == "graph"
                else node_mask
            )
            true = np.asarray(batch.targets[ihead])[mask]
            # NLL mode appends a log-variance channel — collected values
            # are the mean prediction only
            pred = np.asarray(outputs[ihead])[mask][..., : true.shape[-1]]
            pred = pred.reshape(-1, 1)
            true = true.reshape(-1, 1)
            predicted_values[ihead].append(pred)
            true_values[ihead].append(true)

    def _stack_for_predict(self, host_batches, budget=None):
        """Stack + host-side budget estimate for the staged predict path.
        Raises ValueError (ragged shapes) or MemoryError (over budget).
        ``budget`` should be resolved by the caller via
        :meth:`_predict_stage_budget` BEFORE entering any fallback
        handler — resolving it here would let a malformed env override
        masquerade as a ragged-shape ValueError."""
        from hydragnn_tpu.graph.batch import stack_batches

        stacked = stack_batches(host_batches)  # ValueError if ragged
        stage_bytes = sum(
            a.nbytes
            for a in jax.tree_util.tree_leaves(stacked)
            if hasattr(a, "nbytes")
        )
        nb = len(host_batches)
        out_rows = {
            "graph": host_batches[0].graph_mask.shape[0],
            "node": host_batches[0].node_mask.shape[0],
        }
        out_bytes = sum(
            nb * out_rows[t] * d * 4
            for t, d in zip(self.model.output_type, self.model.output_dim)
        )
        if budget is None:
            budget = self._predict_stage_budget()
        if stage_bytes + out_bytes > budget:
            raise MemoryError(
                f"staged predict would need {stage_bytes + out_bytes} bytes "
                f"(budget {budget})"
            )
        return stacked

    def _predict_device_resident(self, state, host_batches, stacked):
        """One-scan, one-readback predict over a staged test set."""
        num_heads = self.model.num_heads
        staged = self.put_batch_stacked(stacked)
        with record_function("hydragnn.predict_scan"):
            loss_b, tasks_b, g_b, outputs_b = jax.device_get(
                self._predict_scan(state.params, state.batch_stats, staged)
            )
        g_arr = np.asarray(g_b, np.float64)
        n = max(float(g_arr.sum()), 1.0)
        loss = float(np.asarray(loss_b, np.float64) @ g_arr) / n
        tasks = (np.asarray(tasks_b, np.float64) * g_arr[:, None]).sum(0) / n
        true_values = [[] for _ in range(num_heads)]
        predicted_values = [[] for _ in range(num_heads)]
        for ib, batch in enumerate(host_batches):
            self._collect_head_values(
                batch,
                [outputs_b[ihead][ib] for ihead in range(num_heads)],
                true_values,
                predicted_values,
            )
        return self._predict_finish(loss, tasks, true_values, predicted_values)

    def _predict_finish(self, loss, tasks, true_values, predicted_values):
        """Shared tail of both predict paths: concat, optional test-data
        dump, already-averaged metrics."""
        true_values = [np.concatenate(v, axis=0) for v in true_values]
        predicted_values = [np.concatenate(v, axis=0) for v in predicted_values]
        dump = os.getenv("HYDRAGNN_DUMP_TESTDATA")
        if dump:
            # per-rank test-prediction dump (train_validate_test.py:602);
            # an explicit path gets the rank embedded so multi-host ranks
            # cannot clobber each other
            rank = jax.process_index()
            if dump == "1":
                path = f"testdata_rank{rank}.npz"
            elif jax.process_count() > 1:
                root, ext = os.path.splitext(dump)
                path = f"{root}_rank{rank}{ext or '.npz'}"
            else:
                path = dump
            np.savez(
                path,
                **{f"true_{i}": v for i, v in enumerate(true_values)},
                **{f"pred_{i}": v for i, v in enumerate(predicted_values)},
            )
        return (loss, np.atleast_1d(tasks), true_values, predicted_values)
