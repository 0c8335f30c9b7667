"""Runtime correctness guards (hydragnn_tpu/analysis/guards.py).

Acceptance (ISSUE 4):

- recompile sentinel: ``steps.train_step`` compiles exactly once per
  batch shape — the compile counter stays FLAT across 2 further epochs
  of varying (bucketed) batches, and across a 100-request serve burst.
- transfer guard: one train epoch and one serve dispatch run under
  ``jax.transfer_guard_device_to_host("disallow")`` — the hot paths'
  only fetches are explicit ``jax.device_get`` calls, so they pass; a
  reintroduced per-batch ``float()`` hard-errors (asserted where the
  backend actually guards transfers; the CPU backend is host-resident
  and has no transfer to guard, so enforcement is probed and skipped
  there rather than faked).

Kept deliberately small: tiny model, few batches — the sentinel logic is
about *counts*, not scale.
"""

import numpy as np
import pytest

import jax

from hydragnn_tpu.analysis.guards import (
    CompileSentinel,
    RecompileError,
    no_host_syncs,
)
from hydragnn_tpu.graph import collate_graphs, pad_sizes_for
from hydragnn_tpu.models import create_model_config
from hydragnn_tpu.train.trainer import Trainer

from test_models_forward import FakeData, arch_config


def _batches(num_batches, num_graphs=4, max_n=6, seed=0):
    """Shape-uniform batches at one (max_n-derived) padded layout."""
    rng = np.random.default_rng(seed)
    n_pad, e_pad, g_pad = pad_sizes_for(
        max_n, 2 * max_n, num_graphs, graph_multiple=8
    )
    return [
        collate_graphs(
            [
                FakeData(rng, int(rng.integers(3, max_n + 1)))
                for _ in range(num_graphs)
            ],
            n_pad,
            e_pad,
            g_pad,
            head_types=("graph", "node"),
            head_dims=(1, 1),
        )
        for _ in range(num_batches)
    ]


class ListLoader:
    def __init__(self, batches):
        self.batches = batches

    def __len__(self):
        return len(self.batches)

    def __iter__(self):
        return iter(self.batches)

    def set_epoch(self, epoch):
        pass


_H = {}


def _trainer():
    """Module-shared trainer + two-bucket batch mix (compile once)."""
    if _H:
        return _H
    model = create_model_config(arch_config("SAGE"))
    trainer = Trainer(
        model, {"Optimizer": {"type": "AdamW", "learning_rate": 1e-3}}
    )
    # two distinct padded shapes = a bucketed epoch's compile surface
    batches = _batches(2, max_n=6, seed=0) + _batches(2, max_n=10, seed=1)
    state = trainer.init_state(batches[0])
    _H.update(trainer=trainer, state=state, batches=batches)
    return _H


# ---- recompile sentinel ---------------------------------------------------


def pytest_sentinel_detects_a_leaked_shape():
    """Negative control: the sentinel must actually trip on a novel
    shape (via the jit cache even when the persistent compile cache
    absorbs the backend compile)."""
    f = jax.jit(lambda x: x * 2.0)
    f(np.ones(4, np.float32))  # warm shape A
    with pytest.raises(RecompileError):
        with CompileSentinel(fns=[f]):
            f(np.ones(8, np.float32))  # novel shape B


def pytest_sentinel_flat_on_warm_shapes():
    f = jax.jit(lambda x: x * 2.0)
    f(np.ones(4, np.float32))
    with CompileSentinel(fns=[f]) as sentinel:
        for _ in range(10):
            f(np.ones(4, np.float32))
    sentinel.assert_flat("warm replay")


def pytest_train_step_compiles_once_across_two_epochs():
    """The acceptance run: warm one epoch over BOTH bucket shapes, then
    two further epochs must add zero compiles and zero jit-cache entries
    on the compiled step."""
    h = _trainer()
    trainer, state, batches = h["trainer"], h["state"], h["batches"]
    loader = ListLoader(batches)
    rng = jax.random.PRNGKey(0)
    # warmup epoch: compiles one executable per bucket shape (+ the
    # metric-accumulation programs)
    state, rng, loss, _ = trainer.train_epoch(state, loader, rng)
    assert np.isfinite(loss)
    with CompileSentinel(fns=[trainer._train_step]) as sentinel:
        for _ in range(2):
            state, rng, loss, _ = trainer.train_epoch(state, loader, rng)
            assert np.isfinite(loss)
    sentinel.assert_flat("2 bucketed epochs after warmup")
    _H["state"] = state  # step donates; keep the live one for other tests


# ---- transfer guard -------------------------------------------------------


def _guard_enforces() -> bool:
    """Does this backend actually error on implicit D2H transfers? The
    CPU platform stores arrays host-side — nothing to guard."""
    x = jax.jit(lambda v: v + 1)(np.ones((), np.float32))
    try:
        with no_host_syncs():
            float(x)
        return False
    except Exception:
        return True


def pytest_transfer_guard_train_epoch_runs_clean():
    """One full streaming epoch under the guard: every put is H2D (out
    of scope), the epoch's ONE readback is an explicit device_get — so
    a guarded run completes and matches an unguarded one."""
    h = _trainer()
    trainer, state, batches = h["trainer"], h["state"], h["batches"]
    loader = ListLoader(batches)
    with no_host_syncs():
        state, _rng, loss, tasks = trainer.train_epoch(
            state, loader, jax.random.PRNGKey(7)
        )
    assert np.isfinite(loss) and np.all(np.isfinite(tasks))
    _H["state"] = state


def pytest_transfer_guard_catches_reintroduced_float():
    """The enforcement direction: a per-batch float() under the guard
    must hard-error. Probed and skipped on host-resident backends where
    jax defines no transfer to guard (the static jaxlint gate covers
    those environments)."""
    if not _guard_enforces():
        pytest.skip(
            "transfer guard is a no-op on this (host-resident) backend"
        )
    h = _trainer()
    trainer, state, batches = h["trainer"], h["state"], h["batches"]

    class HostileLoader(ListLoader):
        pass

    def hostile_acc(acc, metrics, multi=False):
        return (acc or 0.0) + float(metrics["loss"])  # the anti-pattern

    orig = trainer._acc_add
    trainer._acc_add = hostile_acc
    try:
        with pytest.raises(Exception, match="[Tt]ransfer"):
            with no_host_syncs():
                trainer.train_epoch(
                    state, HostileLoader(batches), jax.random.PRNGKey(9)
                )
    finally:
        trainer._acc_add = orig


# ---- serving --------------------------------------------------------------

_S = {}


def _server_harness():
    if _S:
        return _S
    from hydragnn_tpu.serve import (
        InferenceServer,
        ModelRegistry,
        plan_from_samples,
    )
    from test_serve import _graph

    rng = np.random.default_rng(3)
    samples = [_graph(int(n), rng) for n in rng.integers(4, 32, 40)]
    model = create_model_config(arch_config("SAGE"))
    trainer = Trainer(
        model, {"Optimizer": {"type": "AdamW", "learning_rate": 1e-3}}
    )
    plan = plan_from_samples(samples, max_batch_graphs=4, num_buckets=2)
    init_batch, _ = plan.pack([samples[0]], 0)
    state = trainer.init_state(init_batch)
    registry = ModelRegistry()
    registry.register("sage", model, state.params, state.batch_stats)
    server = InferenceServer(registry, plan, max_wait_s=0.002)
    _S.update(server=server, samples=samples, rng=rng)
    return _S


def pytest_serve_burst_100_requests_compile_flat():
    """Warm the server (one compile per bucket), then a 100-request
    burst of mixed sizes must add ZERO compiles — at the jax level (the
    sentinel) and at the serve-metrics level."""
    h = _server_harness()
    server, samples = h["server"], h["samples"]
    with server:  # start() warms every (model, bucket) executable
        compiles_warm = server.metrics.snapshot()["compiles_total"]
        with CompileSentinel() as sentinel:
            futures = [
                server.submit(samples[i % len(samples)])
                for i in range(100)
            ]
            for fut in futures:
                heads = fut.result(timeout=60)
                assert all(np.isfinite(np.asarray(o)).all() for o in heads)
        sentinel.assert_flat("100-request serve burst")
        assert (
            server.metrics.snapshot()["compiles_total"] == compiles_warm
        )


def pytest_transfer_guard_serve_dispatch():
    """One packed dispatch under the guard: inputs are host-packed, the
    output fetch is one explicit device_get — clean."""
    from hydragnn_tpu.serve.server import _Request

    h = _server_harness()
    server, samples = h["server"], h["samples"]
    if not server.is_warm():
        server.warmup()
    g = samples[0]
    entry = server.registry.get("sage")
    bucket, sizes = server.plan.admit(g)
    req = _Request(g, entry, bucket, sizes, deadline=None, fallback=False)
    with no_host_syncs():
        server._dispatch_batch([req], bucket, real_nodes=sizes[0])
    heads = req.future.result(timeout=30)
    assert heads[0].shape == (1,)
    assert all(np.isfinite(np.asarray(o)).all() for o in heads)
