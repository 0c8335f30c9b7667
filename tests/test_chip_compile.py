"""Ask the TPU's compiler before spending chip time.

The v5e compiler is installed here and compiles for a chip that is
DESCRIBED, not attached (``topologies.get_topology_desc``): every Pallas
kernel of ``ops/`` (forward and grad) at the largest shape its VMEM guard
admits, and the whole jitted train step of ``chip_smoke.py`` from shapes.
A guard that admits a shape the compiler refuses is a bug in the guard.
Nothing runs, so this says nothing about results or times. Skipped where
the topology cannot be described.

Also CPU-side unit tests of the start-up path: where the compile cache
goes, that ``dryrun_multichip`` never switches platform, and that the
launchers refuse two chip-using children on one host.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # before libtpu loads

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke as cs
from hydragnn_tpu.ops import dense_agg as da
from hydragnn_tpu.ops import fused_mp as fm
from hydragnn_tpu.ops import local_gather as lg
from hydragnn_tpu.ops import pallas_segment as ps

V5E_HBM_BYTES = 16 * 1024**3
KERNEL = 'custom_call_target="tpu_custom_call"'
# the smoke's own buckets (64 slabs of 80-90 atoms, in-degree 12)
SMOKE_NODES, SMOKE_DIM = 5624, 256


@pytest.fixture(scope="module")
def chip():
    """``SingleDeviceSharding`` on one described v5e chip. The persistent
    compile cache is off around the module: such a compile is written to
    it but cannot be read back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"cannot describe a v5e here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The ops ask ``jax.devices()`` (the CPU here) whether to interpret;
    the compile must steer ``interpret=False`` itself."""
    monkeypatch.setattr(ps, "_interpret", lambda requested: False)
    monkeypatch.setattr(fm, "_interpret", lambda requested: False)


def _shape(chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _compile_fwd_and_grad(fn, n_diff, args):
    """One program holding the forward kernel and its VJP: a squared loss
    keeps the forward result alive in the backward."""

    def loss(*a):
        out = fn(*a)
        out = out if isinstance(out, tuple) else (out,)
        return sum((o * o).sum() for o in out)

    step = jax.value_and_grad(loss, argnums=tuple(range(n_diff)))
    return jax.jit(step).lower(*args).compile().as_text()


def _largest_admitted(admits):
    """Largest multiple of 8 for which ``admits(n)`` holds."""
    lo, hi = 8, 1 << 20
    assert admits(lo) and not admits(hi)
    while hi - lo > 8:
        mid = (lo + hi) // 16 * 8
        lo, hi = (mid, hi) if admits(mid) else (lo, mid)
    return lo


# ---- one-hot segment kernels (ops/pallas_segment.py) ------------------------


@pytest.mark.parametrize("kernel,n_outputs", [("sum", 1), ("moments", 2)])
def pytest_segment_kernel_compiles_at_guard_max(
    chip, compiled_kernels, monkeypatch, kernel, n_outputs
):
    monkeypatch.setenv("HYDRAGNN_PALLAS", "1")
    dim = SMOKE_DIM
    n = _largest_admitted(
        lambda n: ps.pallas_segments_enabled(n, dim, n_outputs)
    )
    op = ps.segment_sum_onehot if kernel == "sum" else ps.segment_moments
    text = _compile_fwd_and_grad(
        lambda data, ids: op(data, ids, n),
        1,
        (_shape(chip, (12 * n, dim)), _shape(chip, (12 * n,), jnp.int32)),
    )
    assert KERNEL in text
    # the shape the v5e compiler refuses for segment_moments (scoped VMEM
    # 17.08M > 16M at 5760 segments x 256) and the smoke's own buckets lie
    # outside the guard by construction: they run on XLA
    assert not ps.pallas_segments_enabled(5760, 256, n_outputs=2)
    assert not ps.pallas_segments_enabled(SMOKE_NODES, dim, n_outputs)


# ---- fused message-passing kernels (ops/fused_mp.py) ------------------------


def _fused_case(chip, op, n, dim):
    """(fn, number of differentiable args, arg shapes) for one wrapper."""
    e = 12 * n
    ids = _shape(chip, (e,), jnp.int32)
    mask = _shape(chip, (e,), jnp.bool_)
    table = _shape(chip, (n, dim))
    if op == "sum":
        return (lambda x, s, r, m: fm.fused_gather_sum(x, s, r, n, m),
                1, (table, ids, ids, mask))
    if op == "mean":
        return (lambda x, s, r, m: fm.fused_gather_mean(x, s, r, n, m),
                1, (table, ids, ids, mask))
    if op == "weighted_sum":
        return (lambda h, w, s, r: fm.fused_gather_weighted_sum(h, w, s, r, n),
                2, (table, _shape(chip, (e, dim)), ids, ids))
    if op == "moments":  # with the encoded-edge term: ef is [E, D + 1]
        return (lambda y, z, s, r, m: fm.fused_gather_moments(
                    y, s, r, n, m, ze=z),
                2, (table, _shape(chip, (e, dim)), ids, ids, mask))
    assert op == "egnn"  # equivariant: all six edge-MLP parameters
    params = tuple(
        _shape(chip, s) for s in
        ((1, dim), (dim, dim), (dim,), (dim, dim), (dim,), (dim, 1))
    )

    def egnn(ys, yr, pos, *rest):
        *p, s, r, m = rest
        return fm.fused_egnn_edge_phase(ys, yr, pos, p, s, r, n, m)

    return (egnn, 3 + len(params),
            (table, table, _shape(chip, (n, 3)), *params, ids, ids, mask))


# (table_dim, out_dim, table_dim_b) of each wrapper, as the models pass them
_FUSED_DIMS = {
    "sum": lambda d: (d, d, 0),
    "mean": lambda d: (d, d + 1, 0),
    "weighted_sum": lambda d: (d, d, 0),
    "moments": lambda d: (d, 2 * d + 1, 0),
    "egnn": lambda d: (d + 3, d + 4, d + 3),
}


@pytest.mark.parametrize(
    "op,dim",
    [(op, SMOKE_DIM) for op in _FUSED_DIMS]
    # the two packings whose widths are not lane multiples, narrow too
    + [("moments", 64), ("egnn", 64)],
)
def pytest_fused_kernel_compiles_at_guard_max(
    chip, compiled_kernels, op, dim
):
    td, od, tdb = _FUSED_DIMS[op](dim)
    n = _largest_admitted(lambda n: fm.fused_mp_enabled(n, n, td, od, tdb))
    fn, n_diff, args = _fused_case(chip, op, n, dim)
    assert KERNEL in _compile_fwd_and_grad(fn, n_diff, args)
    # the smoke's buckets are past every fused guard: XLA runs them
    assert not fm.fused_mp_enabled(SMOKE_NODES, SMOKE_NODES, td, od, tdb)


# ---- block-local neighbour gather (ops/local_gather.py) ---------------------


@pytest.mark.parametrize(
    "n,reach,k_in,dim",
    [
        (88648, 225, 12, 256),  # the PNA cell's largest bucket: h = 2
        (39432, 129, 12, 1),  # its first layer: one feature, padded to 128
        (4104, 257, 19, 512),  # the rule's limit: 20 tiles, blocks at the budget
        (4104, 1153, 12, 1),  # the farthest reach it admits: h = 9, 19 tiles
        (4104, 129, 100, 128),  # a hundred slots: the forward's 0/1 matrix at the budget
    ],
)
def pytest_local_gather_compiles_where_the_rule_selects_it(
    chip, monkeypatch, n, reach, k_in, dim
):
    monkeypatch.setattr(da, "_backend", lambda: "tpu")
    h = lg.window_halo(jnp.bfloat16, reach, k_in, dim, "tpu")
    assert h == -(-(reach - 1) // lg.BLOCK)
    # one more slot, or one more block of reach, and the rule keeps XLA's
    if (n, dim) == (4104, 512):
        assert lg.window_halo(jnp.bfloat16, reach, k_in + 1, dim, "tpu") is None
        assert lg.window_halo(jnp.bfloat16, reach + 1, k_in, dim, "tpu") is None
    lists = lambda dt, k: _shape(chip, (n, k), dt)
    text = _compile_fwd_and_grad(
        lambda x, idx, rev, rmask, mask, stated: da.gather_neighbors(
            x, idx, rev, rmask, mask, stated
        ),
        1,
        (
            _shape(chip, (n, dim), jnp.bfloat16),
            lists(jnp.int32, k_in), lists(jnp.int32, 21),
            lists(jnp.bool_, 21), lists(jnp.bool_, k_in),
            _shape(chip, (reach,), jnp.int8),
        ),
    )
    assert text.count(KERNEL) == 2


# ---- the whole jitted train step of chip_smoke.py ---------------------------


def _smoke_train_step(tmp_path, monkeypatch, sizes):
    """(trainer, state shapes, host batch) through the entry points
    ``run_training`` uses; only shapes are made, nothing is initialized."""
    from hydragnn_tpu.data.loaders import dataset_loading_and_splitting
    from hydragnn_tpu.models.create import create_model_config
    from hydragnn_tpu.train.driver import _arch_for_factory
    from hydragnn_tpu.train.trainer import Trainer
    from hydragnn_tpu.utils.config import update_config

    monkeypatch.setattr(cs, "OUT_DIR", str(tmp_path))
    sz = dict(cs.FULL, conv_layers=1, eval_graphs=4, **sizes)  # depth cut
    config = cs.make_config(sz, "compile", cs.write_dataset(sz, "compile"))
    loaders = dataset_loading_and_splitting(config)
    config = update_config(config, *loaders)
    trainer = Trainer(
        create_model_config(_arch_for_factory(config)),
        config["NeuralNetwork"]["Training"],
    )
    batch = next(iter(loaders[0]))
    return trainer, jax.eval_shape(lambda: trainer.init_state(batch)), batch


def _compile_train_step(chip, trainer, state, batch):
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: _shape(chip, np.shape(a), a.dtype), tree
    )
    return trainer._train_step.lower(
        on_chip(state),
        on_chip(trainer._compact_for_transfer(batch)),
        _shape(chip, (2,), jnp.uint32),
    ).compile()


@pytest.mark.parametrize("backend,kernels", [("cpu", 0), ("tpu", 2)])
def pytest_smoke_train_step_compiles_full_width(
    chip, tmp_path, monkeypatch, backend, kernels
):
    """Headline width and batch (PNA h256 bf16, 64 slabs): the policy lays
    the batch out dense and its collate states locality. Traced for the
    CPU this process runs on, XLA runs the whole step; traced as on the
    chip, the neighbour gather of the (depth-cut) conv is the block-local
    product, forward and backward. Either fits the chip's 16 GB with room
    to spare."""
    monkeypatch.setattr(da, "_backend", lambda: backend)
    trainer, state, batch = _smoke_train_step(
        tmp_path, monkeypatch, dict(train_graphs=64)
    )
    assert "nbr_idx" in batch.extras and batch.x.shape[0] > 5000
    assert 80 <= batch.extras["nbr_reach"].shape[0] <= 90  # its bucket's largest slab
    compiled = _compile_train_step(chip, trainer, state, batch)
    assert compiled.as_text().count(KERNEL) == kernels
    mem = compiled.memory_analysis()
    assert (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
    ) < V5E_HBM_BYTES // 4


def pytest_smoke_train_step_compiles_with_fused_kernels(
    chip, compiled_kernels, tmp_path, monkeypatch
):
    """Same width, forced onto the fused family at a bucket its guard
    admits (16 slabs): the kernels compile inside the complete step."""
    monkeypatch.setenv("HYDRAGNN_AGG", "fused")
    trainer, state, batch = _smoke_train_step(
        tmp_path, monkeypatch, dict(train_graphs=16, batch=16)
    )
    n = batch.x.shape[0]
    assert "nbr_idx" not in (batch.extras or {})
    assert fm.fused_mp_enabled(n, n, 256, 513)
    compiled = _compile_train_step(chip, trainer, state, batch)
    assert KERNEL in compiled.as_text()


# ---- start-up path, on the CPU ----------------------------------------------


@pytest.mark.parametrize("from_env", [True, False])
def pytest_compile_cache_is_placed_from_outside(monkeypatch, from_env):
    from hydragnn_tpu.utils import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(compile_cache, "_enabled", False)
    monkeypatch.delenv("HYDRAGNN_COMPILE_CACHE", raising=False)
    try:
        jax.config.update("jax_compilation_cache_dir", "/set/by/jax")
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
            compile_cache.enable_compile_cache()
            # JAX already honours the variable: the code sets no directory
            assert jax.config.jax_compilation_cache_dir == "/set/by/jax"
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            compile_cache.enable_compile_cache()
            want = os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                ".jax_cache",
            )
            assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def pytest_dryrun_multichip_never_switches_platform():
    from __graft_entry__ import dryrun_multichip

    have = len(jax.devices())
    with pytest.raises(RuntimeError, match=f"JAX reports {have}"):
        dryrun_multichip(have + 1)
    assert jax.devices()[0].platform == "cpu" and len(jax.devices()) == have


def pytest_launchers_refuse_two_chip_children_on_one_host(monkeypatch):
    from hydragnn_tpu.parallel import distributed

    monkeypatch.setattr(distributed, "host_tpu_chips", lambda: 1)
    check = distributed.require_one_process_per_chip
    check(1, {}, "one child")
    check(2, {"JAX_PLATFORMS": "cpu"}, "children pinned to the CPU")
    with pytest.raises(RuntimeError, match="one process at a time"):
        check(2, {}, "two children that would each ask for the chip")
    monkeypatch.setattr(distributed, "host_tpu_chips", lambda: 0)
    check(2, {}, "no chip on this host")
