"""Ask the TPU's compiler before spending chip time.

The v5e compiler is installed here and compiles for a chip that is
DESCRIBED, not attached (``topologies.get_topology_desc``): the Pallas
kernels of ``ops/local_gather.py`` (forward and grad) at the edges of what
``window_halo`` admits, and the whole jitted train step of
``chip_smoke.py`` from shapes. A rule that admits a shape the compiler
refuses is a bug in the rule.
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
from hydragnn_tpu.ops import local_gather as lg

V5E_HBM_BYTES = 16 * 1024**3
KERNEL = 'custom_call_target="tpu_custom_call"'


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


@pytest.mark.parametrize(
    "n,reach",
    [
        (44336, 200),  # the EGNN cell's largest bucket: h = 2, 10 tiles
        (7480, 29),  # its smallest: h = 1
    ],
)
def pytest_egnn_products_compile_at_the_cell_buckets(chip, monkeypatch, n, reach):
    """E_GCL's two calls at the cell's width and 16 slots: the gather of
    128 bf16 columns with 3 f32 positions beside them (9 pieces, a second
    table of the call) and the sender sum of 128 with 3 translations and
    the count (12 pieces), each with its transpose: four kernels, the
    pieces' sums out of the kernel in f32."""
    monkeypatch.setattr(da, "_backend", lambda: "tpu")
    k, d = 16, 128
    assert lg.window_halo(
        jnp.bfloat16, reach, k, lg.lane_width(d, 12), "tpu"
    ) == -(-(reach - 1) // lg.BLOCK)
    lists = {
        "nbr_idx": _shape(chip, (n, k), jnp.int32),
        "nbr_mask": _shape(chip, (n, k), jnp.bool_),
        "rev_idx": _shape(chip, (n, 21), jnp.int32),
        "rev_mask": _shape(chip, (n, 21), jnp.bool_),
        "nbr_reach": _shape(chip, (reach,), jnp.int8),
    }

    def both(y, pos, e, trans, ex):
        y_j, pos_j = da.neighbor_rows(y, ex, exact=pos)
        agg, moved = da.sender_sums(e, ex, exact=trans)
        return y_j.astype(jnp.float32), pos_j, agg.astype(jnp.float32), moved

    text = _compile_fwd_and_grad(
        both, 4,
        (
            _shape(chip, (n, d), jnp.bfloat16), _shape(chip, (n, 3)),
            _shape(chip, (n, k, d), jnp.bfloat16), _shape(chip, (n, k, 4)),
            lists,
        ),
    )
    assert text.count(KERNEL) == 4
    assert "f32[%d,128]" % n in text  # the pieces' sums leave the kernel in f32


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
