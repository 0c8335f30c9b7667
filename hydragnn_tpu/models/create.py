"""Model factory — parity with ``hydragnn/models/create.py:31-312``.

``create_model_config(config["NeuralNetwork"]["Architecture"], ...)`` unpacks
the derived architecture section (after ``update_config``) and dispatches on
``model_type`` to one of the 9 stacks. Returns the flax module; parameters are
materialized separately (functional JAX) by ``init_model_params``.
"""

import functools
from typing import Optional

import jax
import numpy as np

from hydragnn_tpu.models.base import HydraBase
from hydragnn_tpu.models.pna import PNAStack
from hydragnn_tpu.models.gin import GINStack
from hydragnn_tpu.models.gat import GATStack
from hydragnn_tpu.models.mfc import MFCStack
from hydragnn_tpu.models.sage import SAGEStack
from hydragnn_tpu.models.cgcnn import CGCNNStack
from hydragnn_tpu.models.schnet import SCFStack
from hydragnn_tpu.models.egnn import EGCLStack
from hydragnn_tpu.models.dimenet import DIMEStack

STACKS = {
    "GIN": GINStack,
    "PNA": PNAStack,
    "GAT": GATStack,
    "MFC": MFCStack,
    "CGCNN": CGCNNStack,
    "SAGE": SAGEStack,
    "SchNet": SCFStack,
    "DimeNet": DIMEStack,
    "EGNN": EGCLStack,
}
MODEL_TYPES = list(STACKS)


def needs_edge_offsets(arch: dict) -> bool:
    """Whether the batches of the stack that ``arch`` (an Architecture
    section) names must carry each edge's periodic image,
    ``extras["edge_offset"]``: the stack computes distances from positions
    (its ``reads_edge_offset``) and the data is periodic. Every layout
    builder asks this (the loaders, the examples' loaders, serving plans,
    graph partitions), and the stack refuses a periodic batch without it."""
    stack = STACKS.get(arch.get("model_type"))
    return bool(
        getattr(stack, "reads_edge_offset", False)
        and arch.get("periodic_boundary_conditions")
    )


def _normalize_weights(task_weights, num_heads):
    if task_weights is None:
        task_weights = [1.0] * num_heads
    if len(task_weights) != num_heads:
        raise ValueError(
            f"Inconsistent number of loss weights and tasks: "
            f"{len(task_weights)} VS {num_heads}"
        )
    s = sum(abs(w) for w in task_weights)
    return tuple(w / s for w in task_weights)


def create_model_config(config: dict, verbosity: int = 0) -> HydraBase:
    """``config`` is the Architecture section, post-``update_config``."""
    model_type = config["model_type"]
    output_dim = tuple(config["output_dim"])
    output_type = tuple(config["output_type"])
    num_heads = len(output_dim)
    common = dict(
        input_dim=config["input_dim"],
        hidden_dim=config["hidden_dim"],
        output_dim=output_dim,
        output_type=output_type,
        config_heads=config["output_heads"],
        activation=config.get("activation_function", "relu"),
        loss_function_type=config.get("loss_function_type", "mse"),
        equivariance=config.get("equivariance", False),
        loss_weights=_normalize_weights(config.get("task_weights"), num_heads),
        num_conv_layers=config["num_conv_layers"],
        num_nodes=config.get("num_nodes"),
        conv_checkpointing=config.get("conv_checkpointing", False),
        initial_bias=config.get("initial_bias"),
        # uncertainty-weighted NLL multi-task loss — the mode the reference
        # declares but leaves unreachable/unfinished (Base.py:335-354,
        # create.py:71); heads grow one log-variance channel
        loss_nll=bool(config.get("ilossweights_nll", 0)),
        # graph-partition parallelism over one giant graph (config key
        # "partition_axis" names the mesh axis; see parallel/graph_partition)
        partition_axis=config.get("partition_axis"),
    )
    edge_dim = config.get("edge_dim")

    if model_type == "GIN":
        return GINStack(**common)
    if model_type == "PNA":
        assert config.get("pna_deg") is not None, "PNA requires degree input."
        return PNAStack(deg=tuple(config["pna_deg"]), edge_dim=edge_dim, **common)
    if model_type == "GAT":
        # the reference hardcodes 6 / 0.05 (create.py:150-152) and 0.25
        # (Base.py's dropout); a config that states them is heard
        return GATStack(
            heads=config.get("heads", 6),
            negative_slope=config.get("negative_slope", 0.05),
            dropout=config.get("dropout", 0.25),
            **common,
        )
    if model_type == "MFC":
        assert (
            config.get("max_neighbours") is not None
        ), "MFC requires max_neighbours input."
        return MFCStack(
            max_degree=config["max_neighbours"],
            degree_bound=config.get("mfc_degree_bound"),
            **common,
        )
    if model_type == "CGCNN":
        # constant width: hidden == input (CGCNNStack.py:30-40); conv node
        # heads unsupported (CGCNNStack.py:66-89)
        heads_cfg = config["output_heads"]
        if (
            "node" in heads_cfg
            and heads_cfg["node"].get("type") == "conv"
            and any(t == "node" for t in output_type)
        ):
            raise ValueError(
                '"conv" for node features decoder part in CGCNN is not ready yet.'
            )
        common["hidden_dim"] = common["input_dim"]
        return CGCNNStack(edge_dim=edge_dim if edge_dim is not None else 0, **common)
    if model_type == "SAGE":
        return SAGEStack(**common)
    if model_type == "SchNet":
        assert config.get("num_gaussians") is not None
        assert config.get("num_filters") is not None
        assert config.get("radius") is not None
        # the reference passes (num_gaussians, num_filters) positionally
        # into SCFStack(num_filters, num_gaussians, ...), swapping them
        # (create.py:228-247 vs SCFStack.py:33-46); here each is what its
        # name says (docs/MIGRATION.md). ``interaction_block`` gives SchNet's
        # own block (models/schnet.py); absent, HydraGNN's SCFStack form.
        return SCFStack(
            num_filters=config["num_filters"],
            num_gaussians=config["num_gaussians"],
            radius=config["radius"],
            edge_dim=edge_dim,
            interaction_block=bool(config.get("interaction_block", False)),
            periodic=bool(config.get("periodic_boundary_conditions", False)),
            **common,
        )
    if model_type == "DimeNet":
        for key in (
            "basis_emb_size",
            "envelope_exponent",
            "int_emb_size",
            "out_emb_size",
            "num_after_skip",
            "num_before_skip",
            "num_radial",
            "num_spherical",
            "radius",
        ):
            assert config.get(key) is not None, f"DimeNet requires {key} input."
        return DIMEStack(
            basis_emb_size=config["basis_emb_size"],
            envelope_exponent=config["envelope_exponent"],
            int_emb_size=config["int_emb_size"],
            out_emb_size=config["out_emb_size"],
            num_after_skip=config["num_after_skip"],
            num_before_skip=config["num_before_skip"],
            num_radial=config["num_radial"],
            num_spherical=config["num_spherical"],
            radius=config["radius"],
            **common,
        )
    if model_type == "EGNN":
        return EGCLStack(edge_dim=edge_dim if edge_dim is not None else 0, **common)
    raise ValueError(f"Unknown model_type: {model_type}")


# ---------------------------------------------------------------------------
# param-precision policy (mixed bf16 across the model zoo)
# ---------------------------------------------------------------------------

# Minimum hidden width at which bf16 compute pays per stack: below it the
# step is op-latency/scatter-bound and bf16 buys nothing while costing
# precision (graph/segment.py upcasts scatters for exactly this reason);
# at MXU widths the measured wins are large (BENCH_EXTRA dense-bf16 rows,
# e.g. PNA h256 1.76x).
#
# DimeNet's row was read on THIS tree, 2026-10-03 (PR 30, one TPU v5 lite,
# benchmarks/dimenet_family_ab.py: the train step of
# dimenetpp_h128x4_train_mptrj, its traffic at rung 64, hidden 128 x 4
# blocks, ms a step, f32 | bf16): dense lists 30.012 | 21.029 (1.43 x),
# triplet tables 149.371 | 134.452 (1.11 x). Before it the stack was
# absent here ("the measured bf16 delta was within noise", at toy widths
# and with an f32 radial basis that promoted every edge-level product
# back to f32). Its geometry (distances, angles, the spherical and radial
# bases) and the Bessel frequencies stay f32 in a bf16 run
# (models/dimenet.py, DIMEStack.f32_params); against the f32 reference the
# cell's bf16 step reads grad_gap 0.013 where fp8 operands read 1.0
# (PERF.md section 2).
#
# GAT's row was read on THIS tree, 2026-10-04 (PR 32, one TPU v5 lite,
# benchmarks/gat_family_ab.py: the train step of gatv2_h4x256_train_oc20
# at its rung of 256, 4 heads x 256 x 3 layers, ms a step, f32 | bf16):
# dense lists 135.952 | 79.868 (1.70 x: the [N, 12, 1024] tables halve),
# edge list 400.179 | 320.029 (1.25 x). That is a SPEED reading. Accuracy
# (the cell's comparison against its f32 reference, first gradient, worst
# leaf; PERF.md section 2): bf16 0.035-0.26 over 27 seeds, the f32 program
# 0.003-0.007, f32 under HIGHEST products 3e-5, fp8 operands 1.0; scores,
# the softmax and its denominator stay f32 in a bf16 run (models/gat.py),
# so what bf16 costs is the rounding of weights and node states ahead of
# a BatchNorm over a nearly constant layer-0 output. The row stands at
# 128; only 256 was read.
BF16_AUTO_MIN_HIDDEN = {
    "PNA": 128,
    "GAT": 128,
    "GIN": 128,
    "SAGE": 128,
    "MFC": 128,
    "CGCNN": 128,
    "SchNet": 128,
    "EGNN": 128,
    "DimeNet": 128,
}


def resolve_precision(model, training_config: dict) -> dict:
    """:func:`precision_for` a built stack (steps.py consumes it)."""
    from hydragnn_tpu.ops.agg_policy import model_key_for

    return precision_for(
        model_key_for(model), getattr(model, "hidden_dim", 0), training_config
    )


def precision_for(model_key: str, hidden_dim, training_config: dict) -> dict:
    """The ONE mixed-precision decision point, from what a config states
    (the family rule of ``ops/agg_policy.py`` asks it before a model is
    built: EGNN's dense side wins only in bf16).

    Master params always stay f32 for the optimizer; this resolves whether
    the forward/backward COMPUTE runs in bf16. Order:

    1. ``HYDRAGNN_MIXED_PRECISION=0/1`` — operator override;
    2. explicit ``Training.mixed_precision: true/false``;
    3. ``Training.mixed_precision: "auto"`` — the per-model width policy
       above (bf16 iff the stack is in the table AND hidden_dim clears its
       threshold — tiny CI-scale configs stay f32 under "auto");
    4. absent — f32 (the conservative historical default).

    Returns ``{"mixed": bool, "source": "env|explicit|policy|default"}``.
    """
    import os

    env = os.getenv("HYDRAGNN_MIXED_PRECISION")
    if env is not None and env.strip() != "":
        off = env.strip().lower() in ("0", "false", "no", "off")
        return {"mixed": not off, "source": "env"}
    flag = training_config.get("mixed_precision", False)
    if isinstance(flag, str) and flag.strip().lower() == "auto":
        th = BF16_AUTO_MIN_HIDDEN.get(model_key)
        mixed = th is not None and int(hidden_dim or 0) >= th
        return {"mixed": mixed, "source": "policy"}
    return {
        "mixed": bool(flag),
        "source": "explicit" if "mixed_precision" in training_config
        else "default",
    }


def init_model_params(model: HydraBase, example_batch, seed: int = 0):
    """Materialize parameters + batch stats (reference seeds torch with 0,
    ``create.py:107``).

    The init runs under ONE jit: eager flax init dispatches every traced
    primitive as its own XLA program (148 of them for this stack), none
    of which clears JAX's default 1 s persistent-cache threshold — so the
    cost would recur in every process. One program compiles once,
    persists, and PRNG values are bit-identical either way."""
    rngs = {"params": jax.random.PRNGKey(seed), "dropout": jax.random.PRNGKey(1)}
    # one-shot by design: init runs ONCE per process/model, and jitting it
    # is the whole point (one fused program instead of 148 eager dispatches)
    variables = jax.jit(functools.partial(model.init, train=False))(  # jaxlint: disable=jit-in-loop
        rngs, example_batch
    )
    return variables


def print_model(model: HydraBase, variables, verbosity: int = 0):
    """Parameter summary — top-level module table + total trainable count
    (``hydragnn/utils/model.py:173-181``)."""
    from hydragnn_tpu.utils.print_utils import print_distributed

    params = variables.get("params", variables)
    per_module = {}
    total = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        top = getattr(path[0], "key", str(path[0]))
        per_module[top] = per_module.get(top, 0) + int(np.prod(leaf.shape))
        total += int(np.prod(leaf.shape))
    print_distributed(verbosity, f"model: {type(model).__name__}")
    for name in sorted(per_module):
        print_distributed(verbosity, f"  {name}: {per_module[name]:,} params")
    print_distributed(verbosity, f"total trainable params: {total:,}")
    return total
