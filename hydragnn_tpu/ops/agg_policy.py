"""The aggregation family rule, and the reporting of what ran.

Two aggregation families serve the message-passing hot path:

- **segment**: ``jax.ops.segment_*`` scatters over the edge list
  (``graph/segment.py``; XLA fuses them with the surrounding elementwise
  work);
- **dense**: host-built fixed-width neighbor lists, scatter-free masked
  K-axis reductions (``ops/dense_agg.py``), whose neighbour gather and
  sender sum are block-local one-hot products where the operands allow it
  (``ops/local_gather.py window_halo``).

The family is a LAYOUT decision (the loader builds neighbor lists or it
does not), made once per run by :func:`needs_dense_neighbors`: partitioned
-> segment; an explicit ``Architecture.dense_aggregation`` -> that; else
the static width tables below (EGNN's row for runs that compute in bf16,
as the one precision rule resolves it). Nothing else steers it: no
environment name or file of its own.

What ran is emitted as ``agg_choice`` obs events (schema in
``obs/events.py``) and an ``aggregation_kernel`` labeled gauge: the family
the batch layout committed to (source ``layout``, emitted by
``models/base.py`` at trace time) and the implementation of each neighbour
gather and sender sum (source ``operands``, ``ops/dense_agg.py``).
"""

CHOICES = ("segment", "dense")

# Dense/segment crossovers: minimum hidden_dim at which the dense
# scatter-free path beats segment reductions for each model. All rows but
# EGNN's, DimeNet's, GAT's and SchNet's were measured on a v5e before the
# current tree (2026-07/08, same-session A/Bs at deg ~12; not re-measured since,
# nor after PR 27 changed the dense path's cost — ROADMAP D11). Scatter-heavy
# models (PNA's 4 aggregators, GAT's edge softmax, MFC's degree banks,
# DimeNet's triplet axis) cross early; GIN/SAGE only win mildly at MXU
# widths.
#
# EGNN's row was read on THIS tree, 2026-10-03 (PR 29, one TPU v5 lite,
# benchmarks/egnn_family_ab.py: the train step of egnn_h128x7_train_mptrj,
# its traffic, rung 384 and seven layers, dense_aggregation false | true,
# ms a step): hidden 32: 77.9 | 110.6; 64: 83.8 | 109.8; 128: 106.1 | 62.5.
# The smallest of the three at which dense wins by more than 10% is 128:
# where the precision policy ("auto") turns bf16 on, so the dense path's
# gather and sender sum are the products of ops/local_gather.py; under it
# the tables are f32, keep XLA's gathers, and lose to the one fused
# scatter a layer; so does an f32 run AT 128 (mixed_precision false:
# 106.5 | 176.5), which is why the row holds for bf16 runs only
# (DENSE_ROWS_READ_IN_BF16).
#
# DimeNet's row was read on THIS tree too, 2026-10-03 (PR 30, one TPU v5
# lite, benchmarks/dimenet_family_ab.py: the train step of
# dimenetpp_h128x4_train_mptrj, its traffic at rung 64, <= 32 neighbours,
# hidden 128 x 4 blocks, dense_aggregation true | false, ms a step): f32
# 30.012 | 149.371 (4.98 x), bf16 21.029 | 134.452 (6.39 x): the triplet
# tables pay a gather, a scatter and their transposes per triplet and
# layer (~25 triplets an edge), the slot grids one batched product per
# central node; and the tables do not fit a memory-filling batch (the v5e
# compiler refuses rung 96, 16.07 GB, where the lists take 10.7). The row
# stands at 96 in either precision; only 128 was read.
#
# GAT's row was read on THIS tree, 2026-10-04 (PR 32, one TPU v5 lite,
# benchmarks/gat_family_ab.py: the train step of gatv2_h4x256_train_oc20,
# its traffic at its rung of 256, 4 heads x 256 x 3 layers, degree 12,
# dense_aggregation true | false, ms a step): f32 135.952 | 400.179
# (2.94 x), bf16 79.868 | 320.029 (4.01 x). hidden_dim is the width PER
# HEAD here: the tables the row decides about are heads x hidden_dim
# wide (1,024 columns at this reading, which keep XLA's gather on the
# dense side: window_halo refuses eight lane tiles), and the edge list
# pays a 1,025-column scatter per head group and layer. The row stands at
# 96; only 256 (x 4 heads) was read.
#
# SchNet's row was read on THIS tree, 2026-10-15 (PR 36, one TPU v5 lite,
# benchmarks/schnet_family_ab.py: the train step of
# schnet_h1024x5_train_oc20, its traffic, 1,024 x 256 filters x 200
# Gaussians x 5, 6 A / <= 50 neighbours, dense_aggregation true | false, ms
# a step). The old "SchNet never" (one fused scatter a layer) was read at
# small widths before the current tree. Rung 64: f32 68.51 | 47.03 (the
# f32 tables keep XLA's gathers), bf16 35.49 | 42.29 (1.19 x). The cell's
# rung of 128, bf16: 63.37 | 86.43 (1.36 x; by bucket 22.9 | 36.5,
# 40.2 | 75.5, 120.3 | 122.8, 181.0 | 246.1), though the two large
# buckets' step programs run no product kernel (their window is refused,
# XLA's gathers). So the row is bf16-only, at 1,024: only 1,024 was read.
#
# CGCNN's published block (``cgcnn_conv``, keyed on hidden_dim, the width
# its convs run at) was read on THIS tree, 2026-10-18 (one TPU v5 lite,
# benchmarks/cgcnn_family_ab.py: the train step of
# cgcnn_h512x3_train_oc20, its traffic, 512 x 3 convs, 100 Gaussians,
# 6 A / <= 50 neighbours, the shipped edge form, dense_aggregation true |
# false, ms a step). The cell's rung of 64, bf16: 77.925 | 91.878 (1.18 x;
# by bucket 44.9 | 39.4, 72.1 | 85.1, 110.6 | 141.4, 162.1 | 222.8: the
# edge list wins the smallest bucket alone); rung 32, f32: 55.768 |
# 49.534. Every step program gathers through XLA (a 512-wide table at
# K = 47-50 takes no product kernel: ``kernels`` 0). So the row is
# bf16-only, at 512: only 512 was read.
DENSE_AUTO_MIN_HIDDEN = {
    "PNA": 96,
    "GAT": 96,
    "MFC": 96,
    "DimeNet": 96,
    "GIN": 192,
    "SAGE": 192,
    "EGNN": 128,
    "SchNet": 1024,
    # the published block's row (above); HydraGNN's constant-width CGConv
    # has its own rule below
    "CGCNN": 512,
}

# Rows that hold only where the run computes in bf16 (the dense side wins
# as products, and a product wants a bf16 table): ``arch_for_auto_policy``
# states the run's precision as ``bf16_compute``, resolved by the one
# precision rule (``models/create.py precision_for``); absent, the row
# does not apply.
DENSE_ROWS_READ_IN_BF16 = ("EGNN", "SchNet", "CGCNN")

# HydraGNN's constant-width CGConv (CGCNN without ``cgcnn_conv``) keyed on
# its TRUE conv width, input_dim, and INVERSELY: dense gathers
# [N, K, input_dim] blocks, so gather traffic grows with input width while
# the segment scatter cost stays flat. Maximum input_dim at which the
# dense path is picked automatically. Read at small widths before the
# current tree (2026-07/08, like the older rows above), not since.
DENSE_AUTO_MAX_INPUT_DIM = {
    "CGCNN": 64,
}


def _conv_width_key(arch_config: dict) -> str:
    """The Architecture key the stack states its conv width under (its
    ``conv_width_key``; hidden_dim for a model type no stack names)."""
    from hydragnn_tpu.models.create import STACKS

    stack = STACKS.get(arch_config.get("model_type"))
    return "hidden_dim" if stack is None else stack.conv_width_key(arch_config)


def _constant_width(arch_config: dict) -> bool:
    """Convs that run at the input width, decided by the inverse row."""
    return arch_config.get("model_type") in DENSE_AUTO_MAX_INPUT_DIM and (
        _conv_width_key(arch_config) == "input_dim"
    )


def conv_width(arch_config: dict) -> int:
    """The width a stack's convs run at, which the family and precision
    rows key on."""
    return int(arch_config.get(_conv_width_key(arch_config)) or 0)


def auto_dense_aggregation(arch_config: dict) -> bool:
    """The measured-crossover policy: dense iff the (model type, conv
    width) point sits on the dense-winning side of the tables above.
    HydraGNN's constant-width CGConv keys on input_dim instead, and
    inversely; absent/0 input_dim stays conservative: segment."""
    mt = arch_config.get("model_type")
    width = conv_width(arch_config)
    if _constant_width(arch_config):
        return 1 <= width <= DENSE_AUTO_MAX_INPUT_DIM[mt]
    th = DENSE_AUTO_MIN_HIDDEN.get(mt)
    if th is None or width < th:
        return False
    return mt not in DENSE_ROWS_READ_IN_BF16 or bool(
        arch_config.get("bf16_compute")
    )


def static_aggregation_choice(arch_config: dict) -> str:
    """The tables' choice for a model config: what bench.py records as
    ``auto_choice``."""
    return "dense" if auto_dense_aggregation(arch_config) else "segment"


def arch_for_auto_policy(nn_config: dict) -> dict:
    """Architecture dict enriched with what the tables key on and the
    Architecture section does not state: ``input_dim`` (CGCNN's crossover
    key) derived from ``Variables_of_interest.input_node_features`` when
    the config predates ``update_config``, and ``bf16_compute`` for the
    rows read in bf16, from the ``Training`` section through the one
    precision rule. ONE derivation shared by every entry point so their
    dense/segment decisions cannot diverge."""
    arch = nn_config["Architecture"]
    derived = {}
    feats = nn_config.get("Variables_of_interest", {}).get(
        "input_node_features"
    )
    if feats and "input_dim" not in arch:
        derived["input_dim"] = len(feats)
    mt = arch.get("model_type")
    if (
        mt in DENSE_ROWS_READ_IN_BF16
        and "bf16_compute" not in arch
        and not _constant_width(arch)
    ):
        from hydragnn_tpu.models.create import precision_for

        derived["bf16_compute"] = precision_for(
            mt, arch.get("hidden_dim"), nn_config.get("Training", {})
        )["mixed"]
    return dict(arch, **derived) if derived else arch


def needs_dense_neighbors(arch_config: dict) -> bool:
    """Single rule for dense scatter-free aggregation in the BATCH-collate
    path: an explicit ``dense_aggregation`` true/false, else the width
    tables. Off under graph partitioning — there the partitioner builds
    per-shard lists itself (``partition_graph(need_neighbors=True)``,
    wired by the driver)."""
    if arch_config.get("partition_axis"):
        return False
    flag = arch_config.get("dense_aggregation")
    if flag is not None:
        return bool(flag)
    return auto_dense_aggregation(arch_config)


# ---------------------------------------------------------------------------
# bucket signatures
# ---------------------------------------------------------------------------

_STACK_KEYS = {
    "PNAStack": "PNA",
    "GINStack": "GIN",
    "GATStack": "GAT",
    "MFCStack": "MFC",
    "SAGEStack": "SAGE",
    "CGCNNStack": "CGCNN",
    "SCFStack": "SchNet",
    "EGCLStack": "EGNN",
    "DIMEStack": "DimeNet",
}


def model_key_for(model) -> str:
    """Short model key ("PNA", "SchNet", ...) from a stack instance."""
    name = type(model).__name__
    return _STACK_KEYS.get(name, name.replace("Stack", ""))


def bucket_signature(model_key: str, num_nodes: int, num_edges: int,
                     dim: int) -> str:
    """One bucket layout's identity: padded node/edge counts + feature
    width + model: exactly the statics a compiled program is specialized
    on."""
    return f"{model_key}/n{int(num_nodes)}/e{int(num_edges)}/d{int(dim)}"


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------

def emit_choice(signature: str, choice: str, source: str, **extra):
    """One ``agg_choice`` event + ``aggregation_kernel`` gauge per novel
    (signature, choice, source) PER TELEMETRY RUN — deduplicated so
    per-trace re-decisions don't spam the stream. The dedup set lives ON
    the active RunTelemetry (not process-global, and not keyed by id() —
    a GC'd run's address gets reused), so every run's events.jsonl
    stands alone; with no run active there is nothing to emit. ``extra``
    fields ride on the event (the neighbour gather's ``gather`` / ``h``,
    ``ops/dense_agg.py``); a choice outside ``CHOICES`` names no kernel
    family and sets no gauge."""
    from hydragnn_tpu.obs import runtime as obs_rt

    run = obs_rt.active()
    if run is None:
        return
    emitted = getattr(run, "_agg_choice_emitted", None)
    if emitted is None:
        emitted = set()
        run._agg_choice_emitted = emitted
    key = (signature, choice, source)
    if key in emitted:
        return
    emitted.add(key)
    try:
        obs_rt.emit(
            "agg_choice", bucket=signature, choice=choice, source=source,
            **extra,
        )
        if choice not in CHOICES:
            return
        # exactly ONE choice label reads 1 per bucket
        for c in CHOICES:
            run.metrics.registry.set_labeled(
                "aggregation_kernel",
                1.0 if c == choice else 0.0,
                bucket=signature,
                choice=c,
            )
    except Exception:
        pass


def emit_layout_choice(model, batch):
    """Report the aggregation family the batch LAYOUT committed this
    bucket to: ``dense`` when the loader built neighbor lists (every
    dense-capable conv then takes its scatter-free branch), else
    ``segment``. Called once per traced forward (``models/base.py``), so
    the decision enacted at layout time — before any telemetry run
    exists — still shows up as the path that ran."""
    dense = "nbr_idx" in (batch.extras or {})
    emit_choice(
        bucket_signature(
            model_key_for(model), batch.x.shape[0], batch.senders.shape[0],
            model.hidden_dim,
        ),
        "dense" if dense else "segment",
        "layout",
    )
