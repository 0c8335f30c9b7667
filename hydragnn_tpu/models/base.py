"""HydraBase — the multi-headed GNN stack, TPU-native.

Behavioral contract from the reference ``hydragnn/models/Base.py:26-376``:
conv stack -> BatchNorm + activation per layer -> masked global mean pool ->
shared graph MLP + per-head MLPs (graph heads), node heads as shared-weight
MLP / per-node MLP bank / conv stacks -> weighted multi-task loss
(``loss_hpweighted``, ``Base.py:356-373``).

TPU-first differences:
  * one flax module, applied inside a single jitted train step;
  * all pooling/norm/loss are padding-aware (masks from ``GraphBatch``);
  * per-node MLPs (``mlp_per_node``) are a single gathered parameter bank
    (einsum over a [num_mlp, in, out] tensor) instead of a Python loop over
    ``num_nodes`` modules (``Base.py:379-439``) — one MXU matmul;
  * conv gradient checkpointing is ``nn.remat`` (``jax.checkpoint``) instead
    of ``torch.utils.checkpoint`` (``Base.py:296-301``).
"""

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from hydragnn_tpu.graph.batch import GraphBatch
from hydragnn_tpu.models.common import (
    MLP,
    MaskedBatchNorm,
    TorchLinear,
    get_activation,
    global_mean_pool,
    masked_error,
    masked_gaussian_nll,
)


class MLPNode(nn.Module):
    """Node-level head: one shared MLP (``mlp``) or a per-node MLP bank
    (``mlp_per_node``) — reference ``Base.py:379-439``.

    The bank is stored as stacked parameters ``[num_mlp, fan_in, fan_out]``;
    each node gathers its own MLP by its position within the graph, so the
    whole head is a batched matmul instead of ``num_nodes`` separate modules.
    """

    input_dim: int
    output_dim: int
    num_mlp: int
    hidden_dims: Tuple[int, ...]
    activation: str = "relu"

    @nn.compact
    def __call__(self, x, node_index_in_graph):
        act = get_activation(self.activation)
        dims = [self.input_dim] + list(self.hidden_dims) + [self.output_dim]
        sel = jnp.clip(node_index_in_graph, 0, self.num_mlp - 1)
        h = x
        n_layers = len(dims) - 1
        for i in range(n_layers):
            fan_in, fan_out = dims[i], dims[i + 1]
            bound = 1.0 / jnp.sqrt(fan_in)
            kernel = self.param(
                f"kernel_{i}",
                lambda key, shape: jax.random.uniform(
                    key, shape, minval=-bound, maxval=bound
                ),
                (self.num_mlp, fan_in, fan_out),
            )
            bias = self.param(
                f"bias_{i}",
                lambda key, shape: jax.random.uniform(
                    key, shape, minval=-bound, maxval=bound
                ),
                (self.num_mlp, fan_out),
            )
            if self.num_mlp == 1:
                h = h @ kernel[0] + bias[0]
            else:
                h = jnp.einsum("nf,nfo->no", h, kernel[sel]) + bias[sel]
            if i < n_layers - 1:
                h = act(h)
        return h


class HydraBase(nn.Module):
    """Abstract multi-headed stack; subclasses provide ``get_conv``.

    ``get_conv(in_dim, out_dim, last_layer)`` must return a flax module with
    signature ``(x, pos, batch, train) -> (x, pos)`` (positions threaded for
    the E(3)-equivariant stacks, reference ``Base.py:289-302``).
    """

    input_dim: int = 1
    hidden_dim: int = 8
    output_dim: Tuple[int, ...] = ()
    output_type: Tuple[str, ...] = ()
    config_heads: Dict[str, Any] = None
    activation: str = "relu"
    loss_function_type: str = "mse"
    equivariance: bool = False
    loss_weights: Tuple[float, ...] = ()
    # Kendall-style uncertainty-weighted NLL multi-task loss
    # (``Architecture.ilossweights_nll``): every head emits one extra
    # log-variance channel; the loss learns per-sample task weighting. The
    # reference declares this mode but its implementation raises "not ready
    # yet" (``models/Base.py:335-354``) and the factory cannot reach it
    # (``create.py:71``) — here it is finished and config-reachable.
    loss_nll: bool = False
    num_conv_layers: int = 2
    num_nodes: Optional[int] = None
    edge_dim: Optional[int] = None
    conv_checkpointing: bool = False
    initial_bias: Optional[float] = None
    dropout: float = 0.25
    # Graph-partition parallelism (the long-context analog, SURVEY.md §5):
    # when set, the batch is ONE giant graph whose nodes/edges are sharded
    # over this mesh axis (see ``hydragnn_tpu/parallel/graph_partition``).
    # Convs see a halo-extended node table refreshed by all_to_all before
    # every layer; BatchNorm/pooling/loss psum over the axis so numerics
    # match the unpartitioned model exactly.
    partition_axis: Optional[str] = None

    # stacks whose convs read node positions (distances/angles/coordinate
    # updates) set this True; for the rest the partitioned halo exchange
    # skips the pos columns — pure ICI bandwidth savings
    conv_needs_pos: bool = False
    # stacks whose distances come from positions set this (not a field);
    # on periodic data their batches then carry each edge's image
    # (``models/create.py needs_edge_offsets``)
    reads_edge_offset = False

    @property
    def needs_edge_offsets(self) -> bool:
        """This stack's answer to ``models/create.py needs_edge_offsets``."""
        return self.reads_edge_offset and getattr(self, "periodic", False)

    @property
    def use_edge_attr(self) -> bool:
        return self.edge_dim is not None and self.edge_dim > 0

    @property
    def num_heads(self) -> int:
        return len(self.output_dim)

    # ---- subclass hooks ------------------------------------------------
    def get_conv(
        self,
        in_dim: int,
        out_dim: int,
        last_layer: bool = False,
        name: Optional[str] = None,
        **kw,
    ):
        raise NotImplementedError

    def _conv_layer_specs(self):
        """(in_dim, out_dim, bn_dim, conv_kwargs) per encoder layer.

        Default matches ``Base._init_conv`` (``Base.py:115-121``); GAT
        overrides for attention-head concat (``GATStack.py:36-47``).
        """
        specs = []
        for i in range(self.num_conv_layers):
            in_dim = self.input_dim if i == 0 else self.hidden_dim
            specs.append((in_dim, self.hidden_dim, self.hidden_dim, {}))
        return specs

    def _node_conv_specs(self, node_cfg, head_dim):
        """Layer specs for a conv-type node head (``Base.py:145-203``)."""
        dims = node_cfg["dim_headlayers"]
        num = node_cfg["num_headlayers"]
        specs = []
        prev = self.hidden_dim
        for il in range(num):
            specs.append((prev, dims[il], dims[il], {"last_layer": False}))
            prev = dims[il]
        specs.append((prev, head_dim, head_dim, {"last_layer": True}))
        return specs

    def _node_index_in_graph(self, batch: GraphBatch):
        if batch.extras is not None and "node_index_in_graph" in batch.extras:
            # partitioned giant graph: global position precomputed host-side
            return batch.extras["node_index_in_graph"]
        starts = jnp.cumsum(batch.n_node) - batch.n_node
        return jnp.arange(batch.num_nodes, dtype=jnp.int32) - starts[batch.node_graph]

    def _conv_cls(self, cls):
        """Wrap a conv class in ``nn.remat`` when conv checkpointing is on
        (parity with ``torch.utils.checkpoint`` at ``Base.py:296-301``).
        Subclasses must construct their conv through this hook."""
        if self.conv_checkpointing:
            return nn.remat(cls, static_argnums=(4,), prevent_cse=False)
        return cls

    def _apply_conv(self, conv, x, pos, batch, train):
        if self.partition_axis is None:
            return conv(x, pos, batch, train)
        # Partitioned message passing: refresh the halo (remote-sender rows)
        # from their owner shards via all_to_all, run the conv on the
        # extended table, keep the local rows. The analog of exchanging KV
        # blocks in ring attention — features ride ICI, compute stays local.
        from hydragnn_tpu.parallel.graph_partition import halo_extend

        send_idx = batch.extras["halo_send"]
        nl = x.shape[0]
        if self.conv_needs_pos:
            # ONE all_to_all for features+positions (small collectives are
            # latency-bound on ICI; fuse, then split)
            both = halo_extend(
                jnp.concatenate([x, pos], axis=-1), send_idx, self.partition_axis
            )
            xe, pe = both[:, : x.shape[1]], both[:, x.shape[1] :]
        else:
            # convs of this stack never read pos: don't ship it. Pass None
            # so a future pos-reading conv that forgot conv_needs_pos=True
            # fails loudly at trace time instead of silently gathering
            # clamped out-of-range rows.
            xe = halo_extend(x, send_idx, self.partition_axis)
            pe = None
        # convs that build per-node virtual edges (GAT self-loops) consult
        # node_mask at the extended size; halo rows are masked off since
        # their aggregations happen on the owner shard.
        ext = xe.shape[0] - nl
        batch_ext = batch.replace(
            node_mask=jnp.concatenate(
                [batch.node_mask, jnp.zeros((ext,), dtype=batch.node_mask.dtype)]
            )
        )
        c, p = conv(xe, pe, batch_ext, train)
        c = c[:nl]
        if p is not None and p.shape[0] != nl:
            p = p[:nl]
        return c, p

    def _embed(self, x):
        """Input features -> what the first conv reads. Default: as they
        are (SchNet's interaction block embeds them first)."""
        return x

    def _prepare_batch(self, batch: GraphBatch) -> GraphBatch:
        """Once-per-forward hook for values every conv layer would
        otherwise recompute identically (parameter-free functions of the
        batch — e.g. DimeNet's triplet angles and spherical basis, shared
        by all ``num_conv_layers`` interaction blocks). Default: no-op."""
        return batch

    @nn.compact
    def __call__(self, batch: GraphBatch, train: bool = False):
        act = get_activation(self.activation)
        heads_cfg = self.config_heads or {}
        batch = self._prepare_batch(batch)
        from hydragnn_tpu.ops.agg_policy import emit_layout_choice

        emit_layout_choice(self, batch)
        x = self._embed(batch.x)
        pos = batch.pos

        # ---- encoder: conv stack (Base.py:289-302) ----------------------
        # SchNet/EGNN use Identity feature layers instead of BatchNorm
        # (SCFStack.py:63, EGCLStack.py:41)
        use_bn = getattr(self, "conv_use_batchnorm", True)
        # SchNet's interaction block is residual and ends in its own
        # atom-wise layer: no activation between its blocks
        use_act = getattr(self, "conv_activation", True)
        for i, (in_dim, out_dim, bn_dim, kw) in enumerate(self._conv_layer_specs()):
            conv = self.get_conv(in_dim, out_dim, name=f"encoder_conv_{i}", **kw)
            c, pos = self._apply_conv(conv, x, pos, batch, train)
            if use_bn:
                c = MaskedBatchNorm(
                    bn_dim, name=f"encoder_bn_{i}", axis_name=self.partition_axis
                )(c, batch.node_mask, not train)
            x = act(c) if use_act else c

        # ---- decoder: multihead (Base.py:205-283,304-327) ---------------
        with jax.named_scope("heads"):  # the name the device trace reads
            x_graph = global_mean_pool(x, batch.node_graph, batch.n_node, batch.num_graphs)
            if self.partition_axis is not None:
                # nodes of the (single partitioned) graph live on every shard;
                # n_node[0] holds the GLOBAL real-node count, so the psum of the
                # local sums/count yields the exact global mean.
                x_graph = jax.lax.psum(x_graph, self.partition_axis)

            graph_shared = None
            if "graph" in heads_cfg:
                dim_shared = heads_cfg["graph"]["dim_sharedlayers"]
                n_shared = heads_cfg["graph"]["num_sharedlayers"]
                graph_shared = MLP(
                    [dim_shared] * n_shared,
                    activation=self.activation,
                    final_activation=True,
                    name="graph_shared",
                )

            outputs = []
            node_index = None
            # NLL mode: one extra log-variance channel per head (the reference
            # reserves the slot the same way, ``Base.py:241``)
            uq_extra = 1 if self.loss_nll else 0
            for ihead in range(self.num_heads):
                head_type = self.output_type[ihead]
                head_dim = self.output_dim[ihead] + uq_extra
                if head_type == "graph":
                    num_head_hidden = heads_cfg["graph"]["num_headlayers"]
                    dim_head_hidden = heads_cfg["graph"]["dim_headlayers"]
                    layer_dims = list(dim_head_hidden[:num_head_hidden]) + [head_dim]
                    head_mlp = MLP(
                        layer_dims,
                        activation=self.activation,
                        final_bias_value=self.initial_bias,
                        name=f"head_{ihead}_graph",
                    )
                    outputs.append(head_mlp(graph_shared(x_graph)))
                elif head_type == "node":
                    node_cfg = heads_cfg["node"]
                    node_type = node_cfg["type"]
                    hidden_dims = tuple(node_cfg["dim_headlayers"])
                    if node_type in ("mlp", "mlp_per_node"):
                        num_mlp = 1 if node_type == "mlp" else int(self.num_nodes)
                        if node_index is None:
                            node_index = self._node_index_in_graph(batch)
                        head = MLPNode(
                            input_dim=self.hidden_dim,
                            output_dim=head_dim,
                            num_mlp=num_mlp,
                            hidden_dims=hidden_dims,
                            activation=self.activation,
                            name=f"head_{ihead}_node",
                        )
                        out = head(x, node_index)
                        outputs.append(jnp.where(batch.node_mask[:, None], out, 0.0))
                    elif node_type == "conv":
                        # shared hidden convs + per-head output conv, BatchNorm +
                        # activation after every conv incl. the output one
                        # (Base.py:318-323).
                        h = x
                        p = pos
                        for il, (in_dim, od, bn_dim, kw) in enumerate(
                            self._node_conv_specs(node_cfg, head_dim)
                        ):
                            conv = self.get_conv(
                                in_dim, od, name=f"head_{ihead}_conv_{il}", **kw
                            )
                            c, p = self._apply_conv(conv, h, p, batch, train)
                            c = MaskedBatchNorm(
                                bn_dim,
                                name=f"head_{ihead}_bn_{il}",
                                axis_name=self.partition_axis,
                            )(c, batch.node_mask, not train)
                            h = act(c)
                        outputs.append(h)
                    else:
                        raise ValueError(
                            f"Unknown head NN structure for node features: {node_type};"
                            " supported: 'mlp', 'mlp_per_node', 'conv'"
                        )
                else:
                    raise ValueError(f"Unknown head type: {head_type}")
        return tuple(outputs)

    # ---- loss (Base.py:329-373) -----------------------------------------
    @jax.named_scope("loss")
    def loss(self, outputs, batch: GraphBatch):
        """Weighted multi-task loss; returns (total, per-task list).

        ``loss_weights`` are already normalized by their abs-sum at model
        construction (``Base.py:89-90``).
        """
        tot = 0.0
        tasks = []
        for ihead in range(self.num_heads):
            pred = outputs[ihead]
            target = batch.targets[ihead]
            mask = (
                batch.graph_mask
                if self.output_type[ihead] == "graph"
                else batch.node_mask
            )
            if self.loss_nll:
                d = self.output_dim[ihead]
                tot = tot + masked_gaussian_nll(
                    pred[..., :d],
                    pred[..., d:],
                    target,
                    mask,
                    axis_name=self.partition_axis,
                )
                # per-task report stays plain MSE of the mean prediction
                # (the reference's tasks_mseloss, ``Base.py:352``)
                tasks.append(
                    masked_error(
                        pred[..., :d],
                        target,
                        mask,
                        "mse",
                        axis_name=self.partition_axis,
                    )
                )
                continue
            err = masked_error(
                pred,
                target,
                mask,
                self.loss_function_type,
                axis_name=self.partition_axis,
            )
            tasks.append(err)
            tot = tot + self.loss_weights[ihead] * err
        return tot, tasks
