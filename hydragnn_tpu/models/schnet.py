"""SchNet stack (SCF) — continuous-filter convolutions, in two forms.

HydraGNN's form (the default; parity with ``hydragnn/models/SCFStack.py:
32-223``): GaussianSmearing distance basis, CFConv with cosine cutoff,
ShiftedSoftplus filter MLP, Identity feature layers (NO BatchNorm in the
encoder, ``SCFStack.py:51-68``) and Base's activation after every conv,
optional E(3)-equivariant position updates gated OFF on the last conv layer
(``:59-66``).

SchNet's own interaction block (``Architecture.interaction_block: true``;
Schuett et al., arXiv:1706.08566, as PyTorch Geometric's ``SchNet`` /
``InteractionBlock`` / ``CFConv`` compute it): a bias-free embedding
``h = W_e x``, then per interaction ``h <- h + W_3 ssp(W_2 m + b_2) + b_3``
with ``m_i = sum_j (W_1 h_j) * W_ij``, and no activation between the
blocks. Both forms share the continuous filter ``W_ij = (W_f2 ssp(W_f1
e(d_ij) + b_f1) + b_f2) * C(d_ij)``.

Distances are the TRUE periodic ones, ``d_ij = |p_j + o_ij - p_i|`` with
``o_ij`` the edge's image offset (``extras["edge_offset"]``, which every
layout builder carries where ``models/create.py needs_edge_offsets`` says
so, and without which a periodic stack refuses the batch), on both
aggregation families. Where positions do not move
within a forward (no equivariant update, no partition halo), the distances,
the Gaussian expansion and the envelope are computed ONCE a forward
(:meth:`SCFStack._prepare_batch`), in f32, and the expansion is handed to
the filter network rounded to the run's compute dtype, so that in a bf16
run its first product runs in bf16.

TPU design note: the reference recomputes the radius interaction graph from
positions every layer (``RadiusInteractionGraph``). Under XLA we keep the edge
TOPOLOGY static (host-side radius graph with the same cutoff) and recompute
edge WEIGHTS from the current positions each layer — identical when positions
are fixed, and a faithful approximation under the tiny (gain=1e-3) equivariant
position updates.
"""

import math

import jax
import jax.numpy as jnp
from flax import linen as nn

from hydragnn_tpu.graph import segment_sum
from hydragnn_tpu.models.base import HydraBase
from hydragnn_tpu.models.common import (
    TorchLinear,
    gather_weighted_segment_sum,
    shifted_softplus,
)


def _safe_sqrt(x):
    """sqrt with a finite gradient at 0 (double-where): degenerate
    zero-distance pairs (padding edges, dense-layout fill slots) otherwise
    turn a zero cotangent into NaN once pos is parameter-dependent."""
    nonzero = x > 0
    safe = jnp.where(nonzero, x, 1.0)
    return jnp.where(nonzero, jnp.sqrt(safe), 0.0)


def gaussian_smearing(dist, start, stop, num_gaussians):
    """``exp(-gamma (d - mu_k)^2)`` over ``num_gaussians`` centres evenly
    from ``start`` to ``stop``, ``gamma = 0.5 / spacing^2``;
    rank-agnostic: [E] -> [E, G] and dense [N, K] -> [N, K, G]."""
    offset = jnp.linspace(start, stop, num_gaussians)
    coeff = -0.5 / (offset[1] - offset[0]) ** 2
    d = dist[..., None] - offset
    # coeff < 0 and d*d >= 0, so the clamp is forward-identical (and
    # gradient-identical: at the d=0 tie the inner chain-rule factor
    # 2*coeff*d is already 0) — it bounds the exp for the numerics
    # gate against a future dist that escapes the cutoff clamp
    return jnp.exp(jnp.minimum(coeff * d * d, 0.0))


def cosine_cutoff(dist, cutoff):
    return 0.5 * (jnp.cos(dist * math.pi / cutoff) + 1.0)


def edge_vectors(pos, batch, periodic=False):
    """``p_j + o_ij - p_i`` of every edge (``[E, 3]``) or dense slot
    (``[N, K, 3]``), f32, and the slot mask of the dense frame (None on the
    edge list). The dense frame gathers positions through the custom-VJP
    neighbour gather, so an equivariant backward stays scatter-free.
    ``periodic``: the data has images, so a batch without offsets would
    give in-cell differences, up to a cell's width on an image edge."""
    extras = batch.extras or {}
    offset = extras.get("edge_offset")
    if periodic and offset is None:
        raise ValueError(
            "periodic SchNet batch without extras['edge_offset']: build its "
            "layout with need_offsets=models.create.needs_edge_offsets(arch)"
        )
    if "nbr_idx" in extras:
        from hydragnn_tpu.ops.dense_agg import neighbor_rows

        diff = neighbor_rows(pos, extras) - pos[:, None, :]
        if offset is not None:
            diff = diff + offset[extras["nbr_edge"]]
        return diff, extras["nbr_mask"]
    diff = pos[batch.senders] - pos[batch.receivers]
    if offset is not None:
        diff = diff + offset
    return diff, None


def edge_filter_inputs(pos, batch, cutoff, num_gaussians, dtype,
                       use_edge_attr=False, periodic=False):
    """(Gaussian expansion rounded to ``dtype``, cosine envelope) of every
    edge or slot, from the distance in f32: what the filter network reads.
    ``use_edge_attr``: the distance is the norm of ``edge_attr``, HydraGNN's
    normalised lengths (``SCFStack.py:123-131``)."""
    if use_edge_attr:
        attr = batch.edge_attr
        if "nbr_idx" in (batch.extras or {}):
            attr = attr[batch.extras["nbr_edge"]]
        dist = jnp.linalg.norm(attr, axis=-1)
    else:
        diff, _ = edge_vectors(pos, batch, periodic)
        dist = _safe_sqrt((diff * diff).sum(-1))
    rbf = gaussian_smearing(dist, 0.0, cutoff, num_gaussians)
    return rbf.astype(dtype), cosine_cutoff(dist, cutoff)


class CFConv(nn.Module):
    in_dim: int
    out_dim: int
    num_filters: int
    num_gaussians: int
    cutoff: float
    equivariant: bool
    use_edge_attr: bool
    # SchNet's interaction block around the continuous filter: the
    # atom-wise ssp -> W_3 and the residual (the module docstring)
    interaction: bool = False
    periodic: bool = False  # edge_vectors: offsets required
    # graph-partition mode: the coord update aggregates at SENDERS — partials
    # on halo rows are folded back to their owner shard (see egnn.py).
    partition_axis: str = None

    @nn.compact
    def __call__(self, x, pos, batch, train: bool = False):
        n = x.shape[0]
        send, recv = batch.senders, batch.receivers
        extras = batch.extras or {}
        dense = "nbr_idx" in extras
        with jax.named_scope("schnet_filter"):
            if "schnet_rbf" in extras:
                # hoisted once a forward (SCFStack._prepare_batch)
                rbf, cos_cut = extras["schnet_rbf"], extras["schnet_envelope"]
            else:
                rbf, cos_cut = edge_filter_inputs(
                    pos, batch, self.cutoff, self.num_gaussians, x.dtype,
                    self.use_edge_attr, self.periodic,
                )

            # filter network: Linear, ShiftedSoftplus, Linear; cosine cutoff
            w = TorchLinear(self.num_filters, name="filter_0")(rbf)
            w = shifted_softplus(w)
            w = TorchLinear(self.num_filters, name="filter_1")(w)
            w = w * cos_cut.astype(w.dtype)[..., None]
            if dense:
                w = jnp.where(extras["nbr_mask"][..., None], w, 0.0)
            else:
                w = jnp.where(batch.edge_mask[:, None], w, 0.0)

        glorot = nn.initializers.xavier_uniform()
        if self.equivariant:
            # coord update (SCFStack.py:173-181): aggregate at senders
            diff, nmask = edge_vectors(pos, batch, self.periodic)
            norm = _safe_sqrt((diff * diff).sum(-1, keepdims=True)) + 1.0
            coord_diff = diff / norm
            cw = TorchLinear(self.num_filters, name="coord_mlp_0")(w)
            cw = jax.nn.relu(cw)
            small = nn.initializers.variance_scaling(
                0.001 * 0.001 / 3.0, "fan_avg", "uniform"
            )
            cw = cw @ self.param("coord_mlp_1", small, (self.num_filters, 1))
            trans = jnp.clip(coord_diff * cw, -100.0, 100.0)
            if dense:
                # sender-side sum through the reverse lists (scatter-free);
                # per-sender count = real out-degree
                from hydragnn_tpu.ops.dense_agg import sender_sums

                trans = jnp.where(nmask[..., None], trans, 0.0)
                agg = sender_sums(trans, extras)
                cnt = extras["rev_mask"].sum(axis=1).astype(trans.dtype)
                if self.partition_axis is not None:
                    from hydragnn_tpu.parallel.graph_partition import (
                        halo_reduce,
                    )

                    both = halo_reduce(
                        jnp.concatenate([agg, cnt[:, None]], -1),
                        batch.extras["halo_send"],
                        self.partition_axis,
                    )
                    agg, cnt = both[:, :3], both[:, 3]
            else:
                trans = jnp.where(batch.edge_mask[:, None], trans, 0.0)
                # trans and the count share one segment pass + halo_reduce
                both = segment_sum(
                    jnp.concatenate(
                        [trans, batch.edge_mask.astype(trans.dtype)[:, None]],
                        -1,
                    ),
                    send,
                    n,
                )
                if self.partition_axis is not None:
                    from hydragnn_tpu.parallel.graph_partition import (
                        halo_reduce,
                    )

                    both = halo_reduce(
                        both, batch.extras["halo_send"], self.partition_axis
                    )
                agg, cnt = both[:, :3], both[:, 3]
            pos = pos + agg / jnp.maximum(cnt, 1.0)[:, None]

        with jax.named_scope("schnet_cfconv"):
            lin1 = self.param("lin1", glorot, (self.in_dim, self.num_filters))
            h = x @ lin1
            if dense:
                from hydragnn_tpu.ops.dense_agg import dense_sum, neighbor_rows

                h_j = neighbor_rows(h, extras)
                aggr = dense_sum(h_j * w, extras["nbr_mask"])
            else:
                # continuous-filter aggregation; w is already edge-masked
                aggr = gather_weighted_segment_sum(h, w, send, recv, n)
            lin2 = self.param("lin2", glorot, (self.num_filters, self.out_dim))
            bias2 = self.param("bias2", nn.initializers.zeros, (self.out_dim,))
            out = aggr @ lin2 + bias2
        if self.interaction:
            with jax.named_scope("schnet_atomwise"):
                lin3 = self.param("lin3", glorot, (self.out_dim, self.out_dim))
                bias3 = self.param(
                    "bias3", nn.initializers.zeros, (self.out_dim,)
                )
                out = x + shifted_softplus(out) @ lin3 + bias3
        return out, pos


class SCFStack(HydraBase):
    conv_needs_pos: bool = True
    # distances from positions: periodic batches carry each edge's image
    # (models/create.py needs_edge_offsets; not a field)
    reads_edge_offset = True
    periodic: bool = False
    num_filters: int = 126
    num_gaussians: int = 50
    radius: float = 2.0
    conv_use_batchnorm: bool = False  # Identity feature layers (SCFStack.py:63)
    # SchNet's own block (module docstring): embedding, residual
    # interactions, no activation between them
    interaction_block: bool = False

    @property
    def conv_activation(self) -> bool:
        return not self.interaction_block

    def _embed(self, x):
        if not self.interaction_block:
            return x
        if self.equivariance:
            raise ValueError(
                "SchNet's interaction block has no equivariant position "
                "update; set equivariance false or interaction_block false"
            )
        return TorchLinear(self.hidden_dim, use_bias=False, name="embedding")(x)

    def _prepare_batch(self, batch):
        """The geometry every conv reads, once a forward where positions do
        not move within it: the Gaussian expansion rounded to the compute
        dtype and the envelope, both from f32 distances."""
        if self.equivariance or self.partition_axis:
            return batch
        with jax.named_scope("schnet_filter"):
            rbf, envelope = edge_filter_inputs(
                batch.pos, batch, self.radius, self.num_gaussians,
                batch.x.dtype, self.use_edge_attr, self.periodic,
            )
        return batch.replace(extras=dict(
            batch.extras or {}, schnet_rbf=rbf, schnet_envelope=envelope
        ))

    def get_conv(self, in_dim, out_dim, last_layer=False, name=None, **kw):
        return self._conv_cls(CFConv)(
            name=name,
            in_dim=in_dim,
            out_dim=out_dim,
            num_filters=self.num_filters,
            num_gaussians=self.num_gaussians,
            cutoff=self.radius,
            equivariant=self.equivariance and not last_layer,
            use_edge_attr=self.use_edge_attr,
            interaction=self.interaction_block,
            periodic=self.periodic,
            partition_axis=self.partition_axis,
        )

    def _conv_layer_specs(self):
        # same dims as Base, but the equivariance gate needs last_layer info;
        # the interaction block runs hidden -> hidden after the embedding
        specs = []
        for i in range(self.num_conv_layers):
            in_dim = (
                self.input_dim
                if i == 0 and not self.interaction_block
                else self.hidden_dim
            )
            specs.append(
                (
                    in_dim,
                    self.hidden_dim,
                    self.hidden_dim,
                    {"last_layer": i == self.num_conv_layers - 1},
                )
            )
        return specs

    def _node_conv_specs(self, node_cfg, head_dim):
        if self.interaction_block:
            raise ValueError(
                "conv node heads are not defined for SchNet's interaction "
                "block (its residual needs in == out); use an mlp node head"
            )
        return super()._node_conv_specs(node_cfg, head_dim)
