"""SchNet stack (SCF) — continuous-filter convolutions.

Parity with reference ``hydragnn/models/SCFStack.py:32-223``: GaussianSmearing
distance basis, CFConv with cosine cutoff, ShiftedSoftplus filter MLP,
Identity feature layers (NO BatchNorm in the encoder, ``SCFStack.py:51-68``),
optional E(3)-equivariant position updates gated OFF on the last conv layer
(``:59-66``).

TPU design note: the reference recomputes the radius interaction graph from
positions every layer (``RadiusInteractionGraph``). Under XLA we keep the edge
TOPOLOGY static (host-side radius graph with the same cutoff) and recompute
edge WEIGHTS from the current positions each layer — identical when positions
are fixed, and a faithful approximation under the tiny (gain=1e-3) equivariant
position updates.
"""

import math
from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from hydragnn_tpu.graph import segment_mean, segment_sum
from hydragnn_tpu.models.base import HydraBase
from hydragnn_tpu.models.common import TorchLinear, gather_weighted_segment_sum


def shifted_softplus(x):
    return jax.nn.softplus(x) - math.log(2.0)


def _safe_sqrt(x):
    """sqrt with a finite gradient at 0 (double-where): degenerate
    zero-distance pairs (padding edges, dense-layout fill slots) otherwise
    turn a zero cotangent into NaN once pos is parameter-dependent."""
    nonzero = x > 0
    safe = jnp.where(nonzero, x, 1.0)
    return jnp.where(nonzero, jnp.sqrt(safe), 0.0)


class GaussianSmearing(nn.Module):
    start: float
    stop: float
    num_gaussians: int

    @nn.compact
    def __call__(self, dist):
        offset = jnp.linspace(self.start, self.stop, self.num_gaussians)
        coeff = -0.5 / (offset[1] - offset[0]) ** 2
        # rank-agnostic: [E] -> [E, G] and dense [N, K] -> [N, K, G]
        d = dist[..., None] - offset
        # coeff < 0 and d*d >= 0, so the clamp is forward-identical (and
        # gradient-identical: at the d=0 tie the inner chain-rule factor
        # 2*coeff*d is already 0) — it bounds the exp for the numerics
        # gate against a future dist that escapes the cutoff clamp
        return jnp.exp(jnp.minimum(coeff * d * d, 0.0))


class CFConv(nn.Module):
    in_dim: int
    out_dim: int
    num_filters: int
    num_gaussians: int
    cutoff: float
    equivariant: bool
    use_edge_attr: bool
    # graph-partition mode: the coord update aggregates at SENDERS — partials
    # on halo rows are folded back to their owner shard (see egnn.py).
    partition_axis: str = None

    @nn.compact
    def __call__(self, x, pos, batch, train: bool = False):
        n = x.shape[0]
        send, recv = batch.senders, batch.receivers
        extras = batch.extras or {}
        dense = "nbr_idx" in extras
        if dense:
            # dense scatter-free frame (ops/dense_agg.py): every per-edge
            # quantity lives as [N, K, *]; pos gathers go through the
            # custom-VJP gather so the equivariant backward stays
            # scatter-free too
            from hydragnn_tpu.ops.dense_agg import neighbor_rows

            nmask, rmask = extras["nbr_mask"], extras["rev_mask"]
            pos_j = neighbor_rows(pos, extras)
            pos_i = jnp.broadcast_to(pos[:, None, :], pos_j.shape)
            if self.use_edge_attr:
                edge_weight = jnp.linalg.norm(
                    batch.edge_attr[extras["nbr_edge"]], axis=-1
                )
            else:
                diff = pos_j - pos_i
                edge_weight = _safe_sqrt((diff * diff).sum(-1))
            emask = nmask
        elif self.use_edge_attr:
            # reference: edge_weight = edge_attr.norm(dim=-1) on the
            # normalized lengths (SCFStack.py:123-131)
            edge_weight = jnp.linalg.norm(batch.edge_attr, axis=-1)
        else:
            diff = pos[send] - pos[recv]
            edge_weight = _safe_sqrt((diff * diff).sum(-1))
        edge_attr = GaussianSmearing(0.0, self.cutoff, self.num_gaussians)(
            edge_weight
        )

        # filter network: Linear, ShiftedSoftplus, Linear; cosine cutoff
        w = TorchLinear(self.num_filters, name="filter_0")(edge_attr)
        w = shifted_softplus(w)
        w = TorchLinear(self.num_filters, name="filter_1")(w)
        cos_cut = 0.5 * (jnp.cos(edge_weight * math.pi / self.cutoff) + 1.0)
        w = w * cos_cut[..., None]
        if dense:
            w = jnp.where(emask[..., None], w, 0.0)
        else:
            w = jnp.where(batch.edge_mask[:, None], w, 0.0)

        glorot = nn.initializers.xavier_uniform()
        lin1 = self.param("lin1", glorot, (self.in_dim, self.num_filters))
        h = x @ lin1

        if self.equivariant:
            # coord update (SCFStack.py:173-181): aggregate at senders
            if dense:
                diff = pos_j - pos_i
            else:
                diff = pos[send] - pos[recv]
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
                cnt = rmask.sum(axis=1).astype(trans.dtype)
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

        if dense:
            from hydragnn_tpu.ops.dense_agg import dense_sum, neighbor_rows

            h_j = neighbor_rows(h, extras)
            aggr = dense_sum(h_j * w, nmask)
        else:
            # continuous-filter aggregation; w is already edge-masked above
            aggr = gather_weighted_segment_sum(h, w, send, recv, n)
        lin2 = self.param("lin2", glorot, (self.num_filters, self.out_dim))
        bias2 = self.param("bias2", nn.initializers.zeros, (self.out_dim,))
        out = aggr @ lin2 + bias2
        return out, pos


class SCFStack(HydraBase):
    conv_needs_pos: bool = True
    num_filters: int = 126
    num_gaussians: int = 50
    radius: float = 2.0
    conv_use_batchnorm: bool = False  # Identity feature layers (SCFStack.py:63)

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
            partition_axis=self.partition_axis,
        )

    def _conv_layer_specs(self):
        # same dims as Base, but the equivariance gate needs last_layer info
        specs = []
        for i in range(self.num_conv_layers):
            in_dim = self.input_dim if i == 0 else self.hidden_dim
            specs.append(
                (
                    in_dim,
                    self.hidden_dim,
                    self.hidden_dim,
                    {"last_layer": i == self.num_conv_layers - 1},
                )
            )
        return specs
