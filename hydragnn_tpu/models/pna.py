"""PNA stack — Principal Neighbourhood Aggregation.

Behavioral parity with the reference's PyG ``PNAConv`` usage
(``hydragnn/models/PNAStack.py:19-69``): aggregators [mean, min, max, std],
scalers [identity, amplification, attenuation, linear], degree statistics from
the dataset degree histogram, pre_layers=1, post_layers=1, towers=1,
divide_input=False, optional edge encoder.

TPU shape: messages are a gather + fused MLP over the edge axis; the four
aggregations are segment reductions over receivers; scalers are elementwise;
the post-MLP is one MXU matmul over the node axis. Padded edges carry zeroed
messages and the padded-degree clamp keeps the log-scalers finite.
"""

import math
from typing import Optional, Tuple

import jax.numpy as jnp
from flax import linen as nn

from hydragnn_tpu.graph import segment_minmax_fused, segment_moments_fused
from hydragnn_tpu.models.base import HydraBase
from hydragnn_tpu.models.common import SplitLinear, TorchLinear


def pna_degree_averages(deg_histogram) -> Tuple[float, float]:
    """(avg_log, avg_lin) degree statistics from a degree histogram, matching
    PyG's DegreeScalerAggregation init (histogram produced by the analog of
    ``preprocess/utils.py:177-234``)."""
    total = float(sum(deg_histogram))
    total = max(total, 1.0)
    avg_log = (
        sum(h * math.log(d + 1.0) for d, h in enumerate(deg_histogram)) / total
    )
    avg_lin = sum(h * float(d) for d, h in enumerate(deg_histogram)) / total
    return max(avg_log, 1e-12), max(avg_lin, 1e-12)


class PNAConv(nn.Module):
    in_dim: int
    out_dim: int
    avg_deg_log: float
    avg_deg_lin: float
    edge_dim: Optional[int] = None

    @nn.compact
    def __call__(self, x, pos, batch, train: bool = False):
        n = x.shape[0]
        extras = batch.extras or {}
        dense = "nbr_idx" in extras
        use_edge = self.edge_dim is not None and self.edge_dim > 0

        # ---- algebraic message-MLP fusion (round-3 verdict item 1) ----
        # pre_layers=1 means the message MLP is ONE Linear, so
        #   m[r, k] = concat([x_i, x_j, e]) @ W + b
        #           = (x_i @ Wi + b) + (x_j @ Wj + e @ We)
        #           =        yi[r]   +        z[edge]
        # with yi/yj computed by NODE-axis matmuls (K-fold less MXU work
        # than the edge-axis matmul) and z = yj[sender] (+ encoded edge).
        # The aggregators then commute with the per-receiver constant yi:
        # mean/min/max shift by yi, std is shift-invariant — so ALL FOUR
        # statistics reduce to reductions of z, and the [E, 2-3D] concat
        # plus the edge-axis matmul disappear entirely. Parameters stay
        # TorchLinear-compatible (SplitLinear shares names/shapes/init).
        fan_in = 2 * self.in_dim + (self.in_dim if use_edge else 0)
        pre = SplitLinear(
            features=self.in_dim, fan_in=fan_in, name="pre_nn"
        )
        yi = pre.piece(x, 0) + pre.bias  # [N, D]
        yj = pre.piece(x, self.in_dim)  # [N, D]
        ze = None  # [E, D] encoded-edge contribution, shared by both paths
        if use_edge:
            e = TorchLinear(self.in_dim, name="edge_encoder")(batch.edge_attr)
            ze = pre.piece(e, 2 * self.in_dim)

        if dense:
            # scatter-free path: fixed-width neighbor lists, aggregations
            # as masked K-axis reductions, backward via the reverse list.
            # (A fused banded Pallas variant of this gather+stats pass was
            # built and measured in rounds 3-4 — it lost to XLA's own
            # fusion at every scale and was deleted. The gather ALONE is a
            # block-local MXU product where the batch states locality:
            # ops/dense_agg.py gather_neighbors.)
            from hydragnn_tpu.ops.dense_agg import (
                dense_minmax,
                dense_moments,
                neighbor_rows,
            )

            nbr_mask = extras["nbr_mask"]
            z = neighbor_rows(yj, extras)  # [N, K, D]
            if ze is not None:
                z = z + ze[extras["nbr_edge"]]
            z = jnp.where(nbr_mask[..., None], z, 0.0)
            mean_z, std, deg, has = dense_moments(z, nbr_mask)
            mn_z, mx_z = dense_minmax(z, nbr_mask, has)
        else:
            z = yj[batch.senders]  # [E, D]
            if ze is not None:
                z = z + ze
            z = jnp.where(batch.edge_mask[:, None], z, 0.0)

            # mean/std/degree from ONE packed scatter over z (padded edges
            # target the padding node / carry zero weight, so real-node
            # stats are untouched)
            s, cnt, sq = segment_moments_fused(
                z, batch.receivers, n, weights=batch.edge_mask
            )
            has = cnt > 0
            deg = jnp.maximum(cnt, 1.0)
            mean_z = s / deg
            # PNA std numerics: sqrt(relu(E[z^2]-E[z]^2)+eps); identical
            # for m = yi + z because variance ignores the constant shift
            std = jnp.sqrt(
                jnp.maximum(sq / deg - mean_z * mean_z, 0.0) + 1e-5
            )
            # min+max from ONE packed scatter; reuses the non-empty mask
            mn_z, mx_z = segment_minmax_fused(z, batch.receivers, n, has=has)

        # shift the yi constant back in; empty receivers keep the segment
        # fill of 0 (reference scatter semantics)
        mean = jnp.where(has, yi + mean_z, 0.0)
        mn = jnp.where(has, yi + mn_z, 0.0)
        mx = jnp.where(has, yi + mx_z, 0.0)
        aggr = jnp.concatenate([mean, mn, mx, std], axis=-1)
        log_deg = jnp.log(deg + 1.0)
        scaled = jnp.concatenate(
            [
                aggr,  # identity
                aggr * (log_deg / self.avg_deg_log),  # amplification
                aggr * (self.avg_deg_log / log_deg),  # attenuation
                aggr * (deg / self.avg_deg_lin),  # linear
            ],
            axis=-1,
        )
        out = jnp.concatenate([x, scaled], axis=-1)
        # post_layers=1 -> single Linear, then the conv's final lin
        out = TorchLinear(self.out_dim, name="post_nn")(out)
        out = TorchLinear(self.out_dim, name="lin")(out)
        return out, pos


class PNAStack(HydraBase):
    """Reference factory hardcodes: 4 aggregators x 4 scalers + deg histogram
    (``models/PNAStack.py:28-51``, ``models/create.py:112-127``)."""

    deg: Tuple[int, ...] = ()

    def get_conv(self, in_dim, out_dim, last_layer=False, name=None, **kw):
        avg_log, avg_lin = pna_degree_averages(self.deg)
        cls = self._conv_cls(PNAConv)
        return cls(
            name=name,
            in_dim=in_dim,
            out_dim=out_dim,
            avg_deg_log=avg_log,
            avg_deg_lin=avg_lin,
            edge_dim=self.edge_dim if self.use_edge_attr else None,
        )
