"""GAT stack — GATv2 attention (Brody, Alon, Yahav, arXiv:2105.14491).

Parity with reference ``hydragnn/models/GATStack.py:22-118`` (PyG GATv2Conv,
``share_weights=False``, ``add_self_loops=True``; per-layer concat schedule:
hidden layers concat heads, final layer averages them,
``GATStack.py:36-47``; one head count for every layer).

Three ``Architecture`` keys state what the reference hardcodes; each keeps
the reference's value where the config is silent (``models/create.py``):

- ``heads`` (6, reference ``create.py:150-152``): attention heads of every
  layer; ``hidden_dim`` is the width PER HEAD, so a hidden layer's node
  states, and the gathered edge tables, are ``heads x hidden_dim`` wide;
- ``negative_slope`` (0.05, same lines): the LeakyReLU inside the score;
- ``dropout`` (0.25, ``Base.py``'s default): dropout on the attention
  weights in training, fed from the step's ``rng``; 0.0 draws nothing.

TPU shape, two branches chosen by the batch's layout:

- edge list (no ``extras["nbr_idx"]``): self-loops are appended as a
  virtual edge block (static shapes) and the attention softmax is a masked
  segment softmax over receivers (``graph/segment.py``, one fused scatter
  for numerator and denominator);
- dense neighbour lists: ``x_l`` rows are gathered ONCE into an
  ``[N, K, heads x hidden_dim]`` table (``ops/dense_agg.py
  gather_neighbors``; at eight lane tiles it keeps XLA's gather) and the
  softmax is local over the K slots plus a self-loop slot held apart. The
  table and the weights it is contracted with are at the run's dtype;
  scores, the softmax and its denominator (``[N, K, heads]``, 1 / hidden_dim
  of the table) are f32 in every run.

A device trace names the four phases (``jax.named_scope``): ``gat_project``,
``gat_scores``, ``gat_softmax``, ``gat_aggregate``.
"""

from typing import Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from hydragnn_tpu.graph import segment_softmax_unnorm, segment_sum
from hydragnn_tpu.models.base import HydraBase


class GATv2Conv(nn.Module):
    in_dim: int
    out_dim: int
    heads: int
    negative_slope: float
    dropout: float
    concat: bool

    @nn.compact
    def __call__(self, x, pos, batch, train: bool = False):
        n = x.shape[0]
        h, c = self.heads, self.out_dim
        glorot = nn.initializers.xavier_uniform()
        w_l = self.param("w_l", glorot, (self.in_dim, h * c))
        b_l = self.param("b_l", nn.initializers.zeros, (h * c,))
        w_r = self.param("w_r", glorot, (self.in_dim, h * c))
        b_r = self.param("b_r", nn.initializers.zeros, (h * c,))
        att = self.param("att", glorot, (1, h, c))

        with jax.named_scope("gat_project"):
            x_l = (x @ w_l + b_l).reshape(n, h, c)
            x_r = (x @ w_r + b_r).reshape(n, h, c)

        extras = batch.extras or {}
        if "nbr_idx" in extras:
            # dense scatter-free path: attention softmax is LOCAL over the
            # K neighbor slots + 1 self-loop slot — no segment ops at all.
            # The [N, K, H*C] gathered messages are the HBM cost center at
            # GAT's concat widths (H*C = 1024 at 4 heads x 256, 1536 at
            # the default 6): they are materialized ONCE, at the run's
            # dtype, and every consumer reads them in place — no
            # [N, K+1, ...] concat copy (the self-loop slot is handled as
            # separate [N, H, C] terms), and the weighted-message sum
            # contracts the K axis instead of re-reading a broadcast
            # product. Scores, softmax and denominator are [N, K, H]
            # tensors, 1/C of the table: they are f32 in every run (a
            # no-op in an f32 one). The table itself is never upcast: an
            # f32 copy of it is what XLA would keep for the backward pass
            # (2.3 GB a layer at 46k rows x 12 x 1,024), so the sum with
            # x_r and the LeakyReLU stay at the table's dtype and only the
            # ``a`` contraction accumulates in f32.
            from hydragnn_tpu.ops.dense_agg import neighbor_rows

            f32 = jnp.float32
            nmask = extras["nbr_mask"]  # [N, K]
            xl_j = neighbor_rows(x_l.reshape(n, h * c), extras).reshape(
                n, -1, h, c
            )  # [N, K, H, C]
            k = xl_j.shape[1]
            with jax.named_scope("gat_scores"):
                alpha_n = (
                    jax.nn.leaky_relu(xl_j + x_r[:, None], self.negative_slope)
                    * att
                ).sum(axis=-1, dtype=f32)  # [N, K, H]
                alpha_s = (
                    jax.nn.leaky_relu(x_l + x_r, self.negative_slope) * att
                ).sum(axis=-1, dtype=f32)  # [N, H] self-loop
            with jax.named_scope("gat_softmax"):
                alpha_n = jnp.where(nmask[..., None], alpha_n, -1e9)
                alpha_s = jnp.where(batch.node_mask[:, None], alpha_s, -1e9)
                # fully-masked (padded) nodes: amax = -1e9 (finite by the
                # mask convention), exp(0)=1, then re-masked to 0 below
                amax = jnp.maximum(alpha_n.max(axis=1), alpha_s)[:, None]
                ex_n = jnp.where(
                    nmask[..., None], jnp.exp(alpha_n - amax), 0.0
                )
                ex_s = jnp.where(
                    batch.node_mask[:, None],
                    jnp.exp(alpha_s - amax[:, 0]),
                    0.0,
                )
                drop = nn.Dropout(rate=self.dropout, deterministic=not train)
                exd = drop(jnp.concatenate([ex_n, ex_s[:, None]], axis=1))
                den = ex_n.sum(axis=1) + ex_s  # [N, H]
            with jax.named_scope("gat_aggregate"):
                # weighted message sum as a K-axis contraction (XLA chooses
                # the layout; reads xl_j once instead of a broadcast-product
                # rematerialization), the weights at the table's dtype
                num = jnp.einsum(
                    "nkh,nkhc->nhc",
                    exd[:, :k].astype(xl_j.dtype),
                    xl_j,
                    preferred_element_type=f32,
                )
                num = num + exd[:, k][..., None] * x_l.astype(f32)
                out = (num / jnp.maximum(den[..., None], 1e-16)).astype(
                    x_l.dtype
                )
        else:
            # real edges + one self-loop per node (add_self_loops=True)
            loop = jnp.arange(n, dtype=batch.senders.dtype)
            send = jnp.concatenate([batch.senders, loop])
            recv = jnp.concatenate([batch.receivers, loop])
            emask = jnp.concatenate([batch.edge_mask, batch.node_mask])

            with jax.named_scope("gat_scores"):
                g = x_l[send] + x_r[recv]
                g = jax.nn.leaky_relu(g, self.negative_slope)
                alpha = (g * att).sum(axis=-1)  # [E+N, H]
            # fused attention: softmax numerator (weighted messages) and
            # denominator share ONE scatter pass instead of
            # softmax-normalize + aggregate (3 scatter passes -> 2).
            # Attention dropout applies to the numerator only — identical
            # to dropping normalized alphas, since the 1/(1-p) scaling
            # commutes with the division.
            with jax.named_scope("gat_softmax"):
                ex = segment_softmax_unnorm(alpha, recv, n, mask=emask)
                exd = nn.Dropout(
                    rate=self.dropout, deterministic=not train
                )(ex)
            with jax.named_scope("gat_aggregate"):
                packed = jnp.concatenate(
                    [x_l[send] * exd[..., None], ex[..., None]], axis=-1
                )  # [E+N, H, C+1]
                s = segment_sum(
                    packed.reshape(packed.shape[0], h * (c + 1)), recv, n
                ).reshape(n, h, c + 1)
                out = s[..., :c] / jnp.maximum(
                    s[..., -1:], 1e-16
                )  # [N, H, C]

        if self.concat:
            out = out.reshape(n, h * c)
            bias = self.param("bias", nn.initializers.zeros, (h * c,))
        else:
            out = out.mean(axis=1)
            bias = self.param("bias", nn.initializers.zeros, (c,))
        return out + bias, pos


class GATStack(HydraBase):
    heads: int = 6
    negative_slope: float = 0.05

    def _conv_layer_specs(self):
        # concat on all but the last conv layer (GATStack.py:36-47)
        specs = [
            (
                self.input_dim,
                self.hidden_dim,
                self.hidden_dim * self.heads,
                {"concat": True},
            )
        ]
        for _ in range(self.num_conv_layers - 2):
            specs.append(
                (
                    self.hidden_dim * self.heads,
                    self.hidden_dim,
                    self.hidden_dim * self.heads,
                    {"concat": True},
                )
            )
        specs.append(
            (
                self.hidden_dim * self.heads,
                self.hidden_dim,
                self.hidden_dim,
                {"concat": False},
            )
        )
        return specs

    def _node_conv_specs(self, node_cfg, head_dim):
        # concat on hidden node-head convs, average on the output conv
        # (GATStack.py:49-90)
        dims = node_cfg["dim_headlayers"]
        num = node_cfg["num_headlayers"]
        specs = [
            (
                self.hidden_dim,
                dims[0],
                dims[0] * self.heads,
                {"concat": True, "last_layer": False},
            )
        ]
        for il in range(num - 1):
            specs.append(
                (
                    dims[il] * self.heads,
                    dims[il + 1],
                    dims[il + 1] * self.heads,
                    {"concat": True, "last_layer": False},
                )
            )
        specs.append(
            (
                dims[-1] * self.heads,
                head_dim,
                head_dim,
                {"concat": False, "last_layer": True},
            )
        )
        return specs

    def get_conv(
        self,
        in_dim: int,
        out_dim: int,
        last_layer: bool = False,
        concat: bool = True,
        name=None,
        **kw,
    ):
        return self._conv_cls(GATv2Conv)(
            name=name,
            in_dim=in_dim,
            out_dim=out_dim,
            heads=self.heads,
            negative_slope=self.negative_slope,
            dropout=self.dropout,
            concat=concat,
        )
