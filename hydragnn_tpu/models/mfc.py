"""MFC stack — Molecular Fingerprint Convolution.

Parity with reference ``hydragnn/models/MFCStack.py:22-51`` (PyG MFConv):
degree-indexed weight tables, out_i = W_l[d_i](sum_{j->i} x_j) + W_r[d_i](x_i)
with d_i clamped at ``max_degree`` (= config max_neighbours,
``models/create.py``), W_r without bias.

TPU shape: instead of PyG's Python loop over degree buckets with boolean
indexing (dynamic shapes), the weight tables are stacked parameter banks
``[K+1, in, out]`` applied through a one-hot degree expansion — ONE MXU
matmul over the fused (degree-class, feature) axis. The obvious
alternative (gather ``w[deg]`` then batched einsum) materializes a per-
node [in, out] weight matrix — [N, 256, 256] = 1.5 GB at hidden 256 —
and ran HBM-bound at 65 ms/step (round-3 BENCH_EXTRA); the one-hot form
spends K x the minimal FLOPs but they are dense matmul FLOPs, which is
the winning trade on the MXU.
"""

from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from hydragnn_tpu.graph import segment_count, segment_sum
from hydragnn_tpu.models.base import HydraBase


class MFConv(nn.Module):
    in_dim: int
    out_dim: int
    max_degree: int
    # static dataset-wide max in-degree (config derivation); banks above
    # it can never be selected and are sliced out of the compute
    degree_bound: Optional[int] = None

    @nn.compact
    def __call__(self, x, pos, batch, train: bool = False):
        n = x.shape[0]
        k = self.max_degree + 1
        bound = 1.0 / jnp.sqrt(self.in_dim)

        def uniform(key, shape):
            return jax.random.uniform(key, shape, minval=-bound, maxval=bound)

        w_l = self.param("w_l", uniform, (k, self.in_dim, self.out_dim))
        b_l = self.param("b_l", uniform, (k, self.out_dim))
        w_r = self.param("w_r", uniform, (k, self.in_dim, self.out_dim))

        extras = batch.extras or {}
        if "nbr_idx" in extras:  # dense scatter-free path (ops/dense_agg.py)
            from hydragnn_tpu.ops.dense_agg import dense_sum, neighbor_rows

            nmask = extras["nbr_mask"]
            x_j = neighbor_rows(x, extras)
            h = dense_sum(x_j, nmask)
            deg = nmask.sum(axis=1).astype(jnp.float32)
        else:
            msg = x[batch.senders]
            msg = jnp.where(batch.edge_mask[:, None], msg, 0.0)
            h = segment_sum(msg, batch.receivers, n)
            deg = segment_count(
                batch.receivers, n, weights=batch.edge_mask.astype(jnp.float32)
            )
        # static in-degree bounds — dense-list width and/or the dataset-wide
        # max from config derivation — let the compute slice dead banks off
        # the one-hot matmul (the parameter bank keeps its reference shape
        # [K+1, ...]). deg is clamped to the sliced range too, so
        # out-of-contract data (degree above the derived bound at predict
        # time) uses the top retained bank instead of silently zeroing.
        k_used = k
        if self.degree_bound is not None:
            k_used = min(k_used, self.degree_bound + 1)
        if "nbr_idx" in extras:
            k_used = min(k_used, int(extras["nbr_idx"].shape[1]) + 1)
        deg = jnp.clip(deg.astype(jnp.int32), 0, k_used - 1)
        # out_n = h_n @ w_l[deg_n] + x_n @ w_r[deg_n] + b_l[deg_n], with the
        # degree selection as a one-hot expansion: rows of the expanded
        # [N, 2*K*F] operand are zero outside the node's class block, so
        # one dense matmul applies every bank (zeros are exact — numerics
        # match the gathered-bank form)
        onehot = jax.nn.one_hot(deg, k_used, dtype=h.dtype)
        expanded = jnp.concatenate(
            [
                (onehot[:, :, None] * h[:, None, :]).reshape(n, -1),
                (onehot[:, :, None] * x[:, None, :]).reshape(n, -1),
            ],
            axis=1,
        )
        w = jnp.concatenate(
            [
                w_l[:k_used].reshape(k_used * self.in_dim, self.out_dim),
                w_r[:k_used].reshape(k_used * self.in_dim, self.out_dim),
            ],
            axis=0,
        )
        out = expanded @ w + b_l[deg]
        return out, pos


class MFCStack(HydraBase):
    max_degree: int = 10
    degree_bound: Optional[int] = None

    def get_conv(self, in_dim, out_dim, last_layer=False, name=None, **kw):
        return self._conv_cls(MFConv)(
            in_dim=in_dim,
            out_dim=out_dim,
            max_degree=self.max_degree,
            degree_bound=self.degree_bound,
            name=name,
        )
