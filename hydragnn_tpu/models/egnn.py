"""EGNN stack — E(n)-equivariant graph convolution.

Parity with reference ``hydragnn/models/EGCLStack.py:21-245`` (custom E_GCL):
edge MLP on [h_row, h_col, ||dx||^2, e_ij] (2x Linear+ReLU), node MLP on
[h, aggregated messages], tanh-bounded equivariant coordinate update with
xavier(gain=1e-3) final layer, message aggregation at the SENDER index
(``:194,210`` — `row` = edge_index[0]), Identity feature layers (no encoder
BatchNorm, ``:36-46``), coord update gated off on the last layer.

TPU-first deviation: the first edge-MLP Linear is algebraically split into
node-axis projections (see the fusion comment in :class:`E_GCL`) — same
parameters, same math, degree-fold less edge-axis MXU work and no
``[E, 2D+1+edge]`` concat intermediate in HBM.
"""

import jax
import jax.numpy as jnp
from flax import linen as nn

from hydragnn_tpu.graph import segment_sum
from hydragnn_tpu.models.base import HydraBase
from hydragnn_tpu.models.common import SplitLinear, TorchLinear


def _safe_sqrt(x):
    """sqrt with a finite gradient at 0. Degenerate zero-distance pairs
    (padding edges; dense-layout fill slots) sit exactly at radial=0, where
    sqrt's inf derivative turns a zero cotangent into NaN (0*inf) once pos
    is parameter-dependent (equivariant layers >= 2). Double-where keeps
    real-edge values and gradients bit-identical and kills the NaN."""
    nonzero = x > 0
    safe = jnp.where(nonzero, x, 1.0)
    return jnp.where(nonzero, jnp.sqrt(safe), 0.0)


# The dense frame's geometry, one [N, K] plane a component: the chip lays a
# [N, K, 3] array down with 3 of its 128 lanes in use, and every pass over
# it costs what a pass over the messages costs. jit-wrapped, so the seven
# layers trace (and differentiate) each body once.


@jax.jit
def _slot_geometry(pos_j, pos):
    """``(radial [N, K], the three planes of (pos_j - pos_i) / (|.| + 1))``
    of gathered sender positions ``pos_j [N, K, 3]``."""
    diff = [pos_j[..., c] - pos[:, None, c] for c in range(3)]
    radial = sum(d * d for d in diff)
    norm = _safe_sqrt(radial) + 1.0  # norm_diff=True
    return radial, tuple(d / norm for d in diff)


@jax.jit
def _slot_moves(coord_diff, cw, nmask):
    """``[N, K, 4]`` f32: a slot's bounded translation (three planes
    ``coord_diff`` times the weight ``cw [N, K]``) and its count, zero on
    padded slots: what the sender sum takes beside the messages."""
    planes = [
        jnp.where(nmask, jnp.clip(d * cw, -100.0, 100.0), 0.0)
        for d in coord_diff
    ]
    return jnp.stack(planes + [nmask.astype(planes[0].dtype)], axis=-1)


class E_GCL(nn.Module):
    in_dim: int
    out_dim: int
    hidden_dim: int
    edge_attr_dim: int
    equivariant: bool
    # graph-partition mode: aggregations at the SENDER index land partly on
    # halo rows (edges are owned by the receiver's shard) and must be folded
    # back onto their owner via all_to_all (halo_reduce).
    partition_axis: str = None

    def _sender_sum(self, data, row, n, batch):
        out = segment_sum(data, row, n)
        if self.partition_axis is not None:
            from hydragnn_tpu.parallel.graph_partition import halo_reduce

            out = halo_reduce(out, batch.extras["halo_send"], self.partition_axis)
        return out

    def _sender_sum_dense(self, data, exact, extras, batch):
        """Dense-frame sender aggregation (``ops/dense_agg.py``): messages
        ``data`` at their own dtype and, beside them, the f32 columns
        ``exact`` (None on the last layer) summed in f32; plus the
        partition halo fold, ONE for both."""
        from hydragnn_tpu.ops.dense_agg import sender_sums

        sums = sender_sums(data, extras, exact=exact)
        out, moved = (sums, None) if exact is None else sums
        if self.partition_axis is not None:
            from hydragnn_tpu.parallel.graph_partition import halo_reduce

            d = out.shape[-1]
            both = out if moved is None else jnp.concatenate([out, moved], -1)
            both = halo_reduce(both, batch.extras["halo_send"], self.partition_axis)
            out = both[:, :d].astype(out.dtype)
            moved = None if moved is None else both[:, d:]
        return out, moved

    @nn.compact
    def __call__(self, x, pos, batch, train: bool = False):
        n = x.shape[0]
        row, col = batch.senders, batch.receivers
        extras = batch.extras or {}
        dense = "nbr_idx" in extras
        in_dim = x.shape[-1]

        # ---- algebraic edge-MLP fusion (round-4 verdict item 2) ----
        # The first edge-MLP Linear acts on concat([x_row, x_col, radial,
        # e_ij]), so by linearity
        #   L0 = x_row @ Wr + (x_col @ Wc + b) + radial * w_rad + e @ We
        # with the two D x H projections computed ONCE per NODE (deg-fold
        # less MXU work than the edge-axis matmul) and only cheap adds /
        # a rank-1 radial term left on the edge axis. The [E, 2D+1+edge]
        # concat intermediate disappears entirely. Parameters stay
        # TorchLinear-compatible (SplitLinear shares names/shapes/init),
        # same PNA move as models/pna.py:53-74.
        fan_in = 2 * in_dim + 1 + self.edge_attr_dim
        pre = SplitLinear(
            features=self.hidden_dim, fan_in=fan_in, name="edge_mlp_0"
        )
        y_snd = pre.piece(x, 0)  # sender-side contribution [N, H]
        y_rcv = pre.piece(x, in_dim) + pre.bias  # receiver side + bias
        w_rad = pre.kernel[2 * in_dim]  # [H] radial row

        if dense:
            # dense scatter-free frame: per-edge values live as [N, K, *]
            # keyed by (receiver, slot); j = sender, i = receiver
            from hydragnn_tpu.ops.dense_agg import neighbor_rows

            nmask = extras["nbr_mask"]
            emask_nd = nmask[..., None]
            # ONE gather for projected features + positions. The messages
            # run at the width the precision policy gave x and the
            # parameters (bf16: the table is then bf16 and the gather a
            # block-local product, ops/local_gather.py); positions and
            # what is computed from them stay f32, bit for bit
            y_j, pos_j = neighbor_rows(y_snd, extras, exact=pos)
            radial, coord_diff = _slot_geometry(pos_j, pos)
            e = (
                y_j + y_rcv[:, None, :]
                + (radial[..., None] * w_rad).astype(y_j.dtype)
            )
            if self.edge_attr_dim > 0:
                # gather the NARROW raw edge_attr first, project after —
                # projecting first would gather [N, K, H] instead of
                # [N, K, edge_dim] and add a backward scatter
                e = e + pre.piece(
                    batch.edge_attr[extras["nbr_edge"]], 2 * in_dim + 1
                )
        else:
            emask_nd = batch.edge_mask[:, None]
            coord_diff = pos[row] - pos[col]
            radial = (coord_diff * coord_diff).sum(-1, keepdims=True)
            norm = _safe_sqrt(radial) + 1.0  # norm_diff=True
            coord_diff = coord_diff / norm
            e = y_snd[row] + y_rcv[col] + radial * w_rad
            if self.edge_attr_dim > 0:
                e = e + pre.piece(batch.edge_attr, 2 * in_dim + 1)
        e = jax.nn.relu(e)
        e = jax.nn.relu(TorchLinear(self.hidden_dim, name="edge_mlp_1")(e))
        e = jnp.where(emask_nd, e, 0.0)

        if self.equivariant:
            cw = jax.nn.relu(TorchLinear(self.hidden_dim, name="coord_mlp_0")(e))
            small = nn.initializers.variance_scaling(
                0.001 * 0.001 / 3.0, "fan_avg", "uniform"
            )
            w1 = self.param("coord_mlp_1", small, (self.hidden_dim, 1))
            # the translation's weight leaves the messages' width here and
            # stays f32, as everything the positions are computed from
            # (bf16 messages: an f32 product of bf16 operands)
            cw = jnp.dot(cw, w1, preferred_element_type=pos.dtype)
            cw = jnp.tanh(cw)  # tanh=True bounds the update
            # the coord update (trans + count) and the node-model message
            # aggregation all land at the SAME sender index — ONE packed
            # pass (and one halo_reduce) instead of two
            if dense:
                # the messages where they lie, at their own dtype; the
                # translations and the count beside them, f32
                moved = _slot_moves(coord_diff, cw[..., 0], nmask)
                agg, moved = self._sender_sum_dense(e, moved, extras, batch)
            else:
                trans = jnp.clip(coord_diff * cw, -100.0, 100.0)
                trans = jnp.where(emask_nd, trans, 0.0)
                moved = jnp.concatenate(
                    [trans, emask_nd.astype(trans.dtype)], -1
                )
                both = self._sender_sum(
                    jnp.concatenate([e, moved], -1), row, n, batch
                )
                agg, moved = both[:, : self.hidden_dim], both[:, self.hidden_dim :]
            pos = pos + moved[:, :3] / jnp.maximum(moved[:, 3], 1.0)[:, None]
        elif dense:
            agg, _ = self._sender_sum_dense(e, None, extras, batch)
        else:
            # node model: aggregate edge features at the sender index (row)
            agg = self._sender_sum(e, row, n, batch)
        h = jnp.concatenate([x, agg], axis=-1)
        h = jax.nn.relu(TorchLinear(self.hidden_dim, name="node_mlp_0")(h))
        h = TorchLinear(self.out_dim, name="node_mlp_1")(h)
        return h, pos


class EGCLStack(HydraBase):
    conv_needs_pos: bool = True
    conv_use_batchnorm: bool = False  # Identity feature layers (EGCLStack.py:41)

    def get_conv(self, in_dim, out_dim, last_layer=False, name=None, **kw):
        return self._conv_cls(E_GCL)(
            name=name,
            in_dim=in_dim,
            out_dim=out_dim,
            hidden_dim=self.hidden_dim,
            edge_attr_dim=self.edge_dim if self.edge_dim else 0,
            equivariant=self.equivariance and not last_layer,
            partition_axis=self.partition_axis,
        )

    def _conv_layer_specs(self):
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
