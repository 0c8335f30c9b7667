"""CGCNN stack — Crystal Graph Convolutional Neural Network.

Parity with reference ``hydragnn/models/CGCNNStack.py:20-91`` (PyG CGConv,
aggr="add", batch_norm=False): z_ij = [x_i, x_j, e_ij];
out_i = x_i + sum_j sigmoid(W_f z + b_f) * softplus(W_s z + b_s).
Constant width: hidden_dim == input_dim (the factory passes input_dim as
hidden, ``CGCNNStack.py:30-40``), and conv-type node heads are forbidden
(``:66-89`` — enforced in our factory).
"""

import jax
import jax.numpy as jnp
from flax import linen as nn

from hydragnn_tpu.graph import segment_sum
from hydragnn_tpu.models.base import HydraBase
from hydragnn_tpu.models.common import TorchLinear


class CGConv(nn.Module):
    channels: int
    edge_dim: int = 0

    @nn.compact
    def __call__(self, x, pos, batch, train: bool = False):
        extras = batch.extras or {}
        dense = "nbr_idx" in extras
        if dense:  # dense scatter-free path (ops/dense_agg.py)
            from hydragnn_tpu.ops.dense_agg import dense_sum, neighbor_rows

            x_j = neighbor_rows(x, extras)
            parts = [jnp.broadcast_to(x[:, None, :], x_j.shape), x_j]
            if self.edge_dim and self.edge_dim > 0:
                parts.append(batch.edge_attr[extras["nbr_edge"]])
        else:
            parts = [x[batch.receivers], x[batch.senders]]
            if self.edge_dim and self.edge_dim > 0:
                parts.append(batch.edge_attr)
        z = jnp.concatenate(parts, axis=-1)
        gate = jax.nn.sigmoid(TorchLinear(self.channels, name="lin_f")(z))
        core = jax.nn.softplus(TorchLinear(self.channels, name="lin_s")(z))
        msg = gate * core
        if dense:
            out = x + dense_sum(msg, extras["nbr_mask"])
        else:
            msg = jnp.where(batch.edge_mask[:, None], msg, 0.0)
            out = x + segment_sum(msg, batch.receivers, x.shape[0])
        return out, pos


class CGCNNStack(HydraBase):
    def get_conv(self, in_dim, out_dim, last_layer=False, name=None, **kw):
        # CGConv keeps dimensions: in_dim is both in and out.
        return self._conv_cls(CGConv)(
            channels=in_dim, edge_dim=self.edge_dim if self.edge_dim else 0,
            name=name,
        )
