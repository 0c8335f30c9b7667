"""GraphSAGE stack.

Parity with reference ``hydragnn/models/SAGEStack.py:22-43`` (PyG SAGEConv
defaults): out = lin_l(mean_{j->i} x_j) + lin_r(x_i), lin_r without bias.
"""

import jax.numpy as jnp
from flax import linen as nn

from hydragnn_tpu.models.base import HydraBase
from hydragnn_tpu.models.common import TorchLinear, gather_segment_mean


class SAGEConv(nn.Module):
    in_dim: int
    out_dim: int

    @nn.compact
    def __call__(self, x, pos, batch, train: bool = False):
        extras = batch.extras or {}
        if "nbr_idx" in extras:  # dense scatter-free path (ops/dense_agg.py)
            from hydragnn_tpu.ops.dense_agg import dense_sum, neighbor_rows

            nmask = extras["nbr_mask"]
            x_j = neighbor_rows(x, extras)
            deg = nmask.sum(axis=1).astype(x.dtype)
            aggr = dense_sum(x_j, nmask) / jnp.maximum(deg, 1.0)[:, None]
        else:
            # mean over real incoming edges only (sum / real degree)
            aggr = gather_segment_mean(
                x, batch.senders, batch.receivers, x.shape[0],
                batch.edge_mask,
            )
        out = TorchLinear(self.out_dim, name="lin_l")(aggr) + TorchLinear(
            self.out_dim, use_bias=False, name="lin_r"
        )(x)
        return out, pos


class SAGEStack(HydraBase):
    def get_conv(self, in_dim, out_dim, last_layer=False, name=None, **kw):
        return self._conv_cls(SAGEConv)(in_dim=in_dim, out_dim=out_dim, name=name)
