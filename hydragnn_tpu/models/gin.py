"""GIN stack — Graph Isomorphism Network.

Parity with reference ``hydragnn/models/GINStack.py:21-47``: PyG GINConv with
an inner MLP [Linear(in,out), ReLU, Linear(out,out)], trainable eps
initialized at 100.0. Formula: out = MLP((1 + eps) * x_i + sum_{j->i} x_j).
"""

from flax import linen as nn

from hydragnn_tpu.models.base import HydraBase
from hydragnn_tpu.models.common import TorchLinear, gather_segment_sum


class GINConv(nn.Module):
    in_dim: int
    out_dim: int
    eps_init: float = 100.0

    @nn.compact
    def __call__(self, x, pos, batch, train: bool = False):
        eps = self.param("eps", nn.initializers.constant(self.eps_init), ())
        extras = batch.extras or {}
        if "nbr_idx" in extras:  # dense scatter-free path (ops/dense_agg.py)
            from hydragnn_tpu.ops.dense_agg import dense_sum, neighbor_rows

            x_j = neighbor_rows(x, extras)
            aggr = dense_sum(x_j, extras["nbr_mask"])
        else:
            aggr = gather_segment_sum(
                x, batch.senders, batch.receivers, x.shape[0],
                batch.edge_mask,
            )
        h = (1.0 + eps) * x + aggr
        h = TorchLinear(self.out_dim, name="mlp_0")(h)
        h = nn.relu(h)  # GINStack hardcodes ReLU inside the conv MLP
        h = TorchLinear(self.out_dim, name="mlp_1")(h)
        return h, pos


class GINStack(HydraBase):
    def get_conv(self, in_dim, out_dim, last_layer=False, name=None, **kw):
        return self._conv_cls(GINConv)(in_dim=in_dim, out_dim=out_dim, name=name)
