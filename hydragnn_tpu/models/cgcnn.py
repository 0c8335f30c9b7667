"""CGCNN stack — Crystal Graph Convolutional Neural Network, in two forms.

HydraGNN's form (the default; parity with reference
``hydragnn/models/CGCNNStack.py:20-91``, PyG CGConv, aggr="add",
batch_norm=False): z_ij = [x_i, x_j, e_ij];
out_i = x_i + sum_j sigmoid(W_f z + b_f) * softplus(W_s z + b_s).
Constant width: hidden_dim == input_dim (the factory passes input_dim as
hidden, ``CGCNNStack.py:30-40``), and conv-type node heads are forbidden
(``:66-89`` — enforced in our factory).

The published block (``Architecture.cgcnn_conv: true``; Xie & Grossman,
PRL 120, 145301 (2018), as the Open Catalyst baselines' ``CGCNNConv``
computes it): a biased embedding ``h = W_e x + b_e``, then per conv::

    z_ij = BN_edge(W [h_i ; h_j ; g(d_ij)] + b)     (2 x hidden wide)
    (f, s) = split(z_ij)
    m_i  = sum_j sigmoid(f) * softplus(s)
    h_i <- softplus(LayerNorm(m_i) + h_i)

with no BatchNorm and no activation between the convs. ``BN_edge`` is a
BatchNorm over the batch's REAL edge messages (``MaskedBatchNorm`` over the
rows of the edge list or the slots of the dense lists, padding excluded);
``g`` is the Gaussian expansion of the TRUE periodic distance
``|p_j + o_ij - p_i|`` (SchNet's geometry, ``models/schnet.py``), computed
once a forward in f32 (:meth:`CGCNNStack._prepare_batch`).
"""

import jax
import jax.numpy as jnp
from flax import linen as nn

from hydragnn_tpu.graph import segment_sum
from hydragnn_tpu.models.base import HydraBase
from hydragnn_tpu.models.common import MaskedBatchNorm, TorchLinear
from hydragnn_tpu.models.schnet import _safe_sqrt, edge_vectors, gaussian_smearing


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


def edge_preactivation(x, rbf, kernel, bias, batch):
    """``W [x_i ; x_j ; g_ij] + b`` of every edge (``[E, 2C]``) or dense
    slot (``[N, K, 2C]``) as ``(W_i x + b)_i + W_jg [x_j ; g_ij]``: one
    node-level product for the receiver's part, the sender rows gathered at
    their own width C, and a ``(C + G) -> 2C`` product an edge. Read on the
    chip against the concatenated form (``(2C + G) -> 2C`` an edge) and the
    split form (``W_j x`` gathered as a 2C-wide table, ``G -> 2C`` an edge)
    at 512 wide, dense lists, bf16: 78.0 | 97.4 | 129.9 ms a step, the
    narrower gathered table worth more than the smaller product
    (``benchmarks/cgcnn_family_ab.py``, PERF.md section 4)."""
    c = x.shape[-1]
    own = x @ kernel[:c] + bias
    extras = batch.extras or {}
    if "nbr_idx" in extras:
        from hydragnn_tpu.ops.dense_agg import neighbor_rows

        own, x_j = own[:, None, :], neighbor_rows(x, extras)
    else:
        own, x_j = own[batch.receivers], x[batch.senders]
    return own + jnp.concatenate([x_j, rbf], -1) @ kernel[c:]


class CGCNNConv(nn.Module):
    """The published block (module docstring); reads the hoisted Gaussians
    ``extras["cgcnn_rbf"]``."""

    channels: int
    num_gaussians: int

    @nn.compact
    def __call__(self, x, pos, batch, train: bool = False):
        extras = batch.extras or {}
        dense = "nbr_idx" in extras
        wide = 2 * self.channels
        with jax.named_scope("cgcnn_edge"):
            kernel = self.param(
                "lin1", nn.initializers.xavier_uniform(),
                (wide + self.num_gaussians, wide),
            )
            bias = self.param("bias1", nn.initializers.zeros, (wide,))
            z = edge_preactivation(x, extras["cgcnn_rbf"], kernel, bias, batch)
        mask = extras["nbr_mask"] if dense else batch.edge_mask
        with jax.named_scope("cgcnn_edge_bn"):
            z = MaskedBatchNorm(wide, name="bn1")(
                z.reshape(-1, wide), mask.reshape(-1), not train
            ).reshape(z.shape)
        with jax.named_scope("cgcnn_gate"):
            f, s = jnp.split(z, 2, axis=-1)
            msg = jax.nn.sigmoid(f) * jax.nn.softplus(s)
        with jax.named_scope("cgcnn_node"):
            if dense:
                from hydragnn_tpu.ops.dense_agg import dense_sum

                m = dense_sum(msg, mask)
            else:
                msg = jnp.where(mask[:, None], msg, 0.0)
                m = segment_sum(msg, batch.receivers, x.shape[0])
            m = nn.LayerNorm(epsilon=1e-5, name="ln1")(m.astype(jnp.float32))
            out = jax.nn.softplus(m + x.astype(jnp.float32)).astype(x.dtype)
        return out, pos


class CGCNNStack(HydraBase):
    # the published block (module docstring); absent, HydraGNN's CGConv
    cgcnn_conv: bool = False
    num_gaussians: int = 100
    radius: float = 6.0
    periodic: bool = False

    @classmethod
    def reads_offsets(cls, arch: dict) -> bool:
        # only the published block computes distances from positions
        return bool(arch.get("cgcnn_conv"))

    @classmethod
    def conv_width_key(cls, arch: dict) -> str:
        # HydraGNN's CGConv keeps the input width (models/create.py sets
        # hidden_dim = input_dim); the published block runs at hidden_dim
        return "hidden_dim" if arch.get("cgcnn_conv") else "input_dim"

    @property
    def conv_use_batchnorm(self) -> bool:
        return not self.cgcnn_conv

    @property
    def conv_activation(self) -> bool:
        return not self.cgcnn_conv

    def _embed(self, x):
        if not self.cgcnn_conv:
            return x
        return TorchLinear(self.hidden_dim, name="embedding")(x)

    def _prepare_batch(self, batch):
        """The Gaussians every published conv reads, once a forward: from
        the f32 distance, rounded to the compute dtype."""
        if not self.cgcnn_conv:
            return batch
        with jax.named_scope("cgcnn_geometry"):
            diff, _ = edge_vectors(batch.pos, batch, self.periodic)
            dist = _safe_sqrt((diff * diff).sum(-1))
            rbf = gaussian_smearing(dist, 0.0, self.radius, self.num_gaussians)
        return batch.replace(extras=dict(
            batch.extras or {}, cgcnn_rbf=rbf.astype(batch.x.dtype)
        ))

    def _conv_layer_specs(self):
        if not self.cgcnn_conv:
            return super()._conv_layer_specs()
        h = self.hidden_dim
        return [(h, h, h, {})] * self.num_conv_layers

    def get_conv(self, in_dim, out_dim, last_layer=False, name=None, **kw):
        if self.cgcnn_conv:
            return self._conv_cls(CGCNNConv)(
                channels=in_dim, num_gaussians=self.num_gaussians, name=name
            )
        # CGConv keeps dimensions: in_dim is both in and out.
        return self._conv_cls(CGConv)(
            channels=in_dim, edge_dim=self.edge_dim if self.edge_dim else 0,
            name=name,
        )
