"""Shared building blocks for all model stacks.

Numerics are kept behaviorally equivalent to the reference's torch modules
(``hydragnn/models/Base.py``, ``hydragnn/utils/model.py:30-57``) — same
activations, same BatchNorm statistics (masked to real nodes), torch-style
uniform init so tiny CI-scale models land in the same loss basin — while the
implementation is pure functional JAX that XLA can fuse end to end.
"""

import math
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn

from hydragnn_tpu.graph import segment_sum

# torch.nn.Linear default init: U(-1/sqrt(fan_in), 1/sqrt(fan_in)) for both
# weight and bias (kaiming_uniform(a=sqrt(5))). variance_scaling(1/3, fan_in,
# uniform) reproduces the weight bound exactly.
torch_weight_init = nn.initializers.variance_scaling(1.0 / 3.0, "fan_in", "uniform")


def torch_bias_init(fan_in: int):
    """torch.nn.Linear's bias init: U(-1/sqrt(fan_in), 1/sqrt(fan_in)).
    One shared factory so TorchLinear and SplitLinear stay init-identical
    by construction (SplitLinear's checkpoint/seed parity depends on it)."""
    bound = 1.0 / jnp.sqrt(fan_in)
    return lambda key, shape: jax.random.uniform(
        key, shape, minval=-bound, maxval=bound
    )


class SplitLinear(nn.Module):
    """Parameter-compatible with ``TorchLinear(features)`` applied to a
    concatenated ``[..., fan_in]`` input, but exposing kernel SLICES so a
    caller can exploit linearity: ``concat([a, b]) @ W == a @ W[:da] +
    b @ W[da:]``. Same param names ("kernel"/"bias"), shapes and init as
    TorchLinear — checkpoints and seeded-init trajectories are unchanged;
    only the order of floating-point contractions differs."""

    features: int
    fan_in: int

    def setup(self):
        self.kernel = self.param(
            "kernel", torch_weight_init, (self.fan_in, self.features)
        )
        self.bias = self.param(
            "bias", torch_bias_init(self.fan_in), (self.features,)
        )

    def piece(self, x, start: int):
        """``x @ kernel[start : start + x.shape[-1]]`` — one concat
        segment's contribution (no bias; add :attr:`bias` once)."""
        return x @ self.kernel[start : start + x.shape[-1]]

    def __call__(self, x):
        return x @ self.kernel + self.bias


class TorchLinear(nn.Module):
    """Dense layer with torch.nn.Linear's default initialization."""

    features: int
    use_bias: bool = True

    @nn.compact
    def __call__(self, x):
        fan_in = x.shape[-1]
        kernel = self.param("kernel", torch_weight_init, (fan_in, self.features))
        y = x @ kernel
        if self.use_bias:
            bias = self.param(
                "bias", torch_bias_init(fan_in), (self.features,)
            )
            y = y + bias
        return y


def shifted_softplus(x):
    """``softplus(x) - ln 2`` (SchNet's ssp: 0 at 0)."""
    return jax.nn.softplus(x) - math.log(2.0)


def get_activation(name: str) -> Callable:
    """Activation selection (reference: ``utils/model.py:30-47``)."""
    table = {
        "relu": jax.nn.relu,
        "selu": jax.nn.selu,
        "prelu": lambda x: jnp.where(x >= 0, x, 0.25 * x),  # PReLU at init slope
        "elu": jax.nn.elu,
        "gelu": jax.nn.gelu,
        "tanh": jnp.tanh,
        "lrelu_01": lambda x: jax.nn.leaky_relu(x, 0.1),
        "lrelu_025": lambda x: jax.nn.leaky_relu(x, 0.25),
        "lrelu_05": lambda x: jax.nn.leaky_relu(x, 0.5),
        "sigmoid": jax.nn.sigmoid,
        "softplus": jax.nn.softplus,
        "ssp": shifted_softplus,
    }
    if name not in table:
        raise ValueError(f"Unknown activation function: {name}")
    return table[name]


def masked_error(pred, target, mask, kind: str = "mse", axis_name: Optional[str] = None):
    """Masked elementwise loss, mean over real rows x features.

    Matches ``loss_function_selection`` (``utils/model.py:49-57``) applied to
    unpadded tensors: padding rows contribute nothing to numerator or count.

    ``axis_name``: when the rows of ``pred`` are sharded over a mesh axis
    (graph-partition parallelism), numerator and count are ``psum``'d over it
    so the result is the exact global mean — same numerics as unsharded.
    """
    pred = pred.astype(jnp.float32)  # loss reductions always in f32
    target = target.astype(jnp.float32)
    m = mask.reshape(mask.shape + (1,) * (pred.ndim - 1)).astype(pred.dtype)
    # where (not multiply) so NaN/inf garbage in padded rows cannot leak in
    diff = jnp.where(m > 0, pred - target, 0.0)
    count = m.sum() * pred.shape[-1]
    if kind == "mse":
        numer = (diff * diff).sum()
    elif kind == "mae":
        numer = jnp.abs(diff).sum()
    elif kind == "rmse":
        numer = (diff * diff).sum()
    elif kind == "smooth_l1":
        a = jnp.abs(diff)
        val = jnp.where(a < 1.0, 0.5 * diff * diff, a - 0.5)
        numer = (val * m).sum()
    else:
        raise ValueError(f"Unknown loss function: {kind}")
    if axis_name is not None:
        numer = jax.lax.psum(numer, axis_name)
        count = jax.lax.psum(count, axis_name)
    count = jnp.maximum(count, 1.0)
    out = numer / count
    if kind == "rmse":
        # double-where: sqrt'(0) is inf, so a perfectly-fit batch (zero
        # masked error) would NaN the backward pass; forward-identical
        # (sqrt(0) = 0 either way)
        positive = out > 0.0
        safe = jnp.where(positive, out, 1.0)
        out = jnp.where(positive, jnp.sqrt(safe), 0.0)
    return out


def masked_gaussian_nll(
    mu, logvar, target, mask, axis_name: Optional[str] = None, eps: float = 1e-6
):
    """Masked Gaussian negative log-likelihood, mean over real rows.

    The Kendall/Gal/Cipolla multi-task uncertainty weighting the reference
    declares but never finished (``models/Base.py:335-354`` raises "not
    ready yet"; the factory cannot even reach it, ``create.py:71``): each
    head emits one extra channel interpreted as a per-sample log-variance
    ``s``; the loss ``0.5 * (exp(-s) * (mu - y)^2 + s)`` learns to
    down-weight tasks/samples it is uncertain about. Matches torch's
    ``GaussianNLLLoss(full=False)`` up to the 1/2 s-vs-log(var) convention.
    """
    mu = mu.astype(jnp.float32)
    target = target.astype(jnp.float32)
    logvar = logvar.astype(jnp.float32)
    m = mask.reshape(mask.shape + (1,) * (mu.ndim - 1)).astype(mu.dtype)
    diff = jnp.where(m > 0, mu - target, 0.0)
    # clamp the variance away from zero like torch's GaussianNLLLoss(eps)
    logvar = jnp.maximum(logvar, jnp.log(eps))
    val = 0.5 * (jnp.exp(-logvar) * diff * diff + logvar)
    numer = (jnp.where(m > 0, val, 0.0)).sum()
    count = m.sum() * mu.shape[-1]
    if axis_name is not None:
        numer = jax.lax.psum(numer, axis_name)
        count = jax.lax.psum(count, axis_name)
    return numer / jnp.maximum(count, 1.0)


class MaskedBatchNorm(nn.Module):
    """BatchNorm1d over real nodes only (padding excluded from statistics).

    Same statistics contract as torch's BatchNorm1d (eps=1e-5, momentum=0.1,
    biased var for normalization, unbiased var into the running estimate),
    used after every conv layer (reference ``models/Base.py:115-121,295-302``).
    Under a jitted data-parallel step the batch statistics are global across
    the mesh — i.e. SyncBatchNorm semantics (``utils/distributed.py:268-269``)
    by construction, deterministically.
    """

    features: int
    momentum: float = 0.1
    eps: float = 1e-5
    # set when node rows are sharded over a mesh axis (graph-partition
    # parallelism): statistics are psum'd so every shard normalizes with the
    # exact global mean/var — SyncBatchNorm semantics across partitions.
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, x, mask, use_running_average: bool):
        ra_mean = self.variable(
            "batch_stats", "mean", lambda: jnp.zeros((self.features,), jnp.float32)
        )
        ra_var = self.variable(
            "batch_stats", "var", lambda: jnp.ones((self.features,), jnp.float32)
        )
        scale = self.param("scale", nn.initializers.ones, (self.features,))
        bias = self.param("bias", nn.initializers.zeros, (self.features,))

        in_dtype = x.dtype
        x = x.astype(jnp.float32)  # statistics always in f32 (bf16 sums drift)
        if use_running_average:
            mean, var = ra_mean.value, ra_var.value
        elif self.axis_name is not None:
            # two-pass (centered) like the local branch: E[x^2]-E[x]^2 would
            # catastrophically cancel in float32 for large-mean features
            m = mask.astype(x.dtype)[:, None]
            count = m.sum()
            s = (x * m).sum(axis=0)
            count, s = jax.lax.psum((count, s), self.axis_name)
            count = jnp.maximum(count, 1.0)
            mean = s / count
            centered = (x - mean) * m
            var = (
                jax.lax.psum((centered * centered).sum(axis=0), self.axis_name)
                / count
            )
            if not self.is_initializing():
                unbiased = var * count / jnp.maximum(count - 1.0, 1.0)
                ra_mean.value = (
                    1.0 - self.momentum
                ) * ra_mean.value + self.momentum * mean
                ra_var.value = (
                    1.0 - self.momentum
                ) * ra_var.value + self.momentum * unbiased
        else:
            m = mask.astype(x.dtype)[:, None]
            count = jnp.maximum(m.sum(), 1.0)
            mean = (x * m).sum(axis=0) / count
            centered = (x - mean) * m
            var = (centered * centered).sum(axis=0) / count
            if not self.is_initializing():
                unbiased = var * count / jnp.maximum(count - 1.0, 1.0)
                ra_mean.value = (
                    1.0 - self.momentum
                ) * ra_mean.value + self.momentum * mean
                ra_var.value = (
                    1.0 - self.momentum
                ) * ra_var.value + self.momentum * unbiased
        y = (x - mean) * jax.lax.rsqrt(var + self.eps) * scale + bias
        return jnp.where(mask[:, None], y, 0.0).astype(in_dtype)


class MLP(nn.Module):
    """Sequence of TorchLinear layers with activation after each hidden layer.

    ``final_activation`` mirrors the reference's shared graph-head layers,
    which end in an activation (``models/Base.py:208-217``), vs per-head MLPs
    which end in a bare Linear (``:231-244``).
    """

    layer_dims: Sequence[int]
    activation: str = "relu"
    final_activation: bool = False
    final_bias_value: Optional[float] = None  # UQ initial_bias (Base.py:138-143)

    @nn.compact
    def __call__(self, x):
        act = get_activation(self.activation)
        n = len(self.layer_dims)
        for i, dim in enumerate(self.layer_dims):
            if i == n - 1 and self.final_bias_value is not None:
                fan_in = x.shape[-1]
                kernel = self.param(
                    f"final_kernel", torch_weight_init, (fan_in, dim)
                )
                bias = self.param(
                    "final_bias",
                    nn.initializers.constant(self.final_bias_value),
                    (dim,),
                )
                x = x @ kernel + bias
            else:
                x = TorchLinear(dim)(x)
            if i < n - 1 or self.final_activation:
                x = act(x)
        return x


def gather_segment_sum(x, senders, receivers, num_segments, edge_mask):
    """``segment_sum(where(mask, x[senders], 0), receivers)`` — the
    sum-aggregation conv primitive (GIN et al): f32 accumulation, result
    in ``x.dtype``."""
    msg = jnp.where(edge_mask[:, None], x[senders], 0.0)
    return segment_sum(msg, receivers, num_segments)


def gather_segment_mean(x, senders, receivers, num_segments, edge_mask):
    """Masked mean over real incoming edges (SAGE's aggregator): the sum
    over the real in-degree (f32), ``[S, D]``."""
    from hydragnn_tpu.graph import segment_count

    msg = jnp.where(edge_mask[:, None], x[senders], 0.0)
    total = segment_sum(msg, receivers, num_segments)
    deg = segment_count(
        receivers, num_segments, weights=edge_mask.astype(jnp.float32)
    )
    return total / jnp.maximum(deg, 1.0)[:, None]


def gather_weighted_segment_sum(h, w, senders, receivers, num_segments):
    """``segment_sum(h[senders] * w, receivers)`` (SchNet's CFConv
    aggregation; ``w`` pre-masked ``[E, F]``)."""
    return segment_sum(h[senders] * w, receivers, num_segments)


def global_mean_pool(x, node_graph, n_node, num_graphs: int):
    """Padding-aware per-graph mean of node features -> [G, F].

    Equivalent to PyG's ``global_mean_pool`` (``models/Base.py:306-309``); the
    padding graph's row is garbage-free because padded node rows are zero.
    """
    total = segment_sum(x, node_graph, num_graphs)
    denom = jnp.maximum(n_node.astype(x.dtype), 1.0)[:, None]
    return total / denom
