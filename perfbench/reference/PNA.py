"""Plain reference of the PNA multi-head model (Corso et al., Principal
Neighbourhood Aggregation, NeurIPS 2020), as PyG's ``PNAConv`` is used by
HydraGNN: aggregators mean/min/max/std, scalers identity / amplification /
attenuation / linear, one tower, one pre- and one post-layer, a final
linear; BatchNorm over the batch's real nodes and ReLU after each layer;
mean pooling; a graph head and a node head; weighted MSE.

Departures from the paper: none in the mathematics. The message
``W [x_i ; x_j] + b`` is written as ``(x W_i + b)[i] + (x W_j)[j]``.
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import common as C


def prepare(arch, train_degrees):
    """What the model needs from the training set: PNA's degree averages
    (mean of log(d + 1) and of d over every atom), from the in-degrees of
    the reference's own edges."""
    d = np.concatenate(train_degrees).astype(np.float64)
    return {
        "avg_log": float(np.log(d + 1.0).mean()),
        "avg_lin": float(d.mean()),
    }


def init_params(key, arch, input_dim, out_dims):
    hidden, depth = arch["hidden_dim"], arch["num_conv_layers"]
    keys = jax.random.split(key, depth + 1)
    layers = []
    for i in range(depth):
        f = input_dim if i == 0 else hidden
        k1, k2, k3 = jax.random.split(keys[i], 3)
        layers.append({
            "pre": C.linear_init(k1, 2 * f, f),
            "post": C.linear_init(k2, 17 * f, hidden),
            "lin": C.linear_init(k3, hidden, hidden),
            "bn": {"scale": jnp.ones((hidden,)), "bias": jnp.zeros((hidden,))},
        })
    out = {"layers": layers}
    out.update(C.init_heads(keys[-1], hidden, arch["output_heads"], out_dims))
    return out


def to_program(params):
    tree = C.to_program_heads(params)
    for i, l in enumerate(params["layers"]):
        tree[f"encoder_conv_{i}"] = {
            "pre_nn": dict(l["pre"]), "post_nn": dict(l["post"]),
            "lin": dict(l["lin"]),
        }
        tree[f"encoder_bn_{i}"] = dict(l["bn"])
    return tree


def _conv(layer, x, batch, stats, rounding):
    f = x.shape[-1]
    n = x.shape[0]
    send, recv, emask = batch["send"], batch["recv"], batch["edge_mask"]
    w = layer["pre"]["kernel"]
    at_i = C.dense(x, {"kernel": w[:f], "bias": layer["pre"]["bias"]}, rounding)
    at_j = C.dense(x, {"kernel": w[f:]}, rounding)
    m = at_i[recv] + at_j[send]  # [E, f] message j -> i
    em = emask[:, None]
    deg = jax.ops.segment_sum(emask.astype(jnp.float32), recv, n)
    has = (deg > 0)[:, None]
    d = jnp.maximum(deg, 1.0)[:, None]
    mean = jax.ops.segment_sum(jnp.where(em, m, 0.0), recv, n) / d
    sq = jax.ops.segment_sum(jnp.where(em, m * m, 0.0), recv, n) / d
    std = jnp.sqrt(jax.nn.relu(sq - mean * mean) + 1e-5)
    mn = jax.ops.segment_min(jnp.where(em, m, jnp.inf), recv, n)
    mx = jax.ops.segment_max(jnp.where(em, m, -jnp.inf), recv, n)
    mn, mx = jnp.where(has, mn, 0.0), jnp.where(has, mx, 0.0)
    aggr = jnp.concatenate([mean, mn, mx, std], -1)
    log_d = jnp.log(d + 1.0)
    scaled = jnp.concatenate([
        aggr,
        aggr * (log_d / stats["avg_log"]),
        aggr * (stats["avg_log"] / log_d),
        aggr * (d / stats["avg_lin"]),
    ], -1)
    out = C.dense(jnp.concatenate([x, scaled], -1), layer["post"], rounding)
    return C.dense(out, layer["lin"], rounding)


def _batch_norm(bn, h, mask):
    m = mask[:, None].astype(jnp.float32)
    count = jnp.maximum(m.sum(), 1.0)
    mean = (h * m).sum(0) / count
    var = (((h - mean) * m) ** 2).sum(0) / count
    y = (h - mean) / jnp.sqrt(var + 1e-5) * bn["scale"] + bn["bias"]
    return jnp.where(mask[:, None], y, 0.0)


def loss_fn(params, batch, arch, stats, rounding="f32"):
    """(total loss, (graph mse, node mse)) of one batch, train mode."""
    h = batch["x"]
    for layer in params["layers"]:
        def block(layer, h):
            c = _conv(layer, h, batch, stats, rounding)
            return jax.nn.relu(_batch_norm(layer["bn"], c, batch["node_mask"]))

        h = jax.checkpoint(block)(layer, h)
    return C.heads_loss(params, h, batch, arch["task_weights"], rounding)
