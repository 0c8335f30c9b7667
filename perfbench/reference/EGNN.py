"""Plain reference of the EGNN multi-head model (Satorras, Hoogeboom,
Welling, E(n) Equivariant Graph Neural Networks, ICML 2021) in the form of
HydraGNN's ``E_GCL``: edge MLP on [h_row, h_col, |dx|^2] (two Linear+ReLU),
node MLP on [h, sum of messages], coordinate update
``x_row += mean(dx / (|dx| + 1) * tanh(phi_x(m)))`` on all but the last
layer, ReLU after each layer and no feature normalisation; mean pooling; a
graph head and a node head; weighted MSE.

Departures from the paper, all HydraGNN's: no residual on h; messages and
coordinate updates are summed at ``row`` = the edge's source; ``dx`` is
normalised by ``|dx| + 1`` and the coordinate weight passes through tanh;
phi_x's last layer has no bias and a small (gain 1e-3) Xavier init.
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import common as C


def prepare(arch, train_degrees):
    return {}


def init_params(key, arch, input_dim, out_dims):
    hidden, depth = arch["hidden_dim"], arch["num_conv_layers"]
    keys = jax.random.split(key, depth + 1)
    layers = []
    for i in range(depth):
        f = input_dim if i == 0 else hidden
        k = jax.random.split(keys[i], 6)
        layer = {
            "edge0": C.linear_init(k[0], 2 * f + 1, hidden),
            "edge1": C.linear_init(k[1], hidden, hidden),
            "node0": C.linear_init(k[2], f + hidden, hidden),
            "node1": C.linear_init(k[3], hidden, hidden),
        }
        if arch["equivariance"] and i < depth - 1:
            bound = 1e-3 * np.sqrt(6.0 / (hidden + 1))
            layer["coord0"] = C.linear_init(k[4], hidden, hidden)
            layer["coord1"] = {"kernel": jax.random.uniform(
                k[5], (hidden, 1), jnp.float32, -bound, bound)}
        layers.append(layer)
    out = {"layers": layers}
    out.update(C.init_heads(keys[-1], hidden, arch["output_heads"], out_dims))
    return out


def to_program(params):
    tree = C.to_program_heads(params)
    for i, l in enumerate(params["layers"]):
        conv = {
            "edge_mlp_0": dict(l["edge0"]), "edge_mlp_1": dict(l["edge1"]),
            "node_mlp_0": dict(l["node0"]), "node_mlp_1": dict(l["node1"]),
        }
        if "coord0" in l:
            conv["coord_mlp_0"] = dict(l["coord0"])
            conv["coord_mlp_1"] = l["coord1"]["kernel"]
        tree[f"encoder_conv_{i}"] = conv
    return tree


def _layer(layer, h, pos, batch, rounding):
    n = h.shape[0]
    row, col, emask = batch["send"], batch["recv"], batch["edge_mask"]
    em = emask[:, None]
    dx = pos[row] - pos[col]
    radial = (dx * dx).sum(-1, keepdims=True)
    safe = jnp.where(radial > 0, radial, 1.0)
    norm = jnp.where(radial > 0, jnp.sqrt(safe), 0.0) + 1.0
    e_in = jnp.concatenate([h[row], h[col], radial], -1)
    m = jax.nn.relu(C.dense(e_in, layer["edge0"], rounding))
    m = jax.nn.relu(C.dense(m, layer["edge1"], rounding))
    m = jnp.where(em, m, 0.0)
    if "coord0" in layer:
        w = jax.nn.relu(C.dense(m, layer["coord0"], rounding))
        w = jnp.tanh(C.dense(w, layer["coord1"], rounding))
        trans = jnp.where(em, jnp.clip(dx / norm * w, -100.0, 100.0), 0.0)
        count = jax.ops.segment_sum(emask.astype(jnp.float32), row, n)
        pos = pos + jax.ops.segment_sum(trans, row, n) / jnp.maximum(
            count, 1.0)[:, None]
    agg = jax.ops.segment_sum(m, row, n)
    out = jax.nn.relu(C.dense(jnp.concatenate([h, agg], -1), layer["node0"],
                              rounding))
    return C.dense(out, layer["node1"], rounding), pos


def loss_fn(params, batch, arch, stats, rounding="f32"):
    """(total loss, (graph mse, node mse)) of one batch, train mode."""
    h, pos = batch["x"], batch["pos"]
    for layer in params["layers"]:
        def block(layer, h, pos):
            c, p = _layer(layer, h, pos, batch, rounding)
            return jax.nn.relu(c), p

        h, pos = jax.checkpoint(block)(layer, h, pos)
    h = jnp.where(batch["node_mask"][:, None], h, 0.0)
    return C.heads_loss(params, h, batch, arch["task_weights"], rounding)
