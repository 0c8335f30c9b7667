"""Plain reference of the GATv2 multi-head model: the layer of Brody, Alon,
Yahav, "How Attentive are Graph Attention Networks?" (ICLR 2022,
arXiv:2105.14491) as PyTorch Geometric's ``GATv2Conv`` computes it
(``share_weights=False``, ``add_self_loops=True``), at the widths of
Velickovic et al., "Graph Attention Networks" (ICLR 2018, arXiv:1710.10903),
section 3.3, the inductive model (three layers, K = 4 heads of 256 features,
ELU, LeakyReLU slope 0.2, no dropout, no L2), wired as HydraGNN's
``GATStack`` wires it. For receiver i, head h, over j in N(i) and i itself::

    x_l = W_l x + b_l,  x_r = W_r x + b_r                  (each [N, H, C])
    e_ijh = sum_c a_hc LeakyReLU(x_l[j,h,c] + x_r[i,h,c])
    alpha_ijh = exp(e_ijh) / sum_{j' in N(i) + {i}} exp(e_ij'h)
    out_ih = sum_j alpha_ijh x_l[j,h,:]   (+ bias)

hidden layers concatenate the H heads, the last layer averages them; then
train-mode BatchNorm over the batch's real nodes and the activation after
every conv, mean pooling, a graph head and a node head, weighted MSE.

Float32, ``HIGHEST`` products, an EDGE LIST of the reference's own capped
radius graph with one explicit self-loop a node appended: no neighbour
lists, no slots, no separate self term. The equations are computed as
written: ``x_l[send] + x_r[recv]``, LeakyReLU, the ``a`` contraction, a
softmax per (receiver, head) by its own per-receiver maximum and sum, the
weighted sum of ``x_l[send]``. The row axis (edges + self-loops) is walked in
``BLOCKS`` blocks, each rematerialised in the backward pass, so that its
``[rows, H, C]`` tensors fit at a cell's size.

Departures from the two papers, all HydraGNN's:

- one head count for every layer, and the LAST layer averages those same
  heads (the GAT paper's inductive model: 4, 4, then 6 averaged output
  heads);
- BatchNorm between the layers and no skip connection across the middle
  layer (the paper has the skip and no normalisation);
- regression heads and an MSE loss on generated targets (the paper: 121
  sigmoid labels of PPI);
- GATv2's scoring (the ``a`` contraction after the LeakyReLU) in place of
  GAT's.

``rounding`` names how the operands of every product are rounded
(``reference/common.py``); two names are planted faults of this mechanism's
own, for ``calibrate.py``, float32 each: ``"no_softmax"`` (uniform weights
over a receiver's real edges and its self-loop) and ``"no_self_loop"`` (the
self-loops left out), which a run must not pass for.
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import common as C
from .PNA import _batch_norm

BLOCKS = 8  # blocks of the row axis (edges + self-loops)
FAULTS = ("no_softmax", "no_self_loop")
ACTIVATIONS = {"relu": jax.nn.relu, "elu": jax.nn.elu}


def prepare(arch, train_degrees):
    """GATv2 needs nothing from the training set."""
    return {}


def layer_dims(arch, input_dim):
    """(input width, concatenates) of each conv layer."""
    wide = arch["heads"] * arch["hidden_dim"]
    depth = arch["num_conv_layers"]
    return [(input_dim if i == 0 else wide, i < depth - 1) for i in range(depth)]


def glorot(key, shape, fan_in, fan_out):
    bound = np.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound)


def init_params(key, arch, input_dim, out_dims):
    """Seeded weights: the two projections as ``torch.nn.Linear`` draws
    them (biases not nought, so that they are exercised), ``a`` Glorot."""
    heads, hidden = arch["heads"], arch["hidden_dim"]
    dims = layer_dims(arch, input_dim)
    keys = jax.random.split(key, len(dims) + 1)
    layers = []
    for k, (f, concat) in zip(keys, dims):
        k1, k2, k3, k4 = jax.random.split(k, 4)
        out = heads * hidden if concat else hidden
        layers.append({
            "lin_l": C.linear_init(k1, f, heads * hidden),
            "lin_r": C.linear_init(k2, f, heads * hidden),
            "att": glorot(k3, (heads, hidden), heads, hidden),
            "bias": jax.random.uniform(k4, (out,), jnp.float32, -0.1, 0.1),
            "bn": {"scale": jnp.ones((out,)), "bias": jnp.zeros((out,))},
        })
    out = {"layers": layers}
    out.update(C.init_heads(keys[-1], hidden, arch["output_heads"], out_dims))
    return out


def to_program(params):
    tree = C.to_program_heads(params)
    for i, l in enumerate(params["layers"]):
        tree[f"encoder_conv_{i}"] = {
            "w_l": l["lin_l"]["kernel"], "b_l": l["lin_l"]["bias"],
            "w_r": l["lin_r"]["kernel"], "b_r": l["lin_r"]["bias"],
            "att": l["att"][None], "bias": l["bias"],
        }
        tree[f"encoder_bn_{i}"] = dict(l["bn"])
    return tree


def _rounded(x, rounding):
    dt = C.ROUNDINGS[rounding]
    return x if dt is None else x.astype(dt).astype(jnp.float32)


def _rows(batch, self_loops):
    """(send, recv, mask) of the edges with one self-loop a node appended,
    padded to ``BLOCKS`` equal blocks: ``[BLOCKS, rows / BLOCKS]`` each."""
    n = batch["x"].shape[0]
    loop = jnp.arange(n, dtype=batch["send"].dtype)
    loops = batch["node_mask"] if self_loops else jnp.zeros_like(batch["node_mask"])
    send = jnp.concatenate([batch["send"], loop])
    recv = jnp.concatenate([batch["recv"], loop])
    mask = jnp.concatenate([batch["edge_mask"], loops])
    pad = -send.shape[0] % BLOCKS
    cut = lambda a: jnp.pad(a, (0, pad)).reshape(BLOCKS, -1)  # noqa: E731
    return cut(send), cut(recv), cut(mask)


def _conv(layer, x, batch, arch, rounding, concat, fault):
    n = x.shape[0]
    heads, hidden = arch["heads"], arch["hidden_dim"]
    x_l = C.dense(x, layer["lin_l"], rounding).reshape(n, heads, hidden)
    x_r = C.dense(x, layer["lin_r"], rounding).reshape(n, heads, hidden)
    send, recv, mask = _rows(batch, fault != "no_self_loop")
    att = _rounded(layer["att"], rounding)

    @jax.checkpoint
    def scores(rows):
        s, r = rows
        e = jax.nn.leaky_relu(x_l[s] + x_r[r], arch["negative_slope"])
        return (_rounded(e, rounding) * att).sum(-1)  # [rows, H]

    score = jax.lax.map(scores, (send, recv)).reshape(-1, heads)
    flat_recv, flat_mask = recv.reshape(-1), mask.reshape(-1)[:, None]
    if fault == "no_softmax":
        ex = flat_mask.astype(jnp.float32) * jnp.ones((1, heads))
    else:
        top = jax.ops.segment_max(
            jnp.where(flat_mask, score, -jnp.inf), flat_recv, n)
        top = jnp.where(jnp.isfinite(top), top, 0.0)
        ex = jnp.where(flat_mask, jnp.exp(score - top[flat_recv]), 0.0)
    total = jax.ops.segment_sum(ex, flat_recv, n)
    alpha = ex / jnp.maximum(total, 1e-30)[flat_recv]  # [rows, H]

    @jax.checkpoint
    def add_block(acc, rows):
        s, r, a = rows
        weighted = _rounded(a, rounding)[..., None] * _rounded(x_l[s], rounding)
        return acc + jax.ops.segment_sum(weighted, r, n), None

    out, _ = jax.lax.scan(
        add_block, jnp.zeros_like(x_l),
        (send, recv, alpha.reshape(BLOCKS, -1, heads)),
    )
    out = out.reshape(n, heads * hidden) if concat else out.mean(axis=1)
    return out + layer["bias"]


def _mlp(x, layers, rounding, act, final_activation=False):
    for i, layer in enumerate(layers):
        x = C.dense(x, layer, rounding)
        if i < len(layers) - 1 or final_activation:
            x = act(x)
    return x


def loss_fn(params, batch, arch, stats, rounding="f32"):
    """(total loss, (graph mse, node mse)) of one batch, train mode."""
    fault = rounding if rounding in FAULTS else None
    rounding = "f32" if fault else rounding
    act = ACTIVATIONS[arch.get("activation_function", "relu")]
    dims = layer_dims(arch, batch["x"].shape[-1])
    h = batch["x"]
    for layer, (_, concat) in zip(params["layers"], dims):
        def block(layer, h, concat=concat):
            c = _conv(layer, h, batch, arch, rounding, concat, fault)
            return act(_batch_norm(layer["bn"], c, batch["node_mask"]))

        h = jax.checkpoint(block)(layer, h)
    # the heads of reference/common.py with the configuration's activation
    pooled = C.graph_mean(h, batch)
    shared = _mlp(pooled, params["graph_shared"], rounding, act, True)
    out_g = _mlp(shared, params["graph_head"], rounding, act)
    out_n = _mlp(h, params["node_head"], rounding, act)
    tasks = (
        C.mse(out_g, batch["y_graph"], batch["graph_mask"]),
        C.mse(out_n, batch["y_node"], batch["node_mask"]),
    )
    w = np.asarray(arch["task_weights"], np.float64)
    w = w / np.abs(w).sum()
    return w[0] * tasks[0] + w[1] * tasks[1], tasks
