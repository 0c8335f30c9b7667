"""Shared pieces of the plain references: the capped radius graph, batch
assembly from raw graphs, dense layers at a stated precision, AdamW.

Plain ``numpy`` / ``jax.numpy`` in float32. Nothing here imports the program
(``hydragnn_tpu``) or reads anything it made: weights, edges, degree
statistics and optimizer state are all the reference's own.
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np

# how operands of every matrix product are rounded before an exact-f32
# product: the reference itself ("f32"), what the configuration states
# ("bf16"), and the control one step below it ("fp8")
ROUNDINGS = {
    "f32": None,
    "bf16": jnp.bfloat16,
    "fp8": jnp.float8_e4m3fn,
}


def capped_radius_graph(pos, cell, radius, cap):
    """(senders, receivers) of one graph: every ordered pair j -> i within
    ``radius`` (through any of the 27 periodic images when ``cell`` is
    given, never i -> i in the home image), at most ``cap`` incoming edges
    per receiver. The cap keeps the FIRST ``cap`` candidates in (image,
    sender index) order: the rule of torch-cluster's ``radius_graph``,
    which the HydraGNN reference pipeline uses. It is not a
    nearest-neighbour rule."""
    pos = np.asarray(pos, np.float64)
    n = len(pos)
    if cell is None:
        shifts = np.zeros((1, 3))
    else:
        cell = np.asarray(cell, np.float64)
        images = np.array(list(itertools.product((-1, 0, 1), repeat=3)))
        # an image further away than the graph is wide plus the cutoff
        # cannot hold a neighbour
        extent = pos.max(0) - pos.min(0)
        reach = np.all(np.abs(images) * cell - extent <= radius, axis=1)
        shifts = (images * cell)[reach]
    # d[i, s, j] = |pos_j + shift_s - pos_i|
    diff = pos[None, None, :, :] + shifts[None, :, None, :] - pos[:, None, None, :]
    within = (diff * diff).sum(-1) <= radius * radius
    home = np.flatnonzero(np.all(shifts == 0.0, axis=1))
    for s in home:
        within[np.arange(n), s, np.arange(n)] = False
    recv, _, send = np.nonzero(within)  # sorted by (receiver, image, sender)
    first = np.searchsorted(recv, recv, side="left")
    keep = np.arange(len(recv)) - first < cap
    return send[keep], recv[keep]


def _round_up(value, multiple):
    return int(-(-value // multiple) * multiple)


def assemble(graphs, radius, cap, shape, multiple=1024):
    """Concatenate raw graphs into one batch of plain arrays, with the
    reference's own edges, padded to ``shape`` = (nodes, edges, graphs) it
    may not pass; padding is masked everywhere it is read."""
    sends, recvs, n_node = [], [], []
    offset = 0
    for g in graphs:
        s, r = capped_radius_graph(g["pos"], g["cell"], radius, cap)
        sends.append(s + offset)
        recvs.append(r + offset)
        n_node.append(len(g["pos"]))
        offset += len(g["pos"])
    send, recv = np.concatenate(sends), np.concatenate(recvs)
    n, e, ng = offset, len(send), len(graphs)
    if n > shape[0] or e > shape[1] or ng > shape[2]:
        raise ValueError(f"batch {(n, e, ng)} passes its bound {shape}")
    n_pad = _round_up(shape[0] + 1, multiple)
    e_pad = _round_up(shape[1], multiple)
    g_pad = _round_up(shape[2] + 1, 8)

    def pad(a, rows, fill=0):
        out = np.full((rows,) + a.shape[1:], fill, a.dtype)
        out[: len(a)] = a
        return out

    return {
        "x": pad(np.concatenate([g["x_in"] for g in graphs]), n_pad),
        "pos": pad(np.concatenate([g["pos"] for g in graphs]), n_pad),
        "y_node": pad(np.concatenate([g["y_node"] for g in graphs]), n_pad),
        "y_graph": pad(np.stack([g["y_graph"] for g in graphs]), g_pad),
        "graph_id": pad(
            np.repeat(np.arange(ng), n_node).astype(np.int32), n_pad, ng
        ),
        "send": pad(send.astype(np.int32), e_pad, n),
        "recv": pad(recv.astype(np.int32), e_pad, n),
        "node_mask": pad(np.ones(n, bool), n_pad),
        "edge_mask": pad(np.ones(e, bool), e_pad),
        "graph_mask": pad(np.ones(ng, bool), g_pad),
        "n_node": pad(np.asarray(n_node, np.float32), g_pad),
    }


def in_degrees(graphs, radius, cap):
    """Per graph, every atom's number of incoming edges."""
    return [
        np.bincount(
            capped_radius_graph(g["pos"], g["cell"], radius, cap)[1],
            minlength=len(g["pos"]),
        )
        for g in graphs
    ]


def dense(x, layer, rounding):
    """``x @ kernel + bias`` with both operands rounded as ``rounding``
    says and the product itself exact in float32."""
    w = layer["kernel"]
    dt = ROUNDINGS[rounding]
    if dt is not None:
        x = x.astype(dt).astype(jnp.float32)
        w = w.astype(dt).astype(jnp.float32)
    y = jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)
    return y + layer["bias"] if "bias" in layer else y


def mlp(x, layers, rounding, final_activation=False):
    for i, layer in enumerate(layers):
        x = dense(x, layer, rounding)
        if i < len(layers) - 1 or final_activation:
            x = jax.nn.relu(x)
    return x


def linear_init(key, fan_in, fan_out, bias=True):
    """torch.nn.Linear's default: U(-1/sqrt(fan_in), 1/sqrt(fan_in))."""
    kw, kb = jax.random.split(key)
    bound = 1.0 / np.sqrt(fan_in)
    out = {"kernel": jax.random.uniform(kw, (fan_in, fan_out), jnp.float32,
                                        -bound, bound)}
    if bias:
        out["bias"] = jax.random.uniform(kb, (fan_out,), jnp.float32,
                                         -bound, bound)
    return out


def mse(pred, target, mask):
    """Mean squared error over real rows x features."""
    m = mask[:, None].astype(jnp.float32)
    diff = jnp.where(m > 0, pred - target, 0.0)
    return (diff * diff).sum() / jnp.maximum(m.sum() * pred.shape[-1], 1.0)


def graph_mean(h, batch):
    """Per-graph mean of node rows (padding rows land in a padding graph)."""
    total = jax.ops.segment_sum(
        jnp.where(batch["node_mask"][:, None], h, 0.0),
        batch["graph_id"], batch["n_node"].shape[0],
    )
    return total / jnp.maximum(batch["n_node"], 1.0)[:, None]


def head_layers(key, dims):
    keys = jax.random.split(key, len(dims) - 1)
    return [linear_init(k, a, b) for k, a, b in zip(keys, dims[:-1], dims[1:])]


def init_heads(key, hidden, heads, out_dims):
    """Graph head (shared MLP, then its own) and node head (one MLP)."""
    g, nd = heads["graph"], heads["node"]
    k1, k2, k3 = jax.random.split(key, 3)
    shared = [hidden] + [g["dim_sharedlayers"]] * g["num_sharedlayers"]
    own = [shared[-1]] + list(g["dim_headlayers"][: g["num_headlayers"]]) + [out_dims[0]]
    node = [hidden] + list(nd["dim_headlayers"]) + [out_dims[1]]
    return {
        "graph_shared": head_layers(k1, shared),
        "graph_head": head_layers(k2, own),
        "node_head": head_layers(k3, node),
    }


def heads_loss(params, h, batch, weights, rounding):
    """Both heads and the weighted multi-task loss (weights normalised by
    their absolute sum). Returns (total, (graph mse, node mse))."""
    pooled = graph_mean(h, batch)
    shared = mlp(pooled, params["graph_shared"], rounding, final_activation=True)
    out_g = mlp(shared, params["graph_head"], rounding)
    out_n = mlp(h, params["node_head"], rounding)
    tasks = (
        mse(out_g, batch["y_graph"], batch["graph_mask"]),
        mse(out_n, batch["y_node"], batch["node_mask"]),
    )
    w = np.asarray(weights, np.float64)
    w = w / np.abs(w).sum()
    return w[0] * tasks[0] + w[1] * tasks[1], tasks


def to_program_heads(params):
    """The heads under the names the program's parameter tree uses."""
    def seq(layers):
        return {f"TorchLinear_{i}": dict(l) for i, l in enumerate(layers)}

    node = {}
    for i, l in enumerate(params["node_head"]):
        node[f"kernel_{i}"] = l["kernel"][None]
        node[f"bias_{i}"] = l["bias"][None]
    return {
        "graph_shared": seq(params["graph_shared"]),
        "head_0_graph": seq(params["graph_head"]),
        "head_1_node": node,
    }


def adamw_step(params, mu, nu, grads, t, lr, b1=0.9, b2=0.999, eps=1e-8,
               weight_decay=0.01):
    """One AdamW update (Loshchilov & Hutter), ``t`` counted from 1."""
    tm = jax.tree_util.tree_map
    mu = tm(lambda m, g: b1 * m + (1.0 - b1) * g, mu, grads)
    nu = tm(lambda v, g: b2 * v + (1.0 - b2) * g * g, nu, grads)
    c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    params = tm(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                  + weight_decay * p),
        params, mu, nu,
    )
    return params, mu, nu
