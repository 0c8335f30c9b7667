"""Plain reference of SchNet as the Open Catalyst 2020 baseline trains it:
the layer of Schuett et al., "SchNet: A continuous-filter convolutional
neural network for modeling quantum interactions" (NeurIPS 2017,
arXiv:1706.08566) as PyTorch Geometric's ``SchNet`` / ``InteractionBlock`` /
``CFConv`` compute it, at the widths of the S2EF baseline of Chanussot et
al., "The Open Catalyst 2020 (OC20) Dataset and Community Challenges"
(arXiv:2010.09990; ocp ``configs/s2ef/all/schnet/schnet.yml``). For receiver
i, over its neighbours j through any periodic image, with image offset
``o_ij`` (the image's lattice vector)::

    d_ij = |p_j + o_ij - p_i|
    e_k  = exp(-gamma (d_ij - mu_k)^2),  mu_k = k rc / (K - 1),
           gamma = 0.5 / (rc / (K - 1))^2
    C    = 0.5 (cos(pi d_ij / rc) + 1)
    W_ij = (W_f2 ssp(W_f1 e + b_f1) + b_f2) * C
    m_i  = sum_j (W_1 h_j) * W_ij                      W_1 has no bias
    h_i <- h_i + W_3 ssp(W_2 m_i + b_2) + b_3
    h^0  = W_e x (no bias);  ssp(x) = softplus(x) - ln 2

then mean pooling, a graph head and a node head (``ssp`` between their
layers), weighted MSE.

Float32, ``HIGHEST`` products, an EDGE LIST of the reference's own periodic
pairs WITH their image offsets (:func:`periodic_pairs`), capped per receiver
in (image, sender) order, the order of the program's ``radius_graph_pbc``
and of ``common.capped_radius_graph``. The edge axis is walked in ``BLOCKS``
blocks, each rematerialised in the backward pass, so that its ``[E, 256]``
tables fit at a cell's size.

``check.follow`` assembles each step's batch through
``common.assemble``, which keeps no image offsets, and has no hook for a
reference's own assembly (a ``benchmark`` PR's to add: PERF.md section
7). So this module wraps it (:func:`assemble`, installed on import), and
the wrapper acts for THIS reference alone: where the caller's ``ref`` is
this module it adds ``offset [E, 3]`` from :func:`periodic_pairs` (whose
pairs must be exactly ``common``'s, which it checks); for every other
caller, and so for every other cell's reference in the same process, it
returns ``common.assemble``'s batch untouched.

Departures from the two papers, HydraGNN's or the benchmark's:

- mean pooling and HydraGNN's heads (a graph head of one 512-wide ``ssp``
  layer on the pooled state, as SchNet's output block on each atom before
  its sum; a node head of the same form, 3 wide) where SchNet sums per-atom
  energies and OC20 takes forces as -dE/dx;
- a bias-free linear map of a 3-column species descriptor in place of
  the embedding lookup;
- MSE on both heads, on generated OC20-shaped slabs.

``rounding`` names how the operands of every product are rounded
(``reference/common.py``); two names are planted faults of this mechanism's
own, for ``calibrate.py``, float32 each: ``"no_image_offset"`` (the
in-cell difference ``p_j - p_i`` for every edge: what the program computed
before the offsets were carried) and ``"no_cutoff"`` (the envelope
dropped), which a run must not pass for.
"""

import itertools
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from . import common as C

BLOCKS = 8  # blocks of the edge axis
FAULTS = ("no_image_offset", "no_cutoff")


def periodic_pairs(pos, cell, radius, cap):
    """(senders, receivers, offsets ``[E, 3]``) of one graph: every ordered
    pair j -> i within ``radius`` through any of the 27 periodic images
    (only the home image where ``cell`` is None), never i -> i in the home
    image, at most ``cap`` incoming edges per receiver, kept in (image,
    sender) order: ``common.capped_radius_graph``'s pairs, with the image
    each was found through."""
    pos = np.asarray(pos, np.float64)
    n = len(pos)
    if cell is None:
        shifts = np.zeros((1, 3))
    else:
        images = np.array(list(itertools.product((-1, 0, 1), repeat=3)))
        shifts = images * np.asarray(cell, np.float64)
    # d[i, s, j] = |pos_j + shift_s - pos_i|
    diff = pos[None, None, :, :] + shifts[None, :, None, :] - pos[:, None, None, :]
    within = (diff * diff).sum(-1) <= radius * radius
    for s in np.flatnonzero(np.all(shifts == 0.0, axis=1)):
        within[np.arange(n), s, np.arange(n)] = False
    recv, image, send = np.nonzero(within)  # sorted by (receiver, image, sender)
    keep = np.arange(len(recv)) - np.searchsorted(recv, recv) < cap
    return send[keep], recv[keep], shifts[image[keep]].astype(np.float32)


def assemble(graphs, radius, cap, shape, multiple=1024):
    """``common.assemble``'s batch, and, where the caller (``check.follow``)
    follows this reference, ``offset``: each edge's image offset, zero on
    padding."""
    batch = _common_assemble(graphs, radius, cap, shape, multiple)
    if sys._getframe(1).f_locals.get("ref") is not sys.modules[__name__]:
        return batch
    return with_offsets(batch, graphs, radius, cap)


def with_offsets(batch, graphs, radius, cap):
    """``batch`` (``common.assemble``'s, of ``graphs``) and ``offset``."""
    sends, recvs, offsets, start = [], [], [], 0
    for g in graphs:
        s, r, o = periodic_pairs(g["pos"], g["cell"], radius, cap)
        sends.append(s + start)
        recvs.append(r + start)
        offsets.append(o)
        start += len(g["pos"])
    send, recv = np.concatenate(sends), np.concatenate(recvs)
    e = len(send)
    if not (np.array_equal(batch["send"][:e], send)
            and np.array_equal(batch["recv"][:e], recv)
            and not batch["edge_mask"][e:].any()):
        raise RuntimeError("periodic_pairs differs from common's radius graph")
    offset = np.zeros((len(batch["send"]), 3), np.float32)
    offset[:e] = np.concatenate(offsets)
    return dict(batch, offset=offset)


# what check.follow calls; wrapped once however often this is imported
_common_assemble = getattr(C.assemble, "__wrapped__", C.assemble)
assemble.__wrapped__ = _common_assemble
C.assemble = assemble


def prepare(arch, train_degrees):
    """SchNet needs nothing from the training set."""
    return {}


def ssp(x):
    return jax.nn.softplus(x) - math.log(2.0)


def glorot(key, fan_in, fan_out):
    bound = np.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, (fan_in, fan_out), jnp.float32, -bound, bound)


def init_params(key, arch, input_dim, out_dims):
    """Seeded weights: the filter network and the heads as ``torch.nn.Linear``
    draws them, ``W_e`` likewise without a bias, ``W_1``, ``W_2``, ``W_3``
    Glorot as PyG's ``reset_parameters`` (the biases ``b_2``, ``b_3`` drawn
    small and not nought, so that they are exercised)."""
    hidden, filters = arch["hidden_dim"], arch["num_filters"]
    keys = jax.random.split(key, arch["num_conv_layers"] + 2)
    layers = []
    for k in keys[: arch["num_conv_layers"]]:
        k1, k2, k3, k4, k5, k6, k7 = jax.random.split(k, 7)
        layers.append({
            "filter_0": C.linear_init(k1, arch["num_gaussians"], filters),
            "filter_1": C.linear_init(k2, filters, filters),
            "lin1": {"kernel": glorot(k3, hidden, filters)},
            "lin2": {"kernel": glorot(k4, filters, hidden),
                     "bias": jax.random.uniform(k5, (hidden,), jnp.float32, -0.1, 0.1)},
            "lin3": {"kernel": glorot(k6, hidden, hidden),
                     "bias": jax.random.uniform(k7, (hidden,), jnp.float32, -0.1, 0.1)},
        })
    out = {
        "embedding": C.linear_init(keys[-2], input_dim, hidden, bias=False),
        "layers": layers,
    }
    out.update(C.init_heads(keys[-1], hidden, arch["output_heads"], out_dims))
    return out


def to_program(params):
    tree = C.to_program_heads(params)
    tree["embedding"] = dict(params["embedding"])
    for i, l in enumerate(params["layers"]):
        tree[f"encoder_conv_{i}"] = {
            "filter_0": dict(l["filter_0"]), "filter_1": dict(l["filter_1"]),
            "lin1": l["lin1"]["kernel"],
            "lin2": l["lin2"]["kernel"], "bias2": l["lin2"]["bias"],
            "lin3": l["lin3"]["kernel"], "bias3": l["lin3"]["bias"],
        }
    return tree


def _rounded(x, rounding):
    dt = C.ROUNDINGS[rounding]
    return x if dt is None else x.astype(dt).astype(jnp.float32)


def _edges(batch, arch, fault):
    """(send, recv, distance, envelope, mask) of the edges, each cut into
    ``BLOCKS`` equal blocks."""
    vec = batch["pos"][batch["send"]] - batch["pos"][batch["recv"]]
    if fault != "no_image_offset":
        vec = vec + batch["offset"]
    sq = (vec * vec).sum(-1)
    dist = jnp.sqrt(jnp.where(sq > 0, sq, 1.0)) * (sq > 0)
    if fault == "no_cutoff":
        envelope = jnp.ones_like(dist)
    else:
        envelope = 0.5 * (jnp.cos(dist * math.pi / arch["radius"]) + 1.0)
    pad = -dist.shape[0] % BLOCKS
    cut = lambda a: jnp.pad(a, (0, pad)).reshape(BLOCKS, -1)  # noqa: E731
    return (cut(batch["send"]), cut(batch["recv"]), cut(dist), cut(envelope),
            cut(batch["edge_mask"]))


def _interaction(layer, h, edges, arch, rounding):
    n = h.shape[0]
    k = arch["num_gaussians"]
    mu = jnp.linspace(0.0, arch["radius"], k)
    gamma = 0.5 / (arch["radius"] / (k - 1)) ** 2
    xw = C.dense(h, layer["lin1"], rounding)  # [N, F]

    @jax.checkpoint
    def add_block(acc, block):
        s, r, d, env, mask = block
        rbf = jnp.exp(-gamma * (d[:, None] - mu) ** 2)
        w = C.dense(ssp(C.dense(rbf, layer["filter_0"], rounding)),
                    layer["filter_1"], rounding)
        w = jnp.where(mask[:, None], w * env[:, None], 0.0)
        msg = _rounded(xw[s], rounding) * _rounded(w, rounding)
        return acc + jax.ops.segment_sum(msg, r, n), None

    m, _ = jax.lax.scan(add_block, jnp.zeros_like(xw), edges)
    return h + C.dense(ssp(C.dense(m, layer["lin2"], rounding)),
                       layer["lin3"], rounding)


def _mlp(x, layers, rounding, final_activation=False):
    for i, layer in enumerate(layers):
        x = C.dense(x, layer, rounding)
        if i < len(layers) - 1 or final_activation:
            x = ssp(x)
    return x


def loss_fn(params, batch, arch, stats, rounding="f32"):
    """(total loss, (graph mse, node mse)) of one batch."""
    fault = rounding if rounding in FAULTS else None
    rounding = "f32" if fault else rounding
    edges = _edges(batch, arch, fault)
    h = C.dense(batch["x"], params["embedding"], rounding)
    for layer in params["layers"]:
        h = jax.checkpoint(
            lambda layer, h: _interaction(layer, h, edges, arch, rounding)
        )(layer, h)
    pooled = C.graph_mean(h, batch)
    shared = _mlp(pooled, params["graph_shared"], rounding, True)
    out_g = _mlp(shared, params["graph_head"], rounding)
    out_n = _mlp(h, params["node_head"], rounding)
    tasks = (
        C.mse(out_g, batch["y_graph"], batch["graph_mask"]),
        C.mse(out_n, batch["y_node"], batch["node_mask"]),
    )
    w = np.asarray(arch["task_weights"], np.float64)
    w = w / np.abs(w).sum()
    return w[0] * tasks[0] + w[1] * tasks[1], tasks
