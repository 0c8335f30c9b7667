"""Plain reference of CGCNN as the Open Catalyst 2020 baseline trains it:
the layer of Xie & Grossman, "Crystal Graph Convolutional Neural Networks
for an Accurate and Interpretable Prediction of Material Properties" (PRL
120, 145301 (2018), arXiv:1710.10324) as ocp's ``CGCNNConv`` computes it,
at the widths of the S2EF baseline of Chanussot et al., "The Open Catalyst
2020 (OC20) Dataset and Community Challenges" (arXiv:2010.09990; ocp
``configs/s2ef/all/cgcnn/cgcnn.yml``). For receiver i, over its neighbours
j through any periodic image, with image offset ``o_ij``::

    d_ij = |p_j + o_ij - p_i|
    g_k  = exp(-gamma (d_ij - mu_k)^2),  mu_k = k rc / (K - 1),
           gamma = 0.5 / (rc / (K - 1))^2
    z_ij = BN(W [h_i ; h_j ; g] + b)       BN over the batch's real edges
    (f, s) = split(z_ij)
    m_i  = sum_j sigmoid(f) * softplus(s)
    h_i <- softplus(LayerNorm(m_i) + h_i)
    h^0  = W_e x + b_e

then mean pooling, a graph head and a node head (softplus between their
layers), weighted MSE. ``BN`` is train-mode BatchNorm (biased variance,
eps 1e-5) over the real edges of the batch, the LayerNorm's eps 1e-5.

Float32, ``HIGHEST`` products, the concatenated product as written (one
``(2C + K) -> 2C`` product an edge), an EDGE LIST of the reference's own
periodic pairs WITH their image offsets (``reference/SchNet.py
periodic_pairs``), capped per receiver in (image, sender) order. The edge
axis is walked in ``BLOCKS`` blocks, each rematerialised in the backward
pass, three times a conv (the BatchNorm's mean, its variance, then the
gated sum), so that no ``[E, 2C]`` table is held at a cell's size.

``check.follow`` assembles each step's batch through
``common.assemble``, which keeps no image offsets. As
``reference/SchNet.py`` does for itself, this module wraps it on import
(:func:`assemble`, chained over whatever wrapper is installed), and the
wrapper adds ``offset [E, 3]`` only where the caller's ``ref`` is THIS
module; it hands the caller's ``ref`` on to the wrapper it chains to, so
each reference's wrapper acts for that reference alone.

Departures from the two papers, HydraGNN's or the benchmark's:

- HydraGNN's heads in ocp's widths: a graph head of one 128-wide softplus
  layer on the pooled state, two more, then 1 (ocp's ``conv_to_fc``,
  ``fcs`` and ``fc_out``), and a node head of the same widths, 3 wide,
  where OC20 takes forces as -dE/dx;
- a biased linear map of a 3-column species descriptor in place of the
  92-column embedding table;
- MSE on both heads, on generated OC20-shaped slabs.

``rounding`` names how the operands of every product are rounded
(``reference/common.py``); two names are planted faults of this mechanism's
own, for ``calibrate.py``, float32 each: ``"no_image_offset"`` (the
in-cell difference ``p_j - p_i`` for every edge) and ``"no_edge_bn"`` (the
edge BatchNorm left out), which a run must not pass for.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np

from . import common as C
from .SchNet import periodic_pairs, with_offsets  # noqa: F401 (its pairs)

BLOCKS = 8  # blocks of the edge axis
FAULTS = ("no_image_offset", "no_edge_bn")
EPS = 1e-5  # BatchNorm's and LayerNorm's


def assemble(graphs, radius, cap, shape, multiple=1024):
    """``common.assemble``'s batch, and, where the caller (``check.follow``)
    follows this reference, ``offset``: each edge's image offset, zero on
    padding."""
    # named ``ref`` so that the wrapper chained to sees the caller's
    ref = sys._getframe(1).f_locals.get("ref")
    batch = _previous(graphs, radius, cap, shape, multiple)
    if ref is not sys.modules[__name__]:
        return batch
    return with_offsets(batch, graphs, radius, cap)


# what check.follow calls; chained over what is installed (SchNet's wrapper)
_previous = C.assemble
assemble.__wrapped__ = getattr(_previous, "__wrapped__", _previous)
C.assemble = assemble


def prepare(arch, train_degrees):
    """CGCNN needs nothing from the training set."""
    return {}


def glorot(key, fan_in, fan_out):
    bound = np.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, (fan_in, fan_out), jnp.float32, -bound, bound)


def init_params(key, arch, input_dim, out_dims):
    """Seeded weights: ``W`` Glorot as ocp's ``reset_parameters`` (its bias
    drawn small and not nought, so that it is exercised), the BatchNorm and
    LayerNorm at their initial scale 1 and shift 0, the embedding and the
    heads as ``torch.nn.Linear`` draws them."""
    hidden, k = arch["hidden_dim"], arch["num_gaussians"]
    wide = 2 * hidden
    keys = jax.random.split(key, arch["num_conv_layers"] + 2)
    layers = []
    for kl in keys[: arch["num_conv_layers"]]:
        k1, k2 = jax.random.split(kl)
        layers.append({
            "lin1": {"kernel": glorot(k1, wide + k, wide),
                     "bias": jax.random.uniform(k2, (wide,), jnp.float32, -0.1, 0.1)},
            "bn1": {"scale": jnp.ones((wide,)), "bias": jnp.zeros((wide,))},
            "ln1": {"scale": jnp.ones((hidden,)), "bias": jnp.zeros((hidden,))},
        })
    out = {
        "embedding": C.linear_init(keys[-2], input_dim, hidden),
        "layers": layers,
    }
    out.update(C.init_heads(keys[-1], hidden, arch["output_heads"], out_dims))
    return out


def to_program(params):
    tree = C.to_program_heads(params)
    tree["embedding"] = dict(params["embedding"])
    for i, l in enumerate(params["layers"]):
        tree[f"encoder_conv_{i}"] = {
            "lin1": l["lin1"]["kernel"], "bias1": l["lin1"]["bias"],
            "bn1": dict(l["bn1"]), "ln1": dict(l["ln1"]),
        }
    return tree


def _edges(batch, fault):
    """(send, recv, distance, mask) of the edges, each cut into ``BLOCKS``
    equal blocks."""
    vec = batch["pos"][batch["send"]] - batch["pos"][batch["recv"]]
    if fault != "no_image_offset":
        vec = vec + batch["offset"]
    sq = (vec * vec).sum(-1)
    dist = jnp.sqrt(jnp.where(sq > 0, sq, 1.0)) * (sq > 0)
    pad = -dist.shape[0] % BLOCKS
    cut = lambda a: jnp.pad(a, (0, pad)).reshape(BLOCKS, -1)  # noqa: E731
    return (cut(batch["send"]), cut(batch["recv"]), cut(dist),
            cut(batch["edge_mask"]))


def _conv(layer, h, edges, arch, rounding, fault):
    n = h.shape[0]
    k = arch["num_gaussians"]
    mu = jnp.linspace(0.0, arch["radius"], k)
    gamma = 0.5 / (arch["radius"] / (k - 1)) ** 2

    def z_of(block):
        s, r, d, mask = block
        g = jnp.exp(-gamma * (d[:, None] - mu) ** 2)
        z = C.dense(jnp.concatenate([h[r], h[s], g], -1), layer["lin1"], rounding)
        return z, mask[:, None]

    @jax.checkpoint
    def add_sum(acc, block):
        z, m = z_of(block)
        return acc + jnp.where(m, z, 0.0).sum(0), None

    @jax.checkpoint
    def add_square(acc, block):
        z, m = z_of(block)
        return acc + jnp.where(m, (z - mean) ** 2, 0.0).sum(0), None

    @jax.checkpoint
    def add_messages(acc, block):
        z, m = z_of(block)
        if fault != "no_edge_bn":
            z = (z - mean) / jnp.sqrt(var + EPS) * layer["bn1"]["scale"] \
                + layer["bn1"]["bias"]
        f, s = jnp.split(z, 2, axis=-1)
        msg = jnp.where(m, jax.nn.sigmoid(f) * jax.nn.softplus(s), 0.0)
        return acc + jax.ops.segment_sum(msg, block[1], n), None

    wide = 2 * h.shape[1]
    count = jnp.maximum(edges[3].sum(), 1).astype(jnp.float32)
    mean = jax.lax.scan(add_sum, jnp.zeros((wide,)), edges)[0] / count
    var = jax.lax.scan(add_square, jnp.zeros((wide,)), edges)[0] / count
    m = jax.lax.scan(add_messages, jnp.zeros_like(h), edges)[0]
    mu_m = m.mean(-1, keepdims=True)
    var_m = ((m - mu_m) ** 2).mean(-1, keepdims=True)
    normed = (m - mu_m) / jnp.sqrt(var_m + EPS) * layer["ln1"]["scale"] \
        + layer["ln1"]["bias"]
    return jax.nn.softplus(normed + h)


def _mlp(x, layers, rounding, final_activation=False):
    for i, layer in enumerate(layers):
        x = C.dense(x, layer, rounding)
        if i < len(layers) - 1 or final_activation:
            x = jax.nn.softplus(x)
    return x


def loss_fn(params, batch, arch, stats, rounding="f32"):
    """(total loss, (graph mse, node mse)) of one batch, train mode."""
    fault = rounding if rounding in FAULTS else None
    rounding = "f32" if fault else rounding
    edges = _edges(batch, fault)
    h = C.dense(batch["x"], params["embedding"], rounding)
    for layer in params["layers"]:
        h = jax.checkpoint(
            lambda layer, h: _conv(layer, h, edges, arch, rounding, fault)
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
