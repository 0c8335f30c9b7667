"""Plain reference of the DimeNet++ multi-head model (Gasteiger, Giri,
Margraf, Guennemann, "Fast and Uncertainty-Aware Directional Message Passing
for Non-Equilibrium Molecules", arXiv:2011.14115) as HydraGNN runs it
(``hydragnn/models/DIMEStack.py`` over PyTorch Geometric's
``BesselBasisLayer``, ``SphericalBasisLayer``, ``InteractionPPBlock`` and
``OutputPPBlock``): per conv layer ``lin`` on the node states, the embedding
block on ``[h_i, h_j, rbf]``, one interaction block over the triplets
(k->j->i), the output block back to node states; ReLU after each layer; mean
pooling; a graph head and a node head; weighted MSE.

Float32, ``HIGHEST`` products, an explicit TRIPLET list on the reference's
own capped radius graph: every pair of edges (k->j, j->i) with k != i, found
as PyG's ``triplets`` finds them (row j of the receiver-sorted adjacency for
every edge j->i), no neighbour lists or slot grids of the program's, nothing
hoisted out of the layers. The angle is ``arctan2(|cross|, dot)``; the
spherical Bessel functions come from this file's own recurrence at roots this
file finds by bisection in float64 (:func:`bessel_roots`). Graphs are
independent, so the triplet axis is walked in blocks of ``BLOCK`` edges j->i
(each with its at most ``max_neighbours`` edges k->j), each block
rematerialised in the backward pass: the axis fits at a cell's size.

Departures from the paper, all HydraGNN's or its port's:

- edge states do not live across interaction blocks: every conv layer embeds
  them anew from the node states (``lin`` -> embedding block), and a layer's
  output block feeds the next layer's node states instead of being summed
  over the blocks;
- the output block has one hidden layer (the paper: three);
- a layer's internal width is its input width unless that is 1, then its
  output width (``DIMEStack.get_conv``): at one input feature every layer is
  ``hidden_dim`` wide;
- no atom-type embedding: the input features enter through ``lin``;
- the angle of a triplet is taken at atom i between j - i and k - i (as
  ``DIMEStack._conv_args`` and PyG's first ``DimeNet`` take it; the paper's
  angle sits at j);
- forces come from a node head, not from the energy's gradient;
- the bases carry no normalisers: the Bessel functions are not divided by
  ``|j_{l+1}(z_ln)| / sqrt(2)`` nor the Legendre polynomials multiplied by
  ``sqrt((2l + 1) / 4 pi)`` (the program leaves them to the linear layers
  that follow; with seeded weights they are simply absent).

``rounding`` names how the operands of every matrix product are rounded
(``reference/common.py``); ``"no_directional"`` is a planted fault of this
mechanism's own, for ``calibrate.py``: float32 with the triplet sum left out
(``x_kj`` = 0), which a run must not pass for.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import common as C

BLOCK = 1024  # edges j->i per block of the triplet axis (e_pad's multiple)


# ---- bases ---------------------------------------------------------------------


def spherical_jl(l, x, xp=jnp):
    """Spherical Bessel j_l(x) by upward recurrence from j_0 and j_1 (fine
    for x > l, which holds at every root and at this traffic's distances):
    ``jax.numpy`` float32 inside the model, ``numpy`` float64 for the roots."""
    j0 = xp.sin(x) / x
    if l == 0:
        return j0
    j1 = xp.sin(x) / (x * x) - xp.cos(x) / x
    for n in range(1, l):
        j0, j1 = j1, (2 * n + 1) / x * j1 - j0
    return j1


@functools.lru_cache(maxsize=None)
def bessel_roots(num_spherical, num_radial):
    """``[num_spherical, num_radial]`` first roots of j_0 .. j_{S-1}: those
    of j_0 are n pi, and the roots of j_l interlace those of j_{l-1}, so
    each is bracketed by two neighbours of the order below and bisected."""
    count = num_radial + num_spherical - 1
    points = np.arange(1, count + 1) * np.pi
    roots = [points[:num_radial]]
    for l in range(1, num_spherical):
        found = []
        for lo, hi in zip(points[:-1], points[1:]):
            f_lo = spherical_jl(l, lo, np)
            for _ in range(200):
                mid = 0.5 * (lo + hi)
                f_mid = spherical_jl(l, mid, np)
                if (f_mid > 0) == (f_lo > 0):
                    lo, f_lo = mid, f_mid
                else:
                    hi = mid
            found.append(0.5 * (lo + hi))
        points = np.asarray(found)
        roots.append(points[:num_radial])
    return np.stack(roots)


def envelope(d, exponent):
    """u(d) = 1/d + a d^(p-1) + b d^p + c d^(p+1), p = exponent + 1, inside
    the cutoff (d < 1), nought outside (PyG's ``Envelope``)."""
    p = exponent + 1
    a, b, c = -(p + 1) * (p + 2) / 2.0, p * (p + 2.0), -p * (p + 1) / 2.0
    inside = d < 1.0
    d = jnp.where(inside, d, 0.5)
    u = 1.0 / d + a * d ** (p - 1) + b * d**p + c * d ** (p + 1)
    return jnp.where(inside, u, 0.0)


def radial_basis(d, freq, exponent):
    """``[E, R]``: env(d) sin(freq_n d), d the distance over the cutoff."""
    return envelope(d, exponent)[:, None] * jnp.sin(freq * d[:, None])


def spherical_basis(d_kj, angle, arch):
    """``[T, S * R]``: env(d_kj) j_l(z_ln d_kj) P_l(cos angle), l-major."""
    s, r = arch["num_spherical"], arch["num_radial"]
    roots = bessel_roots(s, r)
    env = envelope(d_kj, arch["envelope_exponent"])
    cos_t = jnp.cos(angle)
    legendre = [jnp.ones_like(cos_t), cos_t]
    for l in range(2, s):
        legendre.append(
            ((2 * l - 1) * cos_t * legendre[-1] - (l - 1) * legendre[-2]) / l
        )
    columns = []
    for l in range(s):
        x = d_kj[:, None] * jnp.asarray(roots[l], jnp.float32)  # [T, R]
        columns.append(env[:, None] * spherical_jl(l, x) * legendre[l][:, None])
    return jnp.concatenate(columns, axis=1)


# ---- triplets --------------------------------------------------------------------


def triplet_table(send, recv, edge_mask, num_nodes, cap):
    """For every edge j->i (a row) the edges k->j (``[E, cap]`` edge ids)
    and which of them make a triplet: k->j exists, j->i is real, k != i.
    The edge list is sorted by receiver (``common.capped_radius_graph``
    gives it so, ``common.assemble`` keeps it so) and holds at most ``cap``
    edges into a node, so the edges into j are the ``degree[j]`` rows from
    ``first[j]`` on. numpy in, numpy out; jax in, jax out."""
    xp = jnp if isinstance(send, jax.Array) else np
    nodes = xp.arange(num_nodes)
    first = xp.searchsorted(recv, nodes, side="left")
    degree = xp.searchsorted(recv, nodes, side="right") - first
    slot = xp.arange(cap)
    idx_kj = first[send][:, None] + slot[None, :]
    valid = (slot[None, :] < degree[send][:, None]) & edge_mask[:, None]
    idx_kj = xp.where(valid, idx_kj, 0)
    valid = valid & (send[idx_kj] != recv[:, None])
    return idx_kj, valid


def count_triplets(send, recv, num_nodes, cap):
    """Real triplets of one graph's (or batch's) real edges."""
    send, recv = np.asarray(send), np.asarray(recv)
    return int(triplet_table(
        send, recv, np.ones(len(send), bool), num_nodes, cap)[1].sum())


def _directional_sum(layer, x_kj, dist, batch, arch, rounding):
    """``sum_k sbf(k, j, i) * x_kj[k->j]`` for every edge j->i: ``[E, D]``,
    walked in blocks of ``BLOCK`` edges j->i."""
    send, recv, pos = batch["send"], batch["recv"], batch["pos"]
    cap = arch["max_neighbours"]
    idx_kj, valid = triplet_table(
        send, recv, batch["edge_mask"], pos.shape[0], cap)
    blocks = send.shape[0] // BLOCK

    def block(args):
        idx_kj, valid, idx_ji = args
        # the explicit triplet list of this block: T = BLOCK * cap rows
        t_kj, t_ok = idx_kj.reshape(-1), valid.reshape(-1)
        t_ji = jnp.repeat(idx_ji, cap)
        i, j, k = recv[t_ji], send[t_ji], send[t_kj]
        pos_ji, pos_ki = pos[j] - pos[i], pos[k] - pos[i]
        a = (pos_ji * pos_ki).sum(-1)
        b = jnp.linalg.norm(jnp.cross(pos_ji, pos_ki), axis=-1)
        angle = jnp.arctan2(b, a)
        sbf = spherical_basis(jnp.where(t_ok, dist[t_kj], 1.0), angle, arch)
        sbf = C.dense(C.dense(sbf, layer["int_sbf1"], rounding),
                      layer["int_sbf2"], rounding)
        terms = jnp.where(t_ok[:, None], x_kj[t_kj] * sbf, 0.0)
        return jax.ops.segment_sum(terms, t_ji - idx_ji[0], BLOCK)

    out = jax.lax.map(
        jax.checkpoint(block),
        (idx_kj.reshape(blocks, BLOCK, cap), valid.reshape(blocks, BLOCK, cap),
         jnp.arange(send.shape[0]).reshape(blocks, BLOCK)),
    )
    return out.reshape(send.shape[0], -1)


# ---- the model -------------------------------------------------------------------


def layer_width(in_dim, out_dim):
    """A conv layer's internal width (``DIMEStack.get_conv``)."""
    return out_dim if in_dim == 1 else in_dim


def init_params(key, arch, input_dim, out_dims):
    hidden, depth = arch["hidden_dim"], arch["num_conv_layers"]
    radial = arch["num_radial"]
    sbf = arch["num_spherical"] * radial
    basis, inner, out_emb = (arch["basis_emb_size"], arch["int_emb_size"],
                             arch["out_emb_size"])
    keys = jax.random.split(key, depth + 1)
    layers = []
    for i in range(depth):
        f = input_dim if i == 0 else hidden
        w = layer_width(f, hidden)
        k = iter(jax.random.split(keys[i], 32))
        lin = lambda a, b, bias=True: C.linear_init(next(k), a, b, bias)  # noqa: E731
        res = lambda: {"lin1": lin(w, w), "lin2": lin(w, w)}  # noqa: E731
        layers.append({
            "freq": jnp.arange(1, radial + 1, dtype=jnp.float32) * np.pi,
            "lin": lin(f, w),
            "emb_lin_rbf": lin(radial, w),
            "emb_lin": lin(3 * w, w),
            "int_rbf1": lin(radial, basis, False),
            "int_rbf2": lin(basis, w, False),
            "int_sbf1": lin(sbf, basis, False),
            "int_sbf2": lin(basis, inner, False),
            "int_lin_ji": lin(w, w),
            "int_lin_kj": lin(w, w),
            "int_down": lin(w, inner, False),
            "int_up": lin(inner, w, False),
            "before_skip": [res() for _ in range(arch["num_before_skip"])],
            "int_lin": lin(w, w),
            "after_skip": [res() for _ in range(arch["num_after_skip"])],
            "out_lin_rbf": lin(radial, w, False),
            "out_up": lin(w, out_emb, False),
            "out_0": lin(out_emb, out_emb),
            "out_final": lin(out_emb, hidden, False),
        })
    out = {"layers": layers}
    out.update(C.init_heads(keys[-1], hidden, arch["output_heads"], out_dims))
    return out


def to_program(params):
    tree = C.to_program_heads(params)
    for i, l in enumerate(params["layers"]):
        conv = {"rbf": {"freq": l["freq"]}}
        for name, value in l.items():
            if name in ("before_skip", "after_skip"):
                for n, res in enumerate(value):
                    conv[f"{name}_{n}"] = {k: dict(v) for k, v in res.items()}
            elif name != "freq":
                conv[name] = dict(value)
        tree[f"encoder_conv_{i}"] = conv
    return tree


def _residual(x, res, rounding):
    h = jax.nn.silu(C.dense(x, res["lin1"], rounding))
    return x + jax.nn.silu(C.dense(h, res["lin2"], rounding))


def _layer(layer, x, batch, arch, rounding, directional):
    act = jax.nn.silu
    send, recv, emask = batch["send"], batch["recv"], batch["edge_mask"]
    dense = lambda v, name: C.dense(v, layer[name], rounding)  # noqa: E731
    diff = batch["pos"][recv] - batch["pos"][send]
    d2 = jnp.where(emask, (diff * diff).sum(-1), 1.0)
    dist = jnp.where(emask, jnp.sqrt(d2) / arch["radius"], 1.0)  # over the cutoff
    rbf = radial_basis(dist, layer["freq"], arch["envelope_exponent"])

    # lin, then the embedding block: edge states from node states
    h = dense(x, "lin")
    r = act(dense(rbf, "emb_lin_rbf"))
    e = act(dense(jnp.concatenate([h[recv], h[send], r], -1), "emb_lin"))

    # InteractionPPBlock
    x_ji = act(dense(e, "int_lin_ji"))
    x_kj = act(dense(e, "int_lin_kj")) * dense(dense(rbf, "int_rbf1"), "int_rbf2")
    x_kj = act(dense(x_kj, "int_down"))
    if directional:
        x_kj = _directional_sum(layer, x_kj, dist, batch, arch, rounding)
    else:
        x_kj = jnp.zeros_like(x_kj)
    m = x_ji + act(dense(x_kj, "int_up"))
    for res in layer["before_skip"]:
        m = _residual(m, res, rounding)
    m = act(dense(m, "int_lin")) + e
    for res in layer["after_skip"]:
        m = _residual(m, res, rounding)

    # OutputPPBlock: edge states summed at their receivers
    o = jnp.where(emask[:, None], dense(rbf, "out_lin_rbf") * m, 0.0)
    o = jax.ops.segment_sum(o, recv, x.shape[0])
    o = act(dense(dense(o, "out_up"), "out_0"))
    return dense(o, "out_final")


def prepare(arch, train_degrees):
    return {}


def loss_fn(params, batch, arch, stats, rounding="f32"):
    """(total loss, (graph mse, node mse)) of one batch, train mode."""
    directional = rounding != "no_directional"
    rounding = rounding if directional else "f32"
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    h = batch["x"]
    for layer in params["layers"]:
        def block(layer, h):
            return jax.nn.relu(
                _layer(layer, h, batch, arch, rounding, directional))

        h = jax.checkpoint(block)(layer, h)
    h = jnp.where(batch["node_mask"][:, None], h, 0.0)
    return C.heads_loss(params, h, batch, arch["task_weights"], rounding)
