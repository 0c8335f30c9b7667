"""Operations and bytes one epoch of DimeNet++ training REQUIRES (HydraGNN's
``DIMEStack`` wiring: ``reference/DimeNet.py``), from the batch's real node,
edge and graph counts and the configuration's widths: not the compiler's
count, no padding, no recomputation.

The algorithm counted is the cheapest exact form of each block:

- the embedding block's linear on ``[h_i ; h_j ; r]`` as two node-level
  products plus one per edge on ``r``;
- the two bias-free radial projections composed to one ``[R, w]`` matrix,
  and the two spherical ones to one ``[S * R, int_emb]`` matrix;
- the directional sum factorised the way its basis factorises: per EDGE
  k->j the radial half times the composed matrix (``S * R * int_emb``
  multiply-adds), per TRIPLET only the ``S`` angular coefficients against
  that edge's ``[S, int_emb]`` block. The unfactorised form pays the whole
  ``S * R * int_emb`` per triplet, six times as much.

``run.py`` hands over no triplet count, so triplets are taken as
``edges^2 / nodes - edges``: what a graph whose nodes all have the mean degree
holds (sum of in x out degree is least when the degrees are equal; one
back-turning pair per edge comes off). A bound from BELOW, so the shares
computed from it cannot read high. Backward costs twice the forward's
products; bytes are counted as ``work/EGNN.py`` counts them.
"""

from .PNA import chain, chain_params, head_dims, mm


def layer_widths(arch, input_dim):
    """(input width, internal width, output width) of each conv layer: the
    internal width is the input's unless that is 1 (``DIMEStack.get_conv``)."""
    h = arch["hidden_dim"]
    out = []
    for i in range(arch["num_conv_layers"]):
        f = input_dim if i == 0 else h
        out.append((f, h if f == 1 else f, h))
    return out


def _sizes(arch):
    return (arch["num_radial"], arch["num_spherical"] * arch["num_radial"],
            arch["basis_emb_size"], arch["int_emb_size"], arch["out_emb_size"],
            arch["num_before_skip"] + arch["num_after_skip"])


def triplets_at_least(nodes, edges):
    return max(edges * edges / max(nodes, 1) - edges, 0.0)


def parameters(arch, input_dim, out_dims):
    radial, sbf, basis, inner, out_emb, residuals = _sizes(arch)
    n = 0
    for f, w, h in layer_widths(arch, input_dim):
        n += radial  # the Bessel layer's frequencies
        n += (f * w + w) + (radial * w + w) + (3 * w * w + w)  # lin, embedding
        n += radial * basis + basis * w + sbf * basis + basis * inner
        n += 2 * (w * w + w) + w * inner + inner * w  # ji, kj, down, up
        n += (2 * residuals + 1) * (w * w + w)  # residual layers, int_lin
        n += radial * w + w * out_emb + (out_emb * out_emb + out_emb) + out_emb * h
    return n + sum(chain_params(d) for d in head_dims(arch, out_dims))


def required(arch, input_dim, out_dims, nodes, edges, graphs, steps,
             act_bytes=2):
    """{"flops", "bytes"} of forward + backward + optimizer over ``steps``
    steps that together see ``nodes`` atoms, ``edges`` edges, ``graphs``
    graphs."""
    radial, sbf, basis, inner, out_emb, residuals = _sizes(arch)
    spherical = arch["num_spherical"]
    triplets = triplets_at_least(nodes, edges)
    products, traffic = 0.0, 0.0
    # the bases, once a step: a distance, R sines and S * R Bessel values
    # an edge; a cross, a dot, a root and S Legendre values a triplet
    elementwise = edges * (12.0 + 4 * radial + 8 * sbf) + triplets * (
        30.0 + 5 * spherical)
    for f, w, h in layer_widths(arch, input_dim):
        products += mm(nodes, f, w) + 2 * mm(nodes, w, w)
        products += mm(edges, radial, w) + mm(edges, w, w)  # r, its share of emb_lin
        products += mm(edges, radial, w)  # the composed radial projection
        products += 2 * mm(edges, w, w) + mm(edges, w, inner)  # ji, kj, down
        products += mm(edges, sbf, inner) + 2.0 * triplets * spherical * inner
        products += mm(edges, inner, w)  # up
        products += (2 * residuals + 1) * mm(edges, w, w)
        products += mm(edges, radial, w)  # the output block's radial gate
        products += mm(nodes, w, out_emb) + mm(nodes, out_emb, out_emb)
        products += mm(nodes, out_emb, h)
        # per edge: 8 + 2 x residuals SiLUs (4 operations each), 3 products
        # with a radial term, the skips' adds, the sum at the receiver
        elementwise += edges * ((36.0 + 10 * residuals) * w + 5 * inner)
        elementwise += edges * spherical * inner  # m = radw * x_kj
        elementwise += nodes * (4.0 * out_emb + h)
        traffic += act_bytes * nodes * (f + h) * 3 + 8.0 * edges * 2
    traffic += 4.0 * nodes * 3 * 3
    shared, own, node = head_dims(arch, out_dims)
    products += chain(graphs, shared) + chain(graphs, own) + chain(nodes, node)
    elementwise += nodes * arch["hidden_dim"]
    params = parameters(arch, input_dim, out_dims)
    elementwise += 12.0 * params * steps / 2.0
    traffic += 4.0 * 7 * params * steps
    return {"flops": 3.0 * products + 2.0 * elementwise, "bytes": traffic}
