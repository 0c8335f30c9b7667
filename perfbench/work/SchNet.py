"""Operations and bytes one epoch of SchNet training REQUIRES (the
interaction block of ``reference/SchNet.py``), from the batch's real node,
edge and graph counts and the configuration's widths: not the compiler's
count, no padding, no recomputation.

The algorithm counted is the cheapest exact form of each interaction:

- per real edge the filter network, ``num_gaussians x num_filters`` and
  ``num_filters x num_filters`` products, and the weighted neighbour sum,
  ``num_filters`` multiply-adds;
- per atom ``W_1`` (hidden -> filters), ``W_2`` (filters -> hidden) and
  ``W_3`` (hidden -> hidden); backward costs twice the forward's products;
- the geometry (distance, 200 Gaussians, envelope) once a step, as
  elementwise work: it holds no parameter and positions do not move;
- bytes: each node table read and written once forward, read again and its
  gradient written backward (the interaction's input, ``W_1 h``, ``m``,
  ``W_2 m`` and its output); per real edge the gathered ``W_1 h_j`` row read
  once forward, read once and its cotangent added once backward, its two
  indices and its distance read forward and backward: the filter network
  and the expansion computed where the row is, so no ``[E, filters]`` or
  ``[E, gaussians]`` table crosses HBM. A form that stores them (every
  form XLA makes today) pays more; none pays less.

The embedding, the heads, the loss and AdamW are counted as ``work/PNA.py``
counts them.
"""

from .PNA import chain, chain_params, head_dims, mm


def parameters(arch, input_dim, out_dims):
    hidden, filters = arch["hidden_dim"], arch["num_filters"]
    gaussians = arch["num_gaussians"]
    per_layer = ((gaussians * filters + filters) + (filters * filters + filters)
                 + hidden * filters + (filters * hidden + hidden)
                 + (hidden * hidden + hidden))
    return (input_dim * hidden + arch["num_conv_layers"] * per_layer
            + sum(chain_params(d) for d in head_dims(arch, out_dims)))


def required(arch, input_dim, out_dims, nodes, edges, graphs, steps,
             act_bytes=2):
    """{"flops", "bytes"} of forward + backward + optimizer over ``steps``
    steps that together see ``nodes`` atoms, ``edges`` edges, ``graphs``
    graphs."""
    hidden, filters = arch["hidden_dim"], arch["num_filters"]
    gaussians = arch["num_gaussians"]
    products = mm(nodes, input_dim, hidden)  # the embedding
    # the difference, its square and sum, the root; per Gaussian a
    # difference, its square, the scale and the exponential; the envelope
    elementwise = edges * (8.0 + 4.0 * gaussians + 3.0)
    traffic = 4.0 * nodes * (input_dim + hidden) + 8.0 * edges * 2
    for _ in range(arch["num_conv_layers"]):
        products += mm(edges, gaussians, filters) + mm(edges, filters, filters)
        products += 2.0 * edges * filters  # the weighted neighbour sum
        products += (mm(nodes, hidden, filters) + mm(nodes, filters, hidden)
                     + mm(nodes, hidden, hidden))
        # per edge and filter: two biases, ssp (~3), the envelope
        elementwise += edges * 6.0 * filters
        # per atom: b_2, ssp, b_3 and the residual
        elementwise += nodes * 6.0 * hidden
        traffic += act_bytes * nodes * (3 * hidden + 2 * filters) * 3
        traffic += act_bytes * edges * filters * 3 + (8.0 + 4.0) * edges * 2
    shared, own, node = head_dims(arch, out_dims)
    products += chain(graphs, shared) + chain(graphs, own) + chain(nodes, node)
    elementwise += nodes * hidden  # pooling
    params = parameters(arch, input_dim, out_dims)
    # AdamW: read p, g, m, v; write p, m, v; ~12 operations a parameter
    elementwise += 12.0 * params * steps / 2.0
    traffic += 4.0 * 7 * params * steps
    return {"flops": 3.0 * products + 2.0 * elementwise, "bytes": traffic}
