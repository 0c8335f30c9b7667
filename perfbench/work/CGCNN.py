"""Operations and bytes one epoch of CGCNN training REQUIRES (the published
block of ``reference/CGCNN.py``), from the batch's real node, edge and graph
counts and the configuration's widths: not the compiler's count, no
padding, no recomputation.

The algorithm counted is the cheapest exact form of each conv, whatever
form the program runs, so that no later change of form can read over 100%:

- ``W [h_i ; h_j ; g] + b`` split by linearity: per atom ``W_i`` and
  ``W_j`` (hidden -> 2 x hidden), per real edge ``W_g`` (Gaussians ->
  2 x hidden) and the three-way add;
- per real edge and column the BatchNorm's two passes (its statistics,
  then the normalisation), the gate (sigmoid, softplus, their product)
  and the neighbour sum; per atom the LayerNorm, the residual and its
  softplus; backward costs twice the forward's products;
- the geometry (distance, the Gaussians) once a step, as elementwise work:
  it holds no parameter and positions do not move;
- bytes: each node table read and written once forward, read again and its
  gradient written backward (the conv's input, ``W_i h``, ``W_j h``, ``m``,
  the output); per real edge the gathered ``(W_j h)_j`` row read twice
  forward (the statistics, then the message: the BatchNorm's batch-wide
  reduction sits between them), twice backward (the two sums of the
  BatchNorm's backward, then the cotangent) and its cotangent added once,
  with its two indices and its distance read on each of those passes; the
  Gaussians, the pre-activation and the gate computed where the row is, so
  no ``[E, 2 x hidden]`` table crosses HBM. A form that stores one (every
  form XLA makes today) pays more; none pays less.

The embedding, the heads, the loss and AdamW are counted as ``work/PNA.py``
counts them.
"""

from .PNA import chain, chain_params, head_dims, mm


def parameters(arch, input_dim, out_dims):
    hidden, gaussians = arch["hidden_dim"], arch["num_gaussians"]
    wide = 2 * hidden
    per_layer = (2 * hidden + gaussians) * wide + wide + 2 * wide + 2 * hidden
    return (input_dim * hidden + hidden + arch["num_conv_layers"] * per_layer
            + sum(chain_params(d) for d in head_dims(arch, out_dims)))


def required(arch, input_dim, out_dims, nodes, edges, graphs, steps,
             act_bytes=2):
    """{"flops", "bytes"} of forward + backward + optimizer over ``steps``
    steps that together see ``nodes`` atoms, ``edges`` edges, ``graphs``
    graphs."""
    hidden, gaussians = arch["hidden_dim"], arch["num_gaussians"]
    wide = 2 * hidden
    products = mm(nodes, input_dim, hidden)  # the embedding
    # the difference, its square and sum, the root; per Gaussian a
    # difference, its square, the scale and the exponential
    elementwise = edges * (8.0 + 4.0 * gaussians)
    traffic = 4.0 * nodes * (input_dim + hidden) + 8.0 * edges * 2
    for _ in range(arch["num_conv_layers"]):
        products += 2 * mm(nodes, hidden, wide) + mm(edges, gaussians, wide)
        # per edge and column: the three-way add and the bias (3), the
        # statistics (4), the normalisation (4); per edge and channel the
        # gate (sigmoid ~4, softplus ~3, product 1) and the sum (1)
        elementwise += edges * (11.0 * wide + 9.0 * hidden)
        # per atom and channel: the LayerNorm (~8), residual, softplus (~3)
        elementwise += nodes * 12.0 * hidden
        traffic += act_bytes * nodes * (hidden + 2 * wide + 2 * hidden) * 3
        traffic += act_bytes * edges * wide * 5 + (8.0 + 4.0) * edges * 4
    shared, own, node = head_dims(arch, out_dims)
    products += chain(graphs, shared) + chain(graphs, own) + chain(nodes, node)
    elementwise += nodes * hidden  # pooling
    params = parameters(arch, input_dim, out_dims)
    # AdamW: read p, g, m, v; write p, m, v; ~12 operations a parameter
    elementwise += 12.0 * params * steps / 2.0
    traffic += 4.0 * 7 * params * steps
    return {"flops": 3.0 * products + 2.0 * elementwise, "bytes": traffic}
