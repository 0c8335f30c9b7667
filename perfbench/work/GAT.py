"""Operations and bytes one epoch of GATv2 training REQUIRES (HydraGNN's
``GATStack`` wiring: ``reference/GAT.py``), from the batch's real node, edge
and graph counts and the configuration's widths: not the compiler's count,
no padding, no recomputation.

The algorithm counted is the cheapest exact form of each layer:

- two node-level projections (``W_l``, ``W_r``), the first layer's from
  ``input_dim``, every later one from ``heads x hidden_dim``;
- per attended row (every real edge and one self-loop an atom) the ``a``
  contraction of the score, ``2 x heads x hidden_dim`` operations, and as
  many for the weighted sum; backward costs twice the forward's, as for the
  node-level products;
- bytes: each node table read and written once forward, read again and its
  gradient written backward (the layer's input and output, and the two
  projected tables between them); each attended row's ``x_l`` row read once
  forward (scores, softmax and weighted sum in ONE pass over a receiver's
  rows, the softmax kept running), and read once and its cotangent added
  once backward. A form that makes a pass for the scores and another for
  the sum pays more; none pays less.

BatchNorm, heads, loss and AdamW are counted as ``work/PNA.py`` counts
them. At this cell's widths the bytes bound the least time, not the
operations (``step_roofline_pct.train`` says which on stderr).
"""

from .PNA import chain, chain_params, head_dims, mm


def layer_widths(arch, input_dim):
    """(input width, projected width, output width) of each conv layer:
    hidden layers concatenate the heads, the last one averages them."""
    hidden, depth = arch["hidden_dim"], arch["num_conv_layers"]
    wide = arch["heads"] * hidden
    return [(input_dim if i == 0 else wide, wide,
             wide if i < depth - 1 else hidden) for i in range(depth)]


def parameters(arch, input_dim, out_dims):
    n = 0
    for f, wide, out in layer_widths(arch, input_dim):
        # W_l, b_l, W_r, b_r, a, the output bias, BatchNorm's two
        n += 2 * (f * wide + wide) + wide + out + 2 * out
    return n + sum(chain_params(d) for d in head_dims(arch, out_dims))


def required(arch, input_dim, out_dims, nodes, edges, graphs, steps,
             act_bytes=2):
    """{"flops", "bytes"} of forward + backward + optimizer over ``steps``
    steps that together see ``nodes`` atoms, ``edges`` edges, ``graphs``
    graphs."""
    heads = arch["heads"]
    rows = edges + nodes  # what the softmax is over: edges and self-loops
    products, elementwise, traffic = 0.0, 0.0, 0.0
    for f, wide, out in layer_widths(arch, input_dim):
        products += 2 * mm(nodes, f, wide)
        products += 2 * (2.0 * rows * wide)  # the score's a, the weighted sum
        # per row and feature: the add and the LeakyReLU; per row and head:
        # the running maximum, the exponential, the sum, the division
        elementwise += rows * (2.0 * wide + 5.0 * heads)
        # per node: the heads' mean or nothing, batch norm, the activation
        elementwise += nodes * ((wide - out) + 8.0 * out + 2.0 * out)
        traffic += act_bytes * nodes * (f + out + 2 * wide) * 3
        traffic += act_bytes * rows * wide * 3 + 8.0 * edges * 2
    shared, own, node = head_dims(arch, out_dims)
    products += chain(graphs, shared) + chain(graphs, own) + chain(nodes, node)
    elementwise += nodes * arch["hidden_dim"]  # pooling
    params = parameters(arch, input_dim, out_dims)
    # AdamW: read p, g, m, v; write p, m, v; ~12 operations a parameter
    elementwise += 12.0 * params * steps / 2.0
    traffic += 4.0 * 7 * params * steps
    return {"flops": 3.0 * products + 2.0 * elementwise, "bytes": traffic}
