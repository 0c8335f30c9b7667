"""Operations and bytes one epoch of EGNN training REQUIRES, from the
batch's real node, edge and graph counts and the configuration's widths:
not the compiler's count, no padding, no recomputation.

The algorithm counted is the cheapest exact form: the first edge-MLP layer
on [h_row ; h_col ; r2] as two node-level products plus a rank-one term,
then per edge the second edge layer and (all but the last layer) the
coordinate MLP; backward costs twice the forward's products.
"""

from .PNA import chain, chain_params, head_dims, mm


def layer_widths(arch, input_dim):
    h, depth = arch["hidden_dim"], arch["num_conv_layers"]
    return [
        (input_dim if i == 0 else h, h,
         bool(arch.get("equivariance")) and i < depth - 1)
        for i in range(depth)
    ]


def parameters(arch, input_dim, out_dims):
    n = 0
    for f, h, coord in layer_widths(arch, input_dim):
        n += ((2 * f + 1) * h + h) + (h * h + h) + ((f + h) * h + h) + (h * h + h)
        if coord:
            n += (h * h + h) + h
    return n + sum(chain_params(d) for d in head_dims(arch, out_dims))


def required(arch, input_dim, out_dims, nodes, edges, graphs, steps,
             act_bytes=2):
    """{"flops", "bytes"} of forward + backward + optimizer over ``steps``
    steps that together see ``nodes`` atoms, ``edges`` edges, ``graphs``
    graphs."""
    products, elementwise, traffic = 0.0, 0.0, 0.0
    for f, h, coord in layer_widths(arch, input_dim):
        products += 2 * mm(nodes, f, h) + mm(edges, h, h)
        products += mm(nodes, f + h, h) + mm(nodes, h, h)
        # per edge: distance, two adds and the radial term, two relus, sum
        elementwise += edges * (12 + 6.0 * h)
        if coord:
            products += mm(edges, h, h) + mm(edges, h, 1)
            elementwise += edges * (2.0 * h + 16)
        elementwise += nodes * 3.0 * h
        traffic += act_bytes * nodes * (f + h) * 3 + 4.0 * nodes * 3 * 3
        traffic += 8.0 * edges * 2
    shared, own, node = head_dims(arch, out_dims)
    products += chain(graphs, shared) + chain(graphs, own) + chain(nodes, node)
    elementwise += nodes * arch["hidden_dim"]
    params = parameters(arch, input_dim, out_dims)
    elementwise += 12.0 * params * steps / 2.0
    traffic += 4.0 * 7 * params * steps
    return {"flops": 3.0 * products + 2.0 * elementwise, "bytes": traffic}
