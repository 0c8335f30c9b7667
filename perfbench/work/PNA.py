"""Operations and bytes one epoch of PNA training REQUIRES, from the
batch's real node, edge and graph counts and the configuration's widths:
not the compiler's count, no padding, no recomputation.

The algorithm counted is the cheapest exact form: the message
``W [x_i ; x_j] + b`` as two node-level products, then per edge one add and
the four aggregations; backward costs twice the forward's products.
"""


def mm(rows, fan_in, fan_out):
    return 2.0 * rows * fan_in * fan_out


def head_dims(arch, out_dims):
    h = arch["hidden_dim"]
    g, nd = arch["output_heads"]["graph"], arch["output_heads"]["node"]
    shared = [h] + [g["dim_sharedlayers"]] * g["num_sharedlayers"]
    own = [shared[-1]] + list(g["dim_headlayers"][: g["num_headlayers"]]) + [out_dims[0]]
    node = [h] + list(nd["dim_headlayers"]) + [out_dims[1]]
    return shared, own, node


def chain(rows, dims):
    return sum(mm(rows, a, b) for a, b in zip(dims[:-1], dims[1:]))


def chain_params(dims):
    return sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))


def layer_widths(arch, input_dim):
    h = arch["hidden_dim"]
    return [(input_dim if i == 0 else h, h) for i in range(arch["num_conv_layers"])]


def parameters(arch, input_dim, out_dims):
    n = 0
    for f, h in layer_widths(arch, input_dim):
        n += (2 * f * f + f) + (17 * f * h + h) + (h * h + h) + 2 * h
    return n + sum(chain_params(d) for d in head_dims(arch, out_dims))


def required(arch, input_dim, out_dims, nodes, edges, graphs, steps,
             act_bytes=2):
    """{"flops", "bytes"} of forward + backward + optimizer over ``steps``
    steps that together see ``nodes`` atoms, ``edges`` edges, ``graphs``
    graphs."""
    products, elementwise, traffic = 0.0, 0.0, 0.0
    for f, h in layer_widths(arch, input_dim):
        products += 2 * mm(nodes, f, f) + mm(nodes, 17 * f, h) + mm(nodes, h, h)
        # per edge and feature: add, sum, square+sum, min, max
        elementwise += 6.0 * edges * f
        # per node: 4 scalers over 4 aggregates, std, batch norm, relu
        elementwise += nodes * (16 * 3 * f + 6 * f + 8 * h)
        # a layer's input read and output written, forward; both read and
        # the input's gradient written, backward; two indices per edge
        traffic += act_bytes * nodes * (f + h) * 3 + 8.0 * edges * 2
    shared, own, node = head_dims(arch, out_dims)
    products += chain(graphs, shared) + chain(graphs, own) + chain(nodes, node)
    elementwise += nodes * arch["hidden_dim"]  # pooling
    params = parameters(arch, input_dim, out_dims)
    # AdamW: read p, g, m, v; write p, m, v; ~12 operations a parameter
    elementwise += 12.0 * params * steps / 2.0
    traffic += 4.0 * 7 * params * steps
    return {"flops": 3.0 * products + 2.0 * elementwise, "bytes": traffic}
