"""Seeded-trajectory numerics parity vs eager PyTorch (round-4 verdict
item 9): the strongest real-data-free numerics evidence available in this
container.

A tiny SchNet energy+forces multi-head model (north-star config 2's shape:
graph energy head + 3-dim node forces head) is trained for a few hundred
AdamW steps TWICE from the SAME weights on the SAME batch — once through
this framework's jitted train step, once through an eager-PyTorch
re-implementation of the identical math (reference execution style:
per-op dispatch, index_add_ scatters — ``hydragnn/models/SCFStack.py``,
``train/train_validate_test.py``). Weights are copied jax -> torch, so any
divergence is numerics, not initialization. Losses must agree per step to
float32 tolerance, with only slow drift from differing contraction orders.
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from hydragnn_tpu.graph import collate_graphs, pad_sizes_for
from hydragnn_tpu.models import create_model_config, init_model_params
from hydragnn_tpu.train.optimizer import select_optimizer

HIDDEN = 16
# unequal widths: the factory honours each by its name (HydraGNN swaps them
# positionally, create.py:228-247; docs/MIGRATION.md), as the torch side does
NFILTERS = 16
NGAUSSIANS = 12
CUTOFF = 2.0
STEPS = 200


def _arch():
    return {
        "model_type": "SchNet",
        "input_dim": 1,
        "hidden_dim": HIDDEN,
        "output_dim": [1, 3],
        "output_type": ["graph", "node"],
        "output_heads": {
            "graph": {
                "num_sharedlayers": 2,
                "dim_sharedlayers": 8,
                "num_headlayers": 2,
                "dim_headlayers": [8, 8],
            },
            "node": {
                "num_headlayers": 2,
                "dim_headlayers": [8, 8],
                "type": "mlp",
            },
        },
        "task_weights": [1.0, 1.0],
        "num_conv_layers": 2,
        "num_nodes": 8,
        "edge_dim": None,
        "num_gaussians": NGAUSSIANS,
        "num_filters": NFILTERS,
        "radius": CUTOFF,
        "equivariance": False,
        "max_neighbours": 10,
    }


def _samples(num=6):
    rng = np.random.default_rng(11)

    class S:
        pass

    out = []
    for _ in range(num):
        n = int(rng.integers(4, 9))
        s = S()
        s.x = rng.random((n, 1)).astype(np.float32)
        s.pos = (rng.random((n, 3)) * 1.2).astype(np.float32)
        src = np.repeat(np.arange(n), 2)
        dst = (src + rng.integers(1, n, src.shape[0])) % n
        s.edge_index = np.stack(
            [np.concatenate([src, dst]), np.concatenate([dst, src])]
        ).astype(np.int64)
        s.edge_attr = None
        # energy: sum of features; forces: smooth function of geometry
        center = s.pos - s.pos.mean(0)
        s.targets = [
            np.array([s.x.sum()], np.float32),
            (0.3 * center * s.x).astype(np.float32),
        ]
        out.append(s)
    return out


def _jax_losses(samples, steps):
    batch = collate_graphs(
        samples,
        *pad_sizes_for(8, 32, len(samples)),
        head_types=("graph", "node"),
        head_dims=(1, 3),
    )
    batch = jax.tree_util.tree_map(jnp.asarray, batch)
    model = create_model_config(_arch())
    variables = init_model_params(model, batch)
    params = variables["params"]
    opt = select_optimizer(
        {"Optimizer": {"type": "AdamW", "learning_rate": 1e-3}}
    )
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state):
        def loss_fn(p):
            outputs = model.apply({"params": p}, batch, train=False)
            tot, _ = model.loss(outputs, batch)
            return tot

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return loss, optax.apply_updates(params, updates), opt_state

    losses = []
    for _ in range(steps):
        loss, params, opt_state = step(params, opt_state)
        losses.append(float(loss))
    return variables, np.asarray(losses)


def _torch_losses(variables, samples, steps):
    import torch

    p = jax.tree_util.tree_map(
        lambda a: torch.tensor(np.asarray(a)), variables["params"]
    )
    xs, eis, gids, y_g, y_n, poss = [], [], [], [], [], []
    off = 0
    for g, s in enumerate(samples):
        xs.append(s.x)
        poss.append(s.pos)
        eis.append(s.edge_index + off)
        gids.append(np.full(s.x.shape[0], g))
        y_g.append(s.targets[0])
        y_n.append(s.targets[1])
        off += s.x.shape[0]
    x0 = torch.tensor(np.concatenate(xs))
    pos = torch.tensor(np.concatenate(poss))
    ei = torch.tensor(np.concatenate(eis, axis=1))
    gid = torch.tensor(np.concatenate(gids), dtype=torch.long)
    yg = torch.tensor(np.stack(y_g))
    yn = torch.tensor(np.concatenate(y_n))
    N, G = x0.shape[0], len(samples)
    send, recv = ei[0], ei[1]

    offset = torch.linspace(0.0, CUTOFF, NGAUSSIANS)
    coeff = -0.5 / float(offset[1] - offset[0]) ** 2

    leaves = []

    def P(a):
        t = a.clone().detach().requires_grad_(True)
        leaves.append(t)
        return t

    convs = []
    for i in range(2):
        c = {k: v for k, v in p[f"encoder_conv_{i}"].items()}
        convs.append(
            {
                "f0k": P(c["filter_0"]["kernel"]),
                "f0b": P(c["filter_0"]["bias"]),
                "f1k": P(c["filter_1"]["kernel"]),
                "f1b": P(c["filter_1"]["bias"]),
                "lin1": P(c["lin1"]),
                "lin2": P(c["lin2"]),
                "bias2": P(c["bias2"]),
            }
        )
    gs = [
        (P(p["graph_shared"][f"TorchLinear_{i}"]["kernel"]),
         P(p["graph_shared"][f"TorchLinear_{i}"]["bias"]))
        for i in range(2)
    ]
    hg = [
        (P(p["head_0_graph"][f"TorchLinear_{i}"]["kernel"]),
         P(p["head_0_graph"][f"TorchLinear_{i}"]["bias"]))
        for i in range(3)
    ]
    hn = [
        (P(p["head_1_node"][f"kernel_{i}"][0]),
         P(p["head_1_node"][f"bias_{i}"][0]))
        for i in range(3)
    ]

    def ssp(v):
        return torch.nn.functional.softplus(v) - math.log(2.0)

    def forward():
        h = x0
        for c in convs:
            d = pos[send] - pos[recv]
            ew = d.pow(2).sum(-1).sqrt()
            ea = torch.exp(coeff * (ew[:, None] - offset) ** 2)
            w = ssp(ea @ c["f0k"] + c["f0b"]) @ c["f1k"] + c["f1b"]
            w = w * (0.5 * (torch.cos(ew * math.pi / CUTOFF) + 1.0))[:, None]
            hh = h @ c["lin1"]
            aggr = torch.zeros(N, w.shape[1]).index_add_(
                0, recv, hh[send] * w
            )
            h = torch.relu(aggr @ c["lin2"] + c["bias2"])
        cnt = torch.zeros(G).index_add_(0, gid, torch.ones(N))
        pooled = torch.zeros(G, HIDDEN).index_add_(0, gid, h) / cnt[:, None]
        sg = pooled
        for k, b in gs:
            sg = torch.relu(sg @ k + b)
        og = sg
        for i, (k, b) in enumerate(hg):
            og = og @ k + b
            if i < 2:
                og = torch.relu(og)
        on = h
        for i, (k, b) in enumerate(hn):
            on = on @ k + b
            if i < 2:
                on = torch.relu(on)
        return og, on

    opt = torch.optim.AdamW(leaves, lr=1e-3, eps=1e-8, weight_decay=0.01)
    losses = []
    for _ in range(steps):
        opt.zero_grad()
        og, on = forward()
        loss = 0.5 * torch.nn.functional.mse_loss(og, yg) + \
            0.5 * torch.nn.functional.mse_loss(on, yn)
        loss.backward()
        opt.step()
        losses.append(float(loss))
    return np.asarray(losses)


def pytest_schnet_seeded_trajectory_matches_torch():
    samples = _samples()
    variables, ours = _jax_losses(samples, STEPS)
    theirs = _torch_losses(variables, samples, STEPS)
    # identical math, different contraction order: tight at the start,
    # bounded slow drift over hundreds of steps
    rel = np.abs(ours - theirs) / np.maximum(np.abs(theirs), 1e-8)
    assert rel[:20].max() < 1e-4, f"early divergence: {rel[:20].max()}"
    assert rel.max() < 5e-3, f"trajectory drift: {rel.max()} at {rel.argmax()}"
    # and the trajectory actually trains (not a frozen fixed point)
    assert ours[-1] < 0.5 * ours[0]


# ---- EGNN (north-star config 4's model: equivariant coord channel) ------

EG_IN = 4  # [z-like, centered coords] — the MPtrj feature layout


def _egnn_arch():
    return {
        "model_type": "EGNN",
        "input_dim": EG_IN,
        "hidden_dim": HIDDEN,
        "output_dim": [1, 3],
        "output_type": ["graph", "node"],
        "output_heads": {
            "graph": {
                "num_sharedlayers": 2,
                "dim_sharedlayers": 8,
                "num_headlayers": 2,
                "dim_headlayers": [8, 8],
            },
            "node": {
                "num_headlayers": 2,
                "dim_headlayers": [8, 8],
                "type": "mlp",
            },
        },
        "task_weights": [1.0, 1.0],
        "num_conv_layers": 2,
        "num_nodes": 8,
        "edge_dim": None,
        "radius": CUTOFF,
        "equivariance": True,
        "max_neighbours": 10,
    }


def _egnn_samples(num=6):
    rng = np.random.default_rng(23)

    class S:
        pass

    out = []
    for _ in range(num):
        n = int(rng.integers(4, 9))
        s = S()
        pos = (rng.random((n, 3)) * 1.2).astype(np.float32)
        center = pos - pos.mean(0)
        s.pos = pos
        s.x = np.concatenate(
            [rng.random((n, 1)).astype(np.float32), center], axis=1
        )
        src = np.repeat(np.arange(n), 2)
        dst = (src + rng.integers(1, n, src.shape[0])) % n
        s.edge_index = np.stack(
            [np.concatenate([src, dst]), np.concatenate([dst, src])]
        ).astype(np.int64)
        s.edge_attr = None
        s.targets = [
            np.array([s.x[:, 0].sum()], np.float32),
            (0.3 * center * s.x[:, :1]).astype(np.float32),
        ]
        out.append(s)
    return out


def _egnn_jax_losses(samples, steps):
    batch = collate_graphs(
        samples,
        *pad_sizes_for(8, 32, len(samples)),
        head_types=("graph", "node"),
        head_dims=(1, 3),
    )
    batch = jax.tree_util.tree_map(jnp.asarray, batch)
    model = create_model_config(_egnn_arch())
    variables = init_model_params(model, batch)
    params = variables["params"]
    opt = select_optimizer(
        {"Optimizer": {"type": "AdamW", "learning_rate": 1e-3}}
    )
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state):
        def loss_fn(p):
            outputs = model.apply({"params": p}, batch, train=False)
            tot, _ = model.loss(outputs, batch)
            return tot

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return loss, optax.apply_updates(params, updates), opt_state

    losses = []
    for _ in range(steps):
        loss, params, opt_state = step(params, opt_state)
        losses.append(float(loss))
    return variables, np.asarray(losses)


def _egnn_torch_losses(variables, samples, steps):
    """Eager re-implementation of the E_GCL math in the reference's
    execution style (per-op dispatch, index_add_ scatters at the SENDER
    index — ``hydragnn/models/EGCLStack.py:116-236``): edge MLP on
    [h_row, h_col, ||dx||^2] as ONE concat matmul (the framework's
    SplitLinear is parameter-identical to it), tanh-bounded coord update
    with mean-by-count, coord channel gated off on the last layer."""
    import torch

    p = jax.tree_util.tree_map(
        lambda a: torch.tensor(np.asarray(a)), variables["params"]
    )
    xs, eis, gids, y_g, y_n, poss = [], [], [], [], [], []
    off = 0
    for g, s in enumerate(samples):
        xs.append(s.x)
        poss.append(s.pos)
        eis.append(s.edge_index + off)
        gids.append(np.full(s.x.shape[0], g))
        y_g.append(s.targets[0])
        y_n.append(s.targets[1])
        off += s.x.shape[0]
    x0 = torch.tensor(np.concatenate(xs))
    pos0 = torch.tensor(np.concatenate(poss))
    ei = torch.tensor(np.concatenate(eis, axis=1))
    gid = torch.tensor(np.concatenate(gids), dtype=torch.long)
    yg = torch.tensor(np.stack(y_g))
    yn = torch.tensor(np.concatenate(y_n))
    N, G = x0.shape[0], len(samples)
    row, col = ei[0], ei[1]  # sender, receiver (aggregation at row)

    leaves = []

    def P(a):
        t = a.clone().detach().requires_grad_(True)
        leaves.append(t)
        return t

    convs = []
    for i in range(2):
        c = p[f"encoder_conv_{i}"]
        convs.append(
            {
                "e0k": P(c["edge_mlp_0"]["kernel"]),
                "e0b": P(c["edge_mlp_0"]["bias"]),
                "e1k": P(c["edge_mlp_1"]["kernel"]),
                "e1b": P(c["edge_mlp_1"]["bias"]),
                "c0k": P(c["coord_mlp_0"]["kernel"]) if "coord_mlp_0" in c else None,
                "c0b": P(c["coord_mlp_0"]["bias"]) if "coord_mlp_0" in c else None,
                "c1": P(c["coord_mlp_1"]) if "coord_mlp_1" in c else None,
                "n0k": P(c["node_mlp_0"]["kernel"]),
                "n0b": P(c["node_mlp_0"]["bias"]),
                "n1k": P(c["node_mlp_1"]["kernel"]),
                "n1b": P(c["node_mlp_1"]["bias"]),
            }
        )
    gs = [
        (P(p["graph_shared"][f"TorchLinear_{i}"]["kernel"]),
         P(p["graph_shared"][f"TorchLinear_{i}"]["bias"]))
        for i in range(2)
    ]
    hg = [
        (P(p["head_0_graph"][f"TorchLinear_{i}"]["kernel"]),
         P(p["head_0_graph"][f"TorchLinear_{i}"]["bias"]))
        for i in range(3)
    ]
    hn = [
        (P(p["head_1_node"][f"kernel_{i}"][0]),
         P(p["head_1_node"][f"bias_{i}"][0]))
        for i in range(3)
    ]

    def forward():
        h, pos = x0, pos0
        for li, c in enumerate(convs):
            d = pos[row] - pos[col]
            radial = d.pow(2).sum(-1, keepdim=True)
            unit = d / (radial.sqrt() + 1.0)  # norm_diff=True
            e = torch.cat([h[row], h[col], radial], dim=-1) @ c["e0k"] + c["e0b"]
            e = torch.relu(e)
            e = torch.relu(e @ c["e1k"] + c["e1b"])
            equivariant = li < len(convs) - 1
            if equivariant:
                cw = torch.relu(e @ c["c0k"] + c["c0b"]) @ c["c1"]
                trans = torch.clamp(unit * torch.tanh(cw), -100.0, 100.0)
                coord_agg = torch.zeros(N, 3).index_add_(0, row, trans)
                cnt = torch.zeros(N).index_add_(
                    0, row, torch.ones(row.shape[0])
                )
                pos = pos + coord_agg / torch.clamp(cnt, min=1.0)[:, None]
            agg = torch.zeros(N, e.shape[1]).index_add_(0, row, e)
            hcat = torch.cat([h, agg], dim=-1)
            h = torch.relu(hcat @ c["n0k"] + c["n0b"]) @ c["n1k"] + c["n1b"]
            # the stack relu's every conv output (Base.py:289-302 parity;
            # base.py `x = act(c)` — EGNN skips BatchNorm, not activation)
            h = torch.relu(h)
        cnt = torch.zeros(G).index_add_(0, gid, torch.ones(N))
        pooled = torch.zeros(G, HIDDEN).index_add_(0, gid, h) / cnt[:, None]
        sg = pooled
        for k, b in gs:
            sg = torch.relu(sg @ k + b)
        og = sg
        for i, (k, b) in enumerate(hg):
            og = og @ k + b
            if i < 2:
                og = torch.relu(og)
        on = h
        for i, (k, b) in enumerate(hn):
            on = on @ k + b
            if i < 2:
                on = torch.relu(on)
        return og, on

    opt = torch.optim.AdamW(
        [t for t in leaves if t is not None],
        lr=1e-3, eps=1e-8, weight_decay=0.01,
    )
    losses = []
    for _ in range(steps):
        opt.zero_grad()
        og, on = forward()
        loss = 0.5 * torch.nn.functional.mse_loss(og, yg) + \
            0.5 * torch.nn.functional.mse_loss(on, yn)
        loss.backward()
        opt.step()
        losses.append(float(loss))
    return np.asarray(losses)


def pytest_egnn_seeded_trajectory_matches_torch():
    """Second parity anchor: the EQUIVARIANT stack (coord updates feed the
    next layer's geometry, so any divergence compounds through pos)."""
    samples = _egnn_samples()
    variables, ours = _egnn_jax_losses(samples, STEPS)
    theirs = _egnn_torch_losses(variables, samples, STEPS)
    rel = np.abs(ours - theirs) / np.maximum(np.abs(theirs), 1e-8)
    assert rel[:20].max() < 1e-4, f"early divergence: {rel[:20].max()}"
    assert rel.max() < 5e-3, f"trajectory drift: {rel.max()} at {rel.argmax()}"
    assert ours[-1] < 0.5 * ours[0]
