"""The block-local neighbour gather (``ops/local_gather.py``) against the
indexed read it replaces, and the rule that selects it.

The kernels run in the Pallas INTERPRETER here, asked for by name
(``interpret=True`` on the kernels, ``pltpu.force_tpu_interpret_mode()``
around a whole model): on this CPU the rule itself selects XLA's gather,
and a test that wants the product says ``tpu`` through
``dense_agg._backend``.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hydragnn_tpu.data.dataobj import GraphData
from hydragnn_tpu.data.loaders import collate_for_layout, compute_layout
from hydragnn_tpu.ops import dense_agg as da
from hydragnn_tpu.ops import local_gather as lg

BF16_ULP = 2.0 ** -7  # spacing of bf16 just under 2


def _graph(n, rng, degree=5):
    """A random graph of ``n`` nodes; senders anywhere in the graph, so
    the farthest possible neighbour (n - 1 rows away) does occur."""
    recv = np.repeat(np.arange(n), rng.integers(0, degree + 1, n))
    send = rng.integers(0, n, recv.shape[0])
    if n > 1:  # first and last row name each other: the reach is met
        send = np.concatenate([send, [n - 1, 0]])
        recv = np.concatenate([recv, [0, n - 1]])
    d = GraphData(
        x=rng.random((n, 2)).astype(np.float32),
        pos=rng.random((n, 3)).astype(np.float32),
        edge_index=np.stack([send, recv]).astype(np.int64),
    )
    d.targets = [np.asarray([1.0], np.float32)]
    d.target_types = ["graph"]
    return d


def _collated(sizes, seed=0, device_multiple=1):
    """One collated dense-list batch of graphs of ``sizes`` nodes, in that
    order, under the layout ``compute_layout`` gives them."""
    rng = np.random.default_rng(seed)
    samples = [_graph(n, rng) for n in sizes]
    layout = compute_layout(
        [samples], batch_size=len(samples), need_neighbors=True,
        device_multiple=device_multiple,
    )
    return collate_for_layout(samples, layout), layout


# the bound itself (129 -> h = 1, 225 -> h = 2), a graph straddling two
# blocks (rows 100..228 and the like), small ones, a ragged last block
_SIZES = {
    1: [100, 129, 3, 40, 129, 77, 128, 60],
    2: [90, 225, 17, 225, 130, 8],
}


def _interpreted(monkeypatch):
    """The rule sees a TPU; the kernels it selects run interpreted. The
    callers jit what they run under it, as a step program is: the
    interpreter's callbacks run JAX operations of their own, and beside a
    main thread that dispatches eager operations the two can wait for
    each other."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(da, "_backend", lambda: "tpu")
    return pltpu.force_tpu_interpret_mode()


def _rule_answers(monkeypatch):
    """The list ``window_halo``'s answers are appended to from here on."""
    taken = []
    halo = lg.window_halo
    monkeypatch.setattr(
        lg, "window_halo", lambda *a: taken.append(halo(*a)) or taken[-1]
    )
    return taken


@pytest.mark.parametrize("dim", [1, 64, 130])
@pytest.mark.parametrize("h", [1, 2])
def pytest_product_equals_indexed_read(h, dim):
    batch, layout = _collated(_SIZES[h])
    ex = batch.extras
    assert ex["nbr_reach"].shape == (layout.nbr_reach,)
    assert lg._cdiv(layout.nbr_reach - 1, lg.BLOCK) == h
    n = batch.x.shape[0]
    assert n % lg.BLOCK  # the last block is ragged
    rng = np.random.default_rng(dim)
    x = jnp.asarray(rng.standard_normal((n, dim)), jnp.bfloat16)
    idx, mask = jnp.asarray(ex["nbr_idx"]), jnp.asarray(ex["nbr_mask"])

    # forward: bit for bit on every real slot; padded slots read zero or
    # row 0, which every consumer masks
    out = lg.gather_product(x, idx, h, interpret=True).transpose(1, 0, 2)
    ref = x[idx]
    m = np.asarray(mask)[..., None]
    assert out.dtype == ref.dtype and out.shape == ref.shape
    np.testing.assert_array_equal(
        np.where(m, np.asarray(out, np.float32), 0),
        np.where(m, np.asarray(ref, np.float32), 0),
    )

    # backward: today's reverse-list gather, within one bf16 ulp (both
    # accumulate in f32 and cast once; only the order of the sum differs)
    g = jnp.asarray(rng.standard_normal(ref.shape), jnp.bfloat16)
    gx = lg.scatter_product(g.transpose(1, 0, 2), idx, mask, h, interpret=True)
    _, vjp = jax.vjp(
        lambda t: da._gather_xla(t, idx, ex["rev_idx"], ex["rev_mask"]), x
    )
    (gx_ref,) = vjp(g)
    assert gx.dtype == gx_ref.dtype and gx.shape == gx_ref.shape
    a, b = np.asarray(gx, np.float32), np.asarray(gx_ref, np.float32)
    scale = np.maximum(np.abs(b), 1.0)
    assert np.max(np.abs(a - b) / scale) <= BF16_ULP


def pytest_padded_slot_cotangents_are_not_read():
    """A consumer that forgets to mask leaves cotangent on padded slots
    (index 0): the product drops it, as the reverse list does."""
    batch, _ = _collated(_SIZES[1])
    ex = batch.extras
    idx, mask = jnp.asarray(ex["nbr_idx"]), jnp.asarray(ex["nbr_mask"])
    g = jnp.ones((idx.shape[1], idx.shape[0], 8), jnp.bfloat16)
    gx = lg.scatter_product(g, idx, mask, 1, interpret=True)
    out_degree = np.asarray(ex["rev_mask"]).sum(axis=1)
    np.testing.assert_array_equal(
        np.asarray(gx, np.float32)[:, 0], out_degree.astype(np.float32)
    )


# ---- f32 columns as three bf16 pieces ----------------------------------------


def _f32_values(kind, rng, n=50000):
    if kind == "normal":  # every piece a normal number: |x| >= 2**-102
        x = rng.uniform(1, 2, n) * 2.0 ** rng.integers(-102, 128, n)
        return (x * rng.choice([-1.0, 1.0], n)).astype(np.float32)
    if kind == "positions":  # what the columns hold: coordinates, updates
        return np.concatenate(
            [rng.standard_normal(n) * 30, rng.standard_normal(n) * 1e-4,
             [0.0, 1.0, -1.0, 100.0, np.float32(3.4e38)]]
        ).astype(np.float32)
    assert kind == "tiny"  # the low pieces fall under bf16's normal range
    return (rng.standard_normal(n) * 2.0 ** -115).astype(np.float32)


@pytest.mark.parametrize("kind", ["normal", "positions", "tiny"])
def pytest_three_pieces_rejoin(kind):
    """hi + mid + lo == x bit for bit wherever every piece is a normal
    number; below that (|x| < 2**-102: no coordinate) the low pieces are
    subnormal or flushed and the re-join is off by less than 2**-126."""
    x = _f32_values(kind, np.random.default_rng(7))
    pieces = jax.jit(lg.split_f32)(jnp.asarray(x).reshape(-1, 5))
    assert pieces.dtype == jnp.bfloat16 and pieces.shape == (x.size // 5, 15)
    back = np.asarray(jax.jit(lg.join_f32)(pieces)).reshape(-1)
    assert back.dtype == np.float32
    if kind == "tiny":
        assert np.max(np.abs(back - x)) < 2.0 ** -126
    else:
        np.testing.assert_array_equal(back.view(np.uint32), x.view(np.uint32))


def pytest_pieces_of_what_is_no_number():
    """A negative zero re-joins as zero; a non-finite value as NaN (its
    first remainder is inf - inf), never as a finite number."""
    x = jnp.asarray([[-0.0, np.inf, -np.inf, np.nan]], jnp.float32)
    back = np.asarray(lg.join_f32(lg.split_f32(x.T)))[:, 0]
    assert back[0] == 0.0 and np.isnan(back[1:]).all()


def _lists(h):
    batch, _ = _collated(_SIZES[h])
    ex = {k: jnp.asarray(v) for k, v in batch.extras.items()}
    n, k_in = ex["nbr_idx"].shape
    assert n % lg.BLOCK and ex["rev_idx"].shape[1] > k_in  # ragged; K_out > K_in
    assert not np.asarray(ex["nbr_mask"]).all()  # padded slots
    return batch, ex, n, k_in


@pytest.mark.parametrize("h", [1, 2])
def pytest_gathered_positions_equal_indexed_read(monkeypatch, h):
    """``gather_neighbors(x bf16, exact=pos f32)``: one bf16 table, the
    positions as pieces. Real slots of BOTH results equal the indexed read
    bit for bit; the positions' cotangent is an f32 sum, the table's one
    within a bf16 ulp of the reverse-list gather's."""
    batch, ex, n, k_in = _lists(h)
    silent = {k: v for k, v in ex.items() if k != "nbr_reach"}
    rng = np.random.default_rng(h)
    x = jnp.asarray(rng.standard_normal((n, 24)), jnp.bfloat16)
    pos = jnp.asarray(batch.pos) * 17.0 - 3.0
    m = np.asarray(ex["nbr_mask"])[..., None]
    g = (
        jnp.asarray(rng.standard_normal((n, k_in, 24)), jnp.bfloat16),
        jnp.asarray(rng.standard_normal((n, k_in, 3)) * m, jnp.float32),
    )

    def both_ways(lists):
        out, vjp = jax.vjp(
            lambda t, p: da.neighbor_rows(t, lists, exact=p), x, pos
        )
        return out, vjp(g)

    (ref_x, ref_p), (ref_gx, ref_gp) = jax.jit(both_ways)(silent)
    taken = _rule_answers(monkeypatch)
    with _interpreted(monkeypatch):
        (got_x, got_p), (got_gx, got_gp) = jax.jit(both_ways)(ex)
    assert taken == [h]
    assert got_x.dtype == jnp.bfloat16 and got_p.dtype == jnp.float32
    for got, ref in ((got_x, ref_x), (got_p, ref_p)):
        np.testing.assert_array_equal(
            np.where(m, np.asarray(got, np.float32), 0).view(np.uint32),
            np.where(m, np.asarray(ref, np.float32), 0).view(np.uint32),
        )
    np.testing.assert_array_equal(
        np.where(m, np.asarray(got_p), 0), np.where(m, pos[ex["nbr_idx"]], 0)
    )
    assert got_gx.dtype == jnp.bfloat16 and got_gp.dtype == jnp.float32
    a, b = np.asarray(got_gx, np.float32), np.asarray(ref_gx, np.float32)
    assert np.max(np.abs(a - b) / np.maximum(np.abs(b), 1.0)) <= BF16_ULP
    # against the sum in float64: both sides are f32 sums of <= K_out terms
    exact = np.zeros((n, 3))
    np.add.at(
        exact, np.asarray(ex["nbr_idx"])[m[..., 0]],
        np.asarray(g[1], np.float64)[m[..., 0]],
    )
    for side in (got_gp, ref_gp):
        assert np.max(np.abs(np.asarray(side) - exact)) <= 2.0 ** -20


@pytest.mark.parametrize("operand", ["bf16", "bf16+pieces"])
@pytest.mark.parametrize("h", [1, 2])
def pytest_sender_sum_product_equals_reverse_list(monkeypatch, h, operand):
    """``aggregate_to_senders``: the product (``scatter_product`` forward,
    ``gather_product`` backward, interpreted) against today's reverse-list
    code on the same lists: ragged last block, K_out > K_in, padded slots
    that hold garbage. Sums within a bf16 ulp (messages) and at f32
    accuracy (pieces); cotangent rows equal bit for bit, zero on padded
    slots."""
    batch, ex, n, k_in = _lists(h)
    silent = {k: v for k, v in ex.items() if k != "nbr_reach"}
    rng = np.random.default_rng(10 + h)
    m = np.asarray(ex["nbr_mask"])[..., None]
    # nothing masked beforehand: the sum itself must skip padded slots
    e = jnp.asarray(rng.standard_normal((n, k_in, 40)), jnp.bfloat16)
    trans = jnp.asarray(rng.standard_normal((n, k_in, 3)) * 0.3, jnp.float32)
    args = (e, trans) if operand == "bf16+pieces" else (e,)
    g = (jnp.asarray(rng.standard_normal((n, 40)), jnp.bfloat16),
         jnp.asarray(rng.standard_normal((n, 3)), jnp.float32))[: len(args)]

    def both_ways(lists):
        if len(args) == 1:
            out, vjp = jax.vjp(lambda a: (da.sender_sums(a, lists),), *args)
        else:
            out, vjp = jax.vjp(
                lambda a, t: da.sender_sums(a, lists, exact=t), *args
            )
        return out, vjp(g)

    ref, ref_g = jax.jit(both_ways)(silent)
    taken = _rule_answers(monkeypatch)
    with _interpreted(monkeypatch):
        got, got_g = jax.jit(both_ways)(ex)
    assert taken == [h]
    assert [a.dtype for a in got] == [a.dtype for a in ref]
    assert [a.dtype for a in got] == [jnp.bfloat16, jnp.float32][: len(args)]
    a, b = np.asarray(got[0], np.float32), np.asarray(ref[0], np.float32)
    assert np.max(np.abs(a - b) / np.maximum(np.abs(b), 1.0)) <= BF16_ULP
    # cotangent rows: g[nbr_idx] on real slots, whatever the width
    for got_rows, ref_rows, table in zip(got_g, ref_g, g):
        assert got_rows.dtype == ref_rows.dtype == table.dtype
        want = np.where(m, np.asarray(table, np.float32)[ex["nbr_idx"]], 0)
        for rows in (got_rows, ref_rows):
            np.testing.assert_array_equal(np.asarray(rows, np.float32), want)
    if operand == "bf16+pieces":
        exact = np.zeros((n, 3))
        np.add.at(
            exact, np.asarray(ex["nbr_idx"])[m[..., 0]],
            np.asarray(trans, np.float64)[m[..., 0]],
        )
        for side in (got[1], ref[1]):
            assert np.max(np.abs(np.asarray(side) - exact)) <= 2.0 ** -20


def pytest_f32_operand_keeps_reverse_list_sum(tmp_path, monkeypatch):
    """SchNet's translations are f32: the dtype rule keeps ``xla`` even
    where a TPU and a stated reach would qualify (no pieces unasked)."""
    monkeypatch.setattr(da, "_backend", lambda: "tpu")
    _, ex, n, k_in = _lists(1)
    trans = jnp.ones((n, k_in, 3), jnp.float32)
    events = _emitted(
        tmp_path, lambda: da.sender_sums(trans, ex).block_until_ready()
    )
    assert [(e["bucket"], e["choice"]) for e in events] == [
        (f"scatter/n{n}/k{k_in}/d3/float32", "xla")
    ]



def pytest_pna_conv_grad_equal_on_both_paths(monkeypatch):
    from hydragnn_tpu.models.pna import PNAConv

    batch, _ = _collated([60, 129, 40, 129, 90], seed=3)
    stated = jax.tree_util.tree_map(jnp.asarray, batch)
    silent = stated.replace(
        extras={k: v for k, v in stated.extras.items() if k != "nbr_reach"}
    )
    n, dim = stated.x.shape[0], 32
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((n, dim)), jnp.bfloat16)
    conv = PNAConv(in_dim=dim, out_dim=dim, avg_deg_log=1.5, avg_deg_lin=4.0)
    params = conv.init(jax.random.PRNGKey(0), x, stated.pos, silent)
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16), params
    )

    def loss(params, x, b):
        out, _ = conv.apply(params, x, b.pos, b)
        out = jnp.where(b.node_mask[:, None], out, 0.0)
        return (out.astype(jnp.float32) ** 2).mean()

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
    ref_loss, ref_grads = step(params, x, silent)
    with _interpreted(monkeypatch):
        got_loss, got_grads = step(params, x, stated)
    # the primal is the same bf16 rows selected another way
    assert float(got_loss) == float(ref_loss)
    for got, ref in zip(
        jax.tree_util.tree_leaves(got_grads),
        jax.tree_util.tree_leaves(ref_grads),
    ):
        a, b = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        assert np.max(np.abs(a - b)) <= 2 * BF16_ULP * max(np.abs(b).max(), 1e-6)


def _stack_arch(model_type, **over):
    model_type, _, variant = model_type.partition("+")
    if variant == "coords":  # E_GCL's coordinate update on all but the last
        over.update(equivariance=True, num_conv_layers=3)
    return dict(_STACK_ARCH, model_type=model_type, **over)


_STACK_ARCH = {
        "input_dim": 2, "hidden_dim": 16,
        "output_dim": [1], "output_type": ["graph"], "task_weights": [1.0],
        "output_heads": {"graph": {
            "num_sharedlayers": 1, "dim_sharedlayers": 8,
            "num_headlayers": 1, "dim_headlayers": [8],
        }},
        "num_conv_layers": 2, "num_nodes": 129, "edge_dim": None,
        "pna_deg": [0, 4, 8, 4], "max_neighbours": 8, "equivariance": False,
        "num_gaussians": 8, "num_filters": 16, "radius": 3.0,
}


# products the rule selects per traced model: one gather a conv layer; EGNN
# a gather (positions as pieces) and a sender sum a layer, with or without
# its coordinate update; SchNet's ``pos`` table is f32: the dtype rule keeps
# XLA's for it, and its ``W_1 h`` table is bf16 in every layer since its
# Gaussian expansion enters the filter as bf16 (PR 36; before, the f32
# expansion promoted the filter, and every layer after the first, to f32)
@pytest.mark.parametrize(
    "model_type,products",
    [("PNA", 2), ("GIN", 2), ("SAGE", 2), ("GAT", 2), ("MFC", 2),
     ("CGCNN", 2), ("SchNet", 2), ("EGNN", 4), ("EGNN+coords", 6)],
)
def pytest_stack_grads_equal_on_both_paths(monkeypatch, model_type, products):
    """Every stack of the dense path, whole model under the bf16 policy's
    casts (``train/steps.py``): loss and parameter gradients of the stated
    batch (products, interpreted) against the same batch stating nothing
    (indexed reads). A stack whose padded rows went non-finite would show
    here: a product spreads such a row over its window. A gather selects
    the same rows either way, so the loss is equal; EGNN's sender sum adds
    in another order before its one cast to bf16."""
    from hydragnn_tpu.models import create_model_config, init_model_params

    batch, _ = _collated([60, 129, 40, 129, 90], seed=3)
    stated = jax.tree_util.tree_map(jnp.asarray, batch)
    silent = stated.replace(
        extras={k: v for k, v in stated.extras.items() if k != "nbr_reach"}
    )
    model = create_model_config(_stack_arch(model_type))
    variables = init_model_params(model, silent)

    def loss(params, b):
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16), params
        )
        out = model.apply(
            {**variables, "params": params},
            b.replace(x=b.x.astype(jnp.bfloat16)), train=False,
        )
        return model.loss(out, b)[0]

    step = jax.jit(jax.value_and_grad(loss))
    ref_loss, ref_grads = step(variables["params"], silent)
    taken = _rule_answers(monkeypatch)
    with _interpreted(monkeypatch):
        got_loss, got_grads = step(variables["params"], stated)
    assert sum(h is not None for h in taken) == products
    assert np.isfinite(float(ref_loss))
    slack = BF16_ULP if model_type.startswith("EGNN") else 0.0
    assert abs(float(got_loss) - float(ref_loss)) <= slack * float(ref_loss)
    for got, ref in zip(
        jax.tree_util.tree_leaves(got_grads),
        jax.tree_util.tree_leaves(ref_grads),
    ):
        a, b = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        assert np.max(np.abs(a - b)) <= 2 * BF16_ULP * max(np.abs(b).max(), 1e-6)


# ---- the rule ---------------------------------------------------------------


@pytest.mark.parametrize(
    "case,kwargs,want",
    [
        ("selected", {}, 1),
        ("two_blocks_each_side", {"reach": 225}, 2),
        ("egnn_two_tables", {"reach": 200, "k_in": 16,
                             "dim": lg.lane_width(128, 12)}, 2),
        ("f32_table", {"dtype": jnp.float32}, None),
        ("no_statement", {"reach": None}, None),
        ("not_a_tpu", {"backend": "cpu"}, None),
        ("window_over_crossover", {"reach": 2000}, None),
        ("wide_rows_over_crossover", {"dim": 1536}, None),
        ("blocks_over_vmem", {"k_in": 400}, None),
    ],
)
def pytest_rule_selects_from_operands_alone(case, kwargs, want):
    args = dict(
        dtype=jnp.bfloat16, reach=129, k_in=12, dim=256, backend="tpu"
    )
    args.update(kwargs)
    assert lg.window_halo(**args) == want


def _emitted(tmp_path, fn):
    """The ``agg_choice`` events one telemetry run records around ``fn``."""
    from hydragnn_tpu.obs import runtime as obs_rt
    from hydragnn_tpu.obs.events import validate_events

    outdir = str(tmp_path / "obs")
    obs_rt.activate(obs_rt.RunTelemetry("gather-test", outdir))
    try:
        fn()
    finally:
        obs_rt.deactivate()
    recs = validate_events(
        os.path.join(outdir, "events.jsonl"), require=["agg_choice"]
    )
    return [r for r in recs if r["event"] == "agg_choice"]


def pytest_edge_list_caller_keeps_xla_gather(tmp_path, monkeypatch):
    """Lists built from a bare edge list state no locality: even where a
    TPU and a bf16 table would qualify, the indexed read runs."""
    monkeypatch.setattr(da, "_backend", lambda: "tpu")
    batch, _ = _collated(_SIZES[1])
    bare = da.attach_neighbor_lists(batch.replace(extras=None))
    assert "nbr_reach" not in bare.extras
    for key in ("nbr_idx", "nbr_mask", "rev_idx", "rev_mask"):
        np.testing.assert_array_equal(bare.extras[key], batch.extras[key])
    x = jnp.ones((batch.x.shape[0], 8), jnp.bfloat16)
    events = _emitted(
        tmp_path, lambda: da.neighbor_rows(x, bare.extras).block_until_ready()
    )
    assert [(e["gather"], e["choice"], "h" in e) for e in events] == [
        ("xla", "xla", False)
    ]


@pytest.mark.parametrize("kind", ["gather", "scatter"])
def pytest_stated_batch_reports_onehot(tmp_path, monkeypatch, kind):
    """One line per gathered table and per summed operand, its width what
    goes through the product: 8 columns and 3 f32 columns as 9 pieces."""
    batch, _ = _collated(_SIZES[2])
    n, k = batch.extras["nbr_idx"].shape
    if kind == "gather":
        x = jnp.ones((n, 8), jnp.bfloat16)
        call = lambda: da.neighbor_rows(  # noqa: E731
            x, batch.extras, exact=jnp.ones((n, 3)))
    else:
        x = jnp.ones((n, k, 8), jnp.bfloat16)
        call = lambda: da.sender_sums(  # noqa: E731
            x, batch.extras, exact=jnp.ones((n, k, 3)))
    with _interpreted(monkeypatch):
        events = _emitted(
            tmp_path, lambda: jax.block_until_ready(call())
        )
    assert events == [
        dict(
            events[0], bucket=f"{kind}/n{n}/k{k}/d17/bfloat16",
            choice="onehot", source="operands", gather="onehot", h=2,
        )
    ]


def pytest_graph_over_stated_reach_raises_at_collate():
    rng = np.random.default_rng(0)
    seen = [_graph(n, rng) for n in (20, 33, 12)]
    layout = compute_layout(
        [seen], batch_size=3, need_neighbors=True, device_multiple=1
    )
    assert layout.nbr_reach == 33
    collate_for_layout(seen[:2], layout)
    with pytest.raises(ValueError, match="nbr_reach=33"):
        collate_for_layout([_graph(34, rng)], layout)


def pytest_batch_split_over_devices_states_nothing():
    """A batch sharded over the data axis has neighbours on other devices:
    the put drops the statement (and with it an array whose length no mesh
    divides), so every device's gather stays the indexed read."""
    from hydragnn_tpu.models import create_model_config
    from hydragnn_tpu.parallel.mesh import make_mesh2d
    from hydragnn_tpu.train.trainer import Trainer

    batch, _ = _collated([20, 33, 12, 7], device_multiple=4)
    model = create_model_config(_stack_arch("PNA"))
    training = {"Optimizer": {"type": "AdamW", "learning_rate": 1e-3}}
    whole = Trainer(model, training).put_batch(batch)
    split = Trainer(model, training, mesh=make_mesh2d(4, 1)).put_batch(batch)
    assert whole.extras["nbr_reach"].shape == (33,)
    assert set(split.extras) == set(whole.extras) - {"nbr_reach"}


def pytest_layout_without_lists_states_nothing():
    rng = np.random.default_rng(0)
    seen = [_graph(n, rng) for n in (20, 33, 12)]
    layout = compute_layout([seen], batch_size=3, device_multiple=1)
    assert layout.nbr_reach == 0
    assert not (collate_for_layout(seen, layout).extras or {})
