"""Where the neighbour gather's crossover lies: XLA's indexed read against
the block-local one-hot product (``ops/local_gather.py``), forward and
backward, on one TPU chip. The reading ``MAX_WINDOW_TILES`` was set from
(PERF.md section 6, PR 27).

    python benchmarks/gather_crossover.py [--out chiprun_out/gather_crossover.jsonl]

Three sweeps, every line a JSON object on stdout (and in ``--out``;
``--sweep`` runs some of them):

- ``bucket``: the four buckets of the benchmark's PNA cell (padded rows,
  largest graph), 12 slots a row, at the widths its three layers gather
  (256, 256, 1). Checks on the chip what the CPU tests check in the
  interpreter: real slots equal bit for bit, cotangents within a bf16 ulp.
- ``window``: the product alone at one bucket's rows for halos 1-4 and
  widths 128-512: its cost per index and per [128 x 128] tile of window.
- ``egnn``: the four buckets of the benchmark's EGNN cell laid out dense,
  16 slots a row: E_GCL's gather (128 bf16 columns + 3 f32 positions: 137
  through the product, the positions as pieces, against XLA's gather of
  the f32 table of 131) and its sender sum (128 + 3 f32 translations + the
  count: 140 against 132), forward and backward, ns an index. With
  ``benchmarks/egnn_family_ab.py`` (the whole step) the reading behind
  ``ops/agg_policy.py DENSE_AUTO_MIN_HIDDEN["EGNN"]`` (PERF.md section 6,
  PR 29).

Fails off a TPU: a CPU timing of either side says nothing.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp

from hydragnn_tpu.ops import dense_agg as da
from hydragnn_tpu.ops import local_gather as lg

K_IN = 12
# the one table dtype the rule sends to the product (window_halo)
TABLE = jnp.bfloat16
# (padded rows, largest graph) of pna_h256_train_oc20's buckets
BUCKETS = [(24488, 61), (39432, 93), (57592, 137), (88648, 225)]
# the same of egnn_h128x7_train_mptrj under dense lists, 16 slots a row
EGNN_BUCKETS = [(7480, 29), (14896, 51), (24984, 89), (44336, 200)]
EGNN_K, EGNN_HIDDEN = 16, 128


def block_diagonal_lists(n, reach, rng, k=K_IN):
    """Dense lists of a collated batch: graphs of reach/3..reach rows laid
    down contiguously, every row with ``k`` senders from its own graph
    (the cell's degree cap binds almost everywhere)."""
    sizes = [reach]  # the bound is met
    while sum(sizes) < n - 1 - reach:
        sizes.append(int(rng.integers(max(reach // 3, 2), reach + 1)))
    start = np.concatenate([[0], np.cumsum(sizes)])[:-1]
    size_of = np.repeat(sizes, sizes)
    first_of = np.repeat(start, sizes)
    rows = first_of.shape[0]
    recv = np.repeat(np.arange(rows), k)
    send = first_of[recv] + rng.integers(0, 2**31, recv.shape[0]) % size_of[recv]
    k_in, k_out = da.max_degree(send, recv)
    return da.build_neighbor_lists(send, recv, None, n, k_in, k_out)


def device_ms(fn, *args, iters=20):
    """Median of three timings of ``iters`` enqueued calls, per call."""
    jax.block_until_ready(fn(*args))
    takes = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        takes.append((time.perf_counter() - t0) / iters * 1e3)
    return float(np.median(takes))


def bucket_reading(n, reach, dim, rng):
    ex = {k: jnp.asarray(v) for k, v in block_diagonal_lists(n, reach, rng).items()}
    idx, mask = ex["nbr_idx"], ex["nbr_mask"]
    h = lg._cdiv(reach - 1, lg.BLOCK)
    x = jnp.asarray(rng.standard_normal((n, dim)), TABLE)
    g = jnp.asarray(rng.standard_normal((n, K_IN, dim)), TABLE)

    xla_fwd = jax.jit(lambda t: da._gather_xla(t, idx, ex["rev_idx"], ex["rev_mask"]))
    one_fwd = jax.jit(lambda t: da._gather_onehot(h, t, idx, mask))
    xla_bwd = jax.jit(lambda t, c: jax.vjp(
        lambda u: da._gather_xla(u, idx, ex["rev_idx"], ex["rev_mask"]), t)[1](c)[0])
    one_bwd = jax.jit(lambda t, c: jax.vjp(
        lambda u: da._gather_onehot(h, u, idx, mask), t)[1](c)[0])

    m = np.asarray(mask)[..., None]
    a = np.asarray(one_fwd(x), np.float32)
    b = np.asarray(xla_fwd(x), np.float32)
    fwd_equal = bool(np.array_equal(np.where(m, a, 0), np.where(m, b, 0)))
    ga = np.asarray(one_bwd(x, g), np.float32)
    gb = np.asarray(xla_bwd(x, g), np.float32)
    bwd_gap = float(np.max(np.abs(ga - gb) / np.maximum(np.abs(gb), 1.0)))
    indices = n * K_IN
    line = {
        "sweep": "bucket", "n": n, "reach": reach, "h": h, "dim": dim,
        "k_in": K_IN, "k_out": int(ex["rev_idx"].shape[1]),
        "fwd_equal_on_real_slots": fwd_equal, "bwd_gap_ulps": bwd_gap * 2**7,
    }
    for name, fn, args in [
        ("xla_fwd", xla_fwd, (x,)), ("onehot_fwd", one_fwd, (x,)),
        ("xla_bwd", xla_bwd, (x, g)), ("onehot_bwd", one_bwd, (x, g)),
    ]:
        ms = device_ms(fn, *args)
        line[name + "_ms"] = round(ms, 4)
        line[name + "_ns_per_index"] = round(ms * 1e6 / indices, 3)
    return line


def egnn_reading(n, reach, rng):
    """E_GCL's two calls on one bucket, through ``dense_agg``'s own entry
    points: the batch stating its reach (products) against the same batch
    stating nothing (XLA's gathers)."""
    lists = block_diagonal_lists(n, reach, rng, EGNN_K)
    silent = {k: jnp.asarray(v) for k, v in lists.items()}
    stated = dict(silent, nbr_reach=jnp.zeros(reach, jnp.int8))
    m = np.asarray(silent["nbr_mask"])[..., None]
    d = EGNN_HIDDEN
    y = jnp.asarray(rng.standard_normal((n, d)), TABLE)
    pos = jnp.asarray(rng.standard_normal((n, 3)) * 20, jnp.float32)
    e = jnp.asarray(rng.standard_normal((n, EGNN_K, d)) * m, TABLE)
    trans = jnp.asarray(rng.standard_normal((n, EGNN_K, 4)) * m, jnp.float32)
    g_rows = (e, trans[..., :3])  # cotangents of the gather's two results
    g_sums = (e[:, 0], trans[:, 0])  # and of the sender sum's

    def calls(ex):
        gather = lambda t, p: da.neighbor_rows(t, ex, exact=p)  # noqa: E731
        sums = lambda a, t: da.sender_sums(a, ex, exact=t)  # noqa: E731
        return {
            "gather_fwd": (jax.jit(gather), (y, pos)),
            "gather_bwd": (jax.jit(lambda t, p, g: jax.vjp(gather, t, p)[1](g)),
                           (y, pos, g_rows)),
            "sum_fwd": (jax.jit(sums), (e, trans)),
            "sum_bwd": (jax.jit(lambda a, t, g: jax.vjp(sums, a, t)[1](g)),
                        (e, trans, g_sums)),
        }

    xla, one = calls(silent), calls(stated)
    out = {k: (xla[k][0](*xla[k][1]), one[k][0](*one[k][1])) for k in xla}
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    gap = lambda a, b: float(np.max(  # noqa: E731
        np.abs(f32(a) - f32(b)) / np.maximum(np.abs(f32(b)), 1.0)))
    (ry, rp), (oy, op) = out["gather_fwd"]
    line = {
        "sweep": "egnn", "n": n, "reach": reach,
        "h": lg._cdiv(reach - 1, lg.BLOCK), "k_in": EGNN_K,
        "k_out": int(silent["rev_idx"].shape[1]),
        "widths": {"gather": [d + 9, d + 3], "sum": [d + 12, d + 4]},
        "rows_equal_on_real_slots": bool(
            np.array_equal(np.where(m, f32(oy), 0), np.where(m, f32(ry), 0))),
        "positions_bit_equal_on_real_slots": bool(
            np.array_equal(np.where(m, f32(op), 0), np.where(m, f32(rp), 0))),
        "gather_bwd_gap_ulps": gap(out["gather_bwd"][1][0], out["gather_bwd"][0][0]) * 2**7,
        "gather_bwd_f32_gap": gap(out["gather_bwd"][1][1], out["gather_bwd"][0][1]),
        "sum_gap_ulps": gap(out["sum_fwd"][1][0], out["sum_fwd"][0][0]) * 2**7,
        "sum_f32_gap": gap(out["sum_fwd"][1][1], out["sum_fwd"][0][1]),
        "sum_bwd_equal": bool(all(
            np.array_equal(f32(a), f32(b))
            for a, b in zip(out["sum_bwd"][1], out["sum_bwd"][0]))),
    }
    for side, table in (("xla", xla), ("onehot", one)):
        for name, (fn, args) in table.items():
            ms = device_ms(fn, *args)
            line[f"{side}_{name}_ms"] = round(ms, 4)
            line[f"{side}_{name}_ns_per_index"] = round(
                ms * 1e6 / (n * EGNN_K), 3)
    return line


def window_reading(n, h, dim, rng):
    """The product alone; lists of reach 100 are valid for every halo."""
    ex = {k: jnp.asarray(v) for k, v in block_diagonal_lists(n, 100, rng).items()}
    idx, mask = ex["nbr_idx"], ex["nbr_mask"]
    x = jnp.asarray(rng.standard_normal((n, dim)), TABLE)
    g = jnp.asarray(rng.standard_normal((K_IN, n, dim)), TABLE)
    fwd = jax.jit(lambda t: lg.gather_product(t, idx, h))
    bwd = jax.jit(lambda c: lg.scatter_product(c, idx, mask, h))
    tiles = (2 * h + 1) * lg._cdiv(dim, 128)
    line = {"sweep": "window", "n": n, "h": h, "dim": dim, "tiles": tiles}
    for name, fn, arg in [("onehot_fwd", fwd, x), ("onehot_bwd", bwd, g)]:
        ns = device_ms(fn, arg) * 1e6 / (n * K_IN)
        line[name + "_ns_per_index"] = round(ns, 3)
        line[name + "_ns_per_index_tile"] = round(ns / tiles, 4)
    return line


def einsum_reading(n, reach, dim, rng):
    """The cheap first reading: the same product as a blocked einsum in
    plain XLA (the 0/1 matrix goes through HBM), forward only."""
    ex = block_diagonal_lists(n, reach, rng)
    h = lg._cdiv(reach - 1, lg.BLOCK)
    nb = lg._cdiv(n, lg.BLOCK)
    pad = nb * lg.BLOCK - n
    idx = jnp.pad(jnp.asarray(ex["nbr_idx"]), ((0, pad), (0, 0)))
    x = jnp.asarray(rng.standard_normal((n, dim)), TABLE)

    @jax.jit
    def fwd(t):
        tb = jnp.pad(t, ((h * lg.BLOCK, pad + h * lg.BLOCK), (0, 0)))
        win = jnp.stack(
            [tb[o * lg.BLOCK : (o + nb) * lg.BLOCK] for o in range(2 * h + 1)]
        ).reshape(2 * h + 1, nb, lg.BLOCK, dim)
        win = win.transpose(1, 0, 2, 3).reshape(nb, -1, dim)  # [nb, W, D]
        rel = idx.reshape(nb, lg.BLOCK, K_IN) - (
            (jnp.arange(nb) - h) * lg.BLOCK
        )[:, None, None]
        onehot = (rel[..., None] == jnp.arange(win.shape[1])).astype(t.dtype)
        out = jnp.einsum("brkw,bwd->brkd", onehot, win,
                         preferred_element_type=jnp.float32)
        return out.astype(t.dtype).reshape(nb * lg.BLOCK, K_IN, dim)[:n]

    ms = device_ms(fwd, x)
    return {"sweep": "einsum", "n": n, "reach": reach, "h": h, "dim": dim,
            "einsum_fwd_ms": round(ms, 4),
            "einsum_fwd_ns_per_index": round(ms * 1e6 / (n * K_IN), 3)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--sweep", nargs="+", default=["bucket", "window", "egnn"],
                    choices=["bucket", "window", "egnn"])
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    rng = np.random.default_rng(27)
    lines = [{"device": dev.device_kind, "block": lg.BLOCK,
              "max_window_tiles": lg.MAX_WINDOW_TILES}]
    print(json.dumps(lines[0]), flush=True)

    def emit(line):
        lines.append(line)
        print(json.dumps(line), flush=True)

    if "bucket" in args.sweep:
        for n, reach in BUCKETS:
            for dim in (256, 1):
                emit(bucket_reading(n, reach, dim, rng))
        emit(einsum_reading(*BUCKETS[1], 256, rng))
    if "window" in args.sweep:
        for dim, halos in ((128, (1, 2, 3, 4)), (256, (1, 2, 3, 4)), (512, (1, 2))):
            for h in halos:
                emit(window_reading(BUCKETS[1][0], h, dim, rng))
    if "egnn" in args.sweep:
        for n, reach in EGNN_BUCKETS:
            emit(egnn_reading(n, reach, rng))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(l) + "\n" for l in lines)


if __name__ == "__main__":
    main()
