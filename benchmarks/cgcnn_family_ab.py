"""The published CGCNN block's edge-phase form and its two policy rows, read
on one TPU chip: the train step of the benchmark's CGCNN cell (its
configuration, its traffic), ms a step, in six readings.

- Forms (``--part forms``), at the cell's rung, bf16, dense lists: the
  pre-activation ``W [h_i ; h_j ; g] + b`` as the program ships it
  (``gathered``: the 512-wide ``h_j`` gathered, then ``[h_j ; g]``
  projected an edge, ``W_i h + b`` per node), ``concatenated`` (the three
  gathered or broadcast parts, one 1,124 -> 1,024 product an edge) and
  ``split`` (``(W_i h + b)_i + (W_j h)_j + W_g g``: a gathered 1,024-wide
  table and a 100 -> 1,024 product an edge). The two the program does not
  ship are defined here and patched over ``models/cgcnn.py
  edge_preactivation`` for their readings.
- Families (``--part families``), the shipped form: dense lists | edge
  list at the cell's rung in bf16, and at ``--f32-rung`` (half the cell's
  by default) in f32.

The readings behind ``models/cgcnn.py edge_preactivation``,
``ops/agg_policy.py DENSE_AUTO_MIN_HIDDEN["CGCNN"]`` and
``models/create.py BF16_AUTO_MIN_HIDDEN["CGCNN"]`` (PERF.md section 4).
Positions, offsets, distances and the Gaussians are f32 on both sides of
the precision pair.

    python benchmarks/cgcnn_family_ab.py [--part all|forms|families] [--rung R] [--f32-rung R] [--out readings.jsonl]

How a reading is taken, and what a line holds: ``dimenet_family_ab.py``
(``step_reading``); each line here adds its ``form``. Fails off a TPU.
"""

import argparse
import json
import os

import dimenet_family_ab
from dimenet_family_ab import ROOT

import jax
import jax.numpy as jnp

CELL = "cgcnn_h512x3_train_oc20"


def _sides(x, batch):
    """(receiver rows, sender rows) of ``x`` at every edge or slot."""
    extras = batch.extras or {}
    if "nbr_idx" in extras:
        from hydragnn_tpu.ops.dense_agg import neighbor_rows

        x_j = neighbor_rows(x, extras)
        return jnp.broadcast_to(x[:, None, :], x_j.shape), x_j
    return x[batch.receivers], x[batch.senders]


def concatenated(x, rbf, kernel, bias, batch):
    """One (2C + G) -> 2C product an edge, as the equations are written."""
    x_i, x_j = _sides(x, batch)
    return jnp.concatenate([x_i, x_j, rbf], -1) @ kernel + bias


def split(x, rbf, kernel, bias, batch):
    """``(W_i h + b)_i + (W_j h)_j + W_g g``: the 2C-wide ``W_j h`` gathered,
    a G -> 2C product an edge."""
    c = x.shape[-1]
    own = x @ kernel[:c] + bias
    other = x @ kernel[c : 2 * c]
    geometry = rbf @ kernel[2 * c :]
    extras = batch.extras or {}
    if "nbr_idx" in extras:
        from hydragnn_tpu.ops.dense_agg import neighbor_rows

        return own[:, None, :] + neighbor_rows(other, extras) + geometry
    return own[batch.receivers] + other[batch.senders] + geometry


def main():
    import build
    import traffic_gen
    from hydragnn_tpu.models import cgcnn

    ap = argparse.ArgumentParser()
    ap.add_argument("--rung", type=int, default=None,
                    help="another batch size than the cell's")
    ap.add_argument("--f32-rung", type=int, default=None)
    ap.add_argument("--part", choices=("all", "forms", "families"),
                    default="all")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    out = args.out and os.path.abspath(args.out)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell, config, mix = build.load_cell(CELL, json.load(f))
    rung = args.rung or build.batch_size_for(mix, cell["chips"])
    f32_rung = args.f32_rung or rung // 2
    data = {r: traffic_gen.make_graphs(mix, r * mix["dataset_batches"], 0)
            for r in {rung, f32_rung}}
    shipped = cgcnn.edge_preactivation
    forms = [("gathered", shipped, True, True, rung),
             ("concatenated", concatenated, True, True, rung),
             ("split", split, True, True, rung)]
    families = [("gathered", shipped, True, True, rung),
                ("gathered", shipped, False, True, rung),
                ("gathered", shipped, True, False, f32_rung),
                ("gathered", shipped, False, False, f32_rung)]
    plan = {"forms": forms, "families": families,
            "all": forms + families[1:]}[args.part]
    lines = [{"device": dev.device_kind, "cell": CELL, "rung": rung,
              "f32_rung": f32_rung}]
    print(json.dumps(lines[0]), flush=True)
    for form, fn, dense, bf16, r in plan:
        cgcnn.edge_preactivation = fn
        try:
            line = dimenet_family_ab.step_reading(
                dense, bf16, data[r], cell, config, mix, r, args.iters)
        finally:
            cgcnn.edge_preactivation = shipped
        line = dict(form=form, rung=r, **line)
        lines.append(line)
        print(json.dumps(line), flush=True)
    if out:
        os.makedirs(os.path.dirname(out), exist_ok=True)
        with open(out, "w") as f:
            f.writelines(json.dumps(l) + "\n" for l in lines)


if __name__ == "__main__":
    main()
