"""SchNet's two policy rows, read on one TPU chip: the train step of the
benchmark's SchNet cell (its configuration, its traffic; the rung as given,
64 by default so that the f32 sides fit) with
``Architecture.dense_aggregation`` true | false and
``Training.mixed_precision`` false | true: four readings, ms a step. The
reading behind ``ops/agg_policy.py DENSE_AUTO_MIN_HIDDEN`` (SchNet's row)
and ``models/create.py BF16_AUTO_MIN_HIDDEN["SchNet"]`` (PERF.md section 4,
PR 36). Positions, offsets, distances, the Gaussians and the envelope are
f32 on both sides of the precision pair.

    python benchmarks/schnet_family_ab.py [--rung 64] [--out chiprun_out/schnet_family_ab.jsonl]

How a reading is taken, and what a line holds: ``dimenet_family_ab.py``,
whose ``main`` this runs on its own cell. Fails off a TPU.
"""

import sys

import dimenet_family_ab

CELL = "schnet_h1024x5_train_oc20"

if __name__ == "__main__":
    if "--rung" not in sys.argv:
        sys.argv += ["--rung", "64"]
    dimenet_family_ab.main(CELL)
