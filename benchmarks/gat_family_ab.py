"""GAT's two policy rows, read on one TPU chip: the train step of the
benchmark's GATv2 cell (its configuration, its traffic, its rung: all from
the cell's files, nothing sized here) with
``Architecture.dense_aggregation`` true | false and
``Training.mixed_precision`` false | true: four readings, ms a step. The
reading behind ``ops/agg_policy.py DENSE_AUTO_MIN_HIDDEN["GAT"]`` and
``models/create.py BF16_AUTO_MIN_HIDDEN["GAT"]`` (PERF.md section 6, PR 32).
Scores, softmax and denominator are f32 on both sides of the precision pair.

    python benchmarks/gat_family_ab.py [--rung 128] [--out chiprun_out/gat_family_ab.jsonl]

How a reading is taken, and what a line holds: ``dimenet_family_ab.py``,
whose ``main`` this runs on its own cell. Fails off a TPU.
"""

import dimenet_family_ab

CELL = "gatv2_h4x256_train_oc20"

if __name__ == "__main__":
    dimenet_family_ab.main(CELL)
