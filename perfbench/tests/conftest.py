"""The benchmark's self-checks run on the CPU, at tiny sizes, in one
process: ``python -m pytest perfbench/tests``. They are not part of the
repo's tier-1 suite (``tests/``)."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(PERFBENCH)
for p in (PERFBENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
