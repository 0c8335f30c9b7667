"""Test harness: run everything on an 8-device virtual CPU mesh.

Mirrors the reference CI strategy (SURVEY.md §4): their "fake cluster" is
gloo-on-CPU under mpirun; ours is XLA's host-platform device partitioning —
the same sharded code paths compile and run with N=8 logical devices on one
host, no mocks.

The virtual mesh is THIS file's explicit set-up (no entry point fabricates
one): the platform is pinned to the CPU through the environment (inherited
by the subprocess tests) and through jax.config (which also holds when jax
was imported before this file), both before any backend is initialized.
"""

import collections
import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax
import pytest

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# persistent XLA compile cache, shared by the xdist workers and by later
# runs: recompiles of the jitted train/eval programs are a large share of
# the suite's CPU time (driver paths already enable it, this covers
# direct-Trainer unit tests too)
from hydragnn_tpu.utils.compile_cache import enable_compile_cache

enable_compile_cache()

# ---- CI tiers -------------------------------------------------------------
# Default (tier-1): `-m 'not slow'`, run by the driver on six xdist workers
# with `--dist loadfile`. A whole file goes to one worker; xdist hands the
# files out by their number of cases, most first (`--loadscope-reorder`, its
# default), and binds a worker's next file once it has two cases left. So a
# file's summed time bounds the session from below, a long file with few
# cases starts last, and whatever is bound behind two long cases waits for
# both. The end-to-end trainings (tests/e2e_train.py) are spread over
# test_graphs*.py accordingly: under about 450 s a file, five cases or so,
# the long ones first and the short or skipped ones last.
# HYDRAGNN_FAST_TEST=1: skip the end-to-end/subprocess-heavy files (the
# smoke tier); every file that calls unittest_train_model is listed, or
# skips that case itself (test_suite_layout.py holds the list to that).
# HYDRAGNN_FULL_TEST=1 (read inside the files) widens matrices instead.
if int(os.getenv("HYDRAGNN_FAST_TEST", "0")) == 1:
    collect_ignore = [
        "test_graphs.py",  # e2e accuracy trainings (helper: e2e_train.py)
        "test_graphs_lengths.py",
        "test_graphs_multihead.py",
        "test_graphs_multihead_gat.py",
        "test_mixed_precision.py",  # bf16 e2e training
        "test_examples.py",  # example subprocesses
        "test_multiprocess.py",  # two-process distributed runs
        "test_partitioned_run_training.py",  # partitioned e2e trainings
        "test_model_loadpred.py",  # train+reload e2e runs
        "test_hpo.py",  # HPO trial loops
    ]


@pytest.fixture(autouse=True)
def _no_span_left_open():
    """Files share a worker's process and the span recorder's per-thread
    stack (``utils/tracer.py``; ``reset()`` leaves open spans open). An
    epoch that raises, as ``test_resilience.py``'s unbounded divergence
    does by design, leaves ``train`` open, and whichever file the worker
    takes next would record its spans under ``train/``: which file that
    is changes whenever a file gains a case."""
    yield
    tracer = sys.modules.get("hydragnn_tpu.utils.tracer")
    if tracer is not None:
        tracer._state.stack().clear()


def pytest_terminal_summary(terminalreporter, config):
    """Where the time went, by file (the unit the driver distributes): the
    driver's command has no --durations and its XML is deleted by the next
    run."""
    if hasattr(config, "workerinput"):  # xdist worker: the controller reports
        return
    per_file = collections.Counter()
    for reports in terminalreporter.stats.values():
        for rep in reports:
            if getattr(rep, "when", None) == "call":
                per_file[rep.nodeid.split("::")[0]] += rep.duration
    if not per_file:  # --collect-only
        return
    total = sum(per_file.values())
    terminalreporter.write_line(
        f"call time by file: {total:.0f} s in {len(per_file)} files, heaviest:"
    )
    for name, seconds in per_file.most_common(5):
        terminalreporter.write_line(f"  {seconds:8.1f} s  {name}")
