"""The aggregation family rule and its reporting (ops/agg_policy.py): one
decision (partitioned -> segment; explicit flag; else the width tables),
steered by nothing outside the config, and schema-valid ``agg_choice``
events for what ran."""

import json
import os

import numpy as np
import pytest

from hydragnn_tpu.ops import agg_policy as ap

# (stack, width key, a width on the segment side, a width on the dense
# side) for each of the nine conv stacks; None: the table has no such side
_SIDES = [
    ("PNA", "hidden_dim", 95, 96),
    ("GAT", "hidden_dim", 95, 96),
    ("MFC", "hidden_dim", 95, 96),
    ("DimeNet", "hidden_dim", 95, 96),
    ("GIN", "hidden_dim", 191, 192),
    ("SAGE", "hidden_dim", 191, 192),
    ("CGCNN", "input_dim", 65, 64),  # inverse, and keyed on its input width
    ("SchNet", "hidden_dim", 1023, 1024),  # read on the chip in PR 36, bf16
    ("EGNN", "hidden_dim", 127, 128),  # read on the chip in PR 29, in bf16
]


@pytest.mark.parametrize(
    "model_type,key,width,dense",
    [(m, k, seg, False) for m, k, seg, _ in _SIDES]
    + [(m, k, den, True) for m, k, _, den in _SIDES if den is not None],
)
def pytest_table_decides_each_stack_on_each_side(model_type, key, width, dense):
    arch = {"model_type": model_type, "hidden_dim": 64, key: width,
            "bf16_compute": True}
    assert ap.needs_dense_neighbors(arch) is dense
    assert ap.static_aggregation_choice(arch) == (
        "dense" if dense else "segment"
    )


@pytest.mark.parametrize(
    "training,dense",
    [
        ({"mixed_precision": "auto"}, True),  # bf16 from 128 on: products
        ({"mixed_precision": True}, True),
        ({"mixed_precision": False}, False),  # f32 tables keep XLA's gathers
        ({}, False),  # the default is f32
    ],
    ids=["auto", "bf16", "f32", "unstated"],
)
def pytest_egnn_row_holds_for_bf16_runs_only(training, dense):
    """EGNN's dense side wins as products (106.1 | 62.5 ms a step at 128)
    and loses with f32 tables (106.5 | 176.5): the row asks the one
    precision rule, through the one derivation every entry point shares."""
    nn = {
        "Architecture": {"model_type": "EGNN", "hidden_dim": 128},
        "Training": training,
    }
    arch = ap.arch_for_auto_policy(nn)
    assert arch["bf16_compute"] is dense and arch is not nn["Architecture"]
    assert ap.needs_dense_neighbors(arch) is dense
    assert not ap.needs_dense_neighbors(nn["Architecture"])  # unstated: segment


@pytest.mark.parametrize("flag", [True, False])
@pytest.mark.parametrize(
    "arch",
    [
        {"model_type": "PNA", "hidden_dim": 256},  # table: dense
        {"model_type": "EGNN", "hidden_dim": 64},  # under its row: segment
        {"model_type": "CGCNN", "hidden_dim": 64},  # no input_dim: segment
    ],
    ids=lambda a: a["model_type"],
)
def pytest_explicit_flag_beats_the_table(arch, flag):
    assert ap.needs_dense_neighbors(dict(arch, dense_aggregation=flag)) is flag


@pytest.mark.parametrize("flag", [None, True])
def pytest_partitioned_is_never_dense(flag):
    arch = {"model_type": "PNA", "hidden_dim": 256, "partition_axis": "data"}
    if flag is not None:
        arch["dense_aggregation"] = flag
    assert not ap.needs_dense_neighbors(arch)


def pytest_input_dim_derived_once_for_the_width_rule():
    nn = {
        "Architecture": {"model_type": "CGCNN", "hidden_dim": 64},
        "Variables_of_interest": {"input_node_features": [0, 1, 2]},
    }
    assert ap.arch_for_auto_policy(nn)["input_dim"] == 3
    assert ap.needs_dense_neighbors(ap.arch_for_auto_policy(nn))
    nn["Architecture"]["input_dim"] = 256  # a stated width is kept
    assert ap.arch_for_auto_policy(nn) is nn["Architecture"]


def pytest_no_file_and_no_environment_name_moves_the_decision(monkeypatch):
    """What steered the layout before PR 28 and must not now: a decision
    file at the old cache path inside the checkout, and five names."""
    from hydragnn_tpu.utils.compile_cache import DEFAULT_CACHE_DIR

    pna = {"model_type": "PNA", "hidden_dim": 256}
    schnet = {"model_type": "SchNet", "hidden_dim": 128}  # no row: segment
    before = (ap.needs_dense_neighbors(pna), ap.needs_dense_neighbors(schnet))
    assert before == (True, False)
    path = os.path.join(DEFAULT_CACHE_DIR, "autotune.json")
    planted = not os.path.exists(path)  # a stray one serves as well
    flipped = {"timings_ms": {"segment": 1.0, "dense": 2.0}, "ts": 9e9}
    record = {
        "PNA/n48/e160/d256": dict(flipped, choice="segment"),
        "SchNet/n48/e160/d128": dict(flipped, choice="dense"),
    }
    try:
        if planted:
            os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
            with open(path, "w") as f:
                json.dump({"version": 1, "devices": {"cpu": record}}, f)
        monkeypatch.setenv("HYDRAGNN_AUTOTUNE_CACHE", path)
        monkeypatch.setenv("HYDRAGNN_AUTOTUNE", "1")
        monkeypatch.setenv("HYDRAGNN_PALLAS", "1")
        monkeypatch.setenv("HYDRAGNN_FUSED_MP", "1")
        for forced in ("segment", "dense", "fused"):
            monkeypatch.setenv("HYDRAGNN_AGG", forced)
            after = (
                ap.needs_dense_neighbors(pna), ap.needs_dense_neighbors(schnet)
            )
            assert after == before
    finally:
        if planted:
            os.remove(path)


def pytest_tables_are_defined_in_one_module():
    from hydragnn_tpu.data import loaders

    assert loaders.needs_dense_neighbors is ap.needs_dense_neighbors
    assert not hasattr(loaders, "auto_dense_aggregation")
    assert ap.CHOICES == ("segment", "dense")


class _Stack:
    hidden_dim = 8


class GINStack(_Stack):
    pass


class _Batch:
    def __init__(self, extras=None):
        self.x = np.zeros((48, 8), np.float32)
        self.senders = np.zeros((160,), np.int32)
        self.extras = extras


def pytest_choice_events_re_emitted_per_telemetry_run(tmp_path):
    # the dedup is scoped to the active RunTelemetry: a second run in the
    # same process must get its own agg_choice records
    from hydragnn_tpu.obs import runtime as obs_rt
    from hydragnn_tpu.obs.events import validate_events

    for run in ("one", "two"):
        outdir = str(tmp_path / run)
        obs_rt.activate(obs_rt.RunTelemetry(run, outdir))
        try:
            ap.emit_layout_choice(GINStack(), _Batch())
            ap.emit_layout_choice(GINStack(), _Batch())  # deduplicated
        finally:
            obs_rt.deactivate()
        recs = validate_events(
            os.path.join(outdir, "events.jsonl"), require=["agg_choice"]
        )
        assert sum(r["event"] == "agg_choice" for r in recs) == 1


def pytest_choices_emitted_as_schema_valid_events(tmp_path):
    from hydragnn_tpu.obs import runtime as obs_rt
    from hydragnn_tpu.obs.events import validate_events

    outdir = str(tmp_path / "obs")
    telem = obs_rt.activate(obs_rt.RunTelemetry("ap-test", outdir))
    dense = {"nbr_idx": np.zeros((48, 4), np.int32)}
    try:
        ap.emit_layout_choice(GINStack(), _Batch())
        ap.emit_layout_choice(GINStack(), _Batch(dense))
        ap.emit_choice("gather/n48/k4/d8/bfloat16", "onehot", "operands", h=1)
    finally:
        obs_rt.deactivate()
    recs = validate_events(
        os.path.join(outdir, "events.jsonl"), require=["agg_choice"]
    )
    ev = [r for r in recs if r["event"] == "agg_choice"]
    sig = ap.bucket_signature("GIN", 48, 160, 8)
    assert [(r["bucket"], r["choice"], r["source"]) for r in ev] == [
        (sig, "segment", "layout"),
        (sig, "dense", "layout"),
        ("gather/n48/k4/d8/bfloat16", "onehot", "operands"),
    ]
    assert ev[2]["h"] == 1
    # the labeled gauge carries the family the bucket ended on, and one
    # label reads 1; a gather's implementation names no family: no gauge
    snap = telem.metrics.registry.get("aggregation_kernel")
    on = {k for k, v in snap.items() if v == 1.0}
    assert len(on) == 1 and "choice=dense" in next(iter(on))
    assert not any("gather/" in k for k in snap)


def pytest_resolve_precision_policy():
    # the param-precision policy (models/create.py): env > explicit >
    # auto width table > conservative default
    from hydragnn_tpu.models.create import resolve_precision
    from hydragnn_tpu.models.pna import PNAStack

    wide = PNAStack(hidden_dim=256, deg=(0, 1))
    narrow = PNAStack(hidden_dim=64, deg=(0, 1))
    assert resolve_precision(wide, {}) == {
        "mixed": False, "source": "default"
    }
    assert resolve_precision(wide, {"mixed_precision": "auto"})["mixed"]
    assert not resolve_precision(narrow, {"mixed_precision": "auto"})["mixed"]
    assert resolve_precision(narrow, {"mixed_precision": True}) == {
        "mixed": True, "source": "explicit"
    }
    os.environ["HYDRAGNN_MIXED_PRECISION"] = "0"
    try:
        assert resolve_precision(wide, {"mixed_precision": True}) == {
            "mixed": False, "source": "env"
        }
    finally:
        del os.environ["HYDRAGNN_MIXED_PRECISION"]
    # DimeNet computes in bf16 from 128 on under auto (read on the chip,
    # PR 30), and stays f32 under it, as the whole zoo
    from hydragnn_tpu.models.create import precision_for

    auto = {"mixed_precision": "auto"}
    assert precision_for("DimeNet", 128, auto)["mixed"] is True
    assert precision_for("DimeNet", 64, auto)["mixed"] is False
