"""numlint (analysis --suite=numerics): the numerics & kernel-safety suite.

Per rule: a bad snippet that must flag and a good snippet that must not,
plus the numlint suppression tag (and its one-line scope), the
``--list-rules`` catalog for the fourth suite, the baseline ratchet, and
the acceptance regressions — the merged tree runs clean against the
committed (empty) ``.numlint-baseline.json``, and reintroducing an
unguarded exp or an unmasked gather fails the gate.

Everything here is pure-AST: no jax execution. The compiled-memory half
of numlint (``analysis/mem.py``) is covered by
``tests/test_numlint_mem.py`` and the CI ratchet smoke; the runtime half
(``nan_sentinel``) by the sentinel tests in the same file.
"""

import json
import os
import textwrap

from hydragnn_tpu.analysis import analyze_paths
from hydragnn_tpu.analysis.__main__ import main as lint_main
from hydragnn_tpu.analysis.core import rules_in_suite

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NUMERICS_RULES = {
    "low-precision-accum",
    "precision-policy-bypass",
    "unguarded-exp-log-div",
    "nan-unsafe-where",
    "unmasked-gather-id",
    "pallas-vmem-unbounded",
}


def _lint(tmp_path, files, select=None):
    for rel, src in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(src))
    return analyze_paths(
        [str(tmp_path)],
        root=str(tmp_path),
        select=select or rules_in_suite("numerics"),
    ).findings


def _rules_of(findings):
    return sorted({f.rule for f in findings})


def pytest_numerics_suite_registry():
    assert rules_in_suite("numerics") == NUMERICS_RULES


# ---- low-precision-accum --------------------------------------------------

_ACCUM_BAD = """
    import jax
    import jax.numpy as jnp

    def dense_sum(h, nbr_mask):
        hm = jnp.where(nbr_mask[..., None], h, 0.0)
        return hm.sum(axis=1)

    def scatter(x, gid, n):
        return jax.ops.segment_sum(x, gid, num_segments=n)

    def prefix(w):
        return jnp.cumsum(w)

    def contract(a, b):
        return jnp.matmul(a, b)
"""

_ACCUM_GOOD = """
    import jax
    import jax.numpy as jnp
    import numpy as np

    def dense_sum(h, nbr_mask):
        hm = jnp.where(nbr_mask[..., None], h, 0.0).astype(jnp.float32)
        return hm.sum(axis=1).astype(h.dtype)

    def scatter(x, gid, n):
        return jax.ops.segment_sum(
            x.astype(jnp.float32), gid, num_segments=n
        )

    def prefix(w):
        return jnp.cumsum(w, dtype=jnp.float32)

    def offsets(batch, deg):
        # integer count prefix sums and host numpy never run bf16
        a = jnp.cumsum(batch.n_node)
        b = np.cumsum(deg)
        return a, b

    def degree(nbr_mask):
        return nbr_mask.sum(axis=1)  # bool mask -> int accumulation

    def contract(a, b):
        return jnp.matmul(a, b, preferred_element_type=jnp.float32)

    def agg_kernel(h_ref, o_ref):
        # kernel bodies see pre-masked f32 refs by the wrapper contract
        o_ref[...] = h_ref[...].sum(axis=1)
"""


def pytest_low_precision_accum(tmp_path):
    bad = _lint(
        tmp_path, {"ops/bad_agg.py": _ACCUM_BAD},
        select={"low-precision-accum"},
    )
    assert len(bad) == 4, [(f.line, f.message) for f in bad]
    assert _rules_of(bad) == ["low-precision-accum"]
    good = _lint(
        tmp_path, {"ops/good_agg.py": _ACCUM_GOOD},
        select={"low-precision-accum"},
    )
    assert [f for f in good if f.path.endswith("good_agg.py")] == []


def pytest_accum_scoped_to_numeric_dirs(tmp_path):
    # the same accumulation in serve/ (host orchestration) is exempt
    found = _lint(
        tmp_path, {"serve/router.py": _ACCUM_BAD},
        select={"low-precision-accum"},
    )
    assert found == []


# ---- precision-policy-bypass ----------------------------------------------

_BYPASS_BAD = """
    import jax.numpy as jnp

    def pack(x):
        return x.astype(jnp.bfloat16)

    def alloc(n):
        return jnp.zeros((n,), dtype=jnp.float16)
"""


def pytest_precision_policy_bypass(tmp_path):
    bad = _lint(
        tmp_path, {"serve/pack.py": _BYPASS_BAD},
        select={"precision-policy-bypass"},
    )
    assert len(bad) == 2, [(f.line, f.message) for f in bad]
    # the sanctioned application site is exempt: steps.py casts per the
    # resolve_precision policy
    good = _lint(
        tmp_path, {"train/steps.py": _BYPASS_BAD},
        select={"precision-policy-bypass"},
    )
    assert [f for f in good if f.path.endswith("steps.py")] == []


# ---- unguarded-exp-log-div ------------------------------------------------

_EXPLOG_BAD = """
    import jax.numpy as jnp

    def f(x, h):
        e = jnp.exp(x)
        l = jnp.log(x)
        d = x - h
        r = jnp.sqrt(d)
        return e + l + r + x / h.sum(1)
"""

_EXPLOG_GOOD = """
    import jax.numpy as jnp

    def f(x, h, eps):
        e = jnp.exp(jnp.minimum(x, 0.0))
        l = jnp.log(x + 1e-9)
        d = x - h
        r = jnp.sqrt(d + eps)
        w = jnp.sqrt(x)  # plain width/fan-in: never triggers
        s = jnp.exp(x - x.max())  # max-shifted softmax idiom
        return e + l + r + w + s + x / jnp.maximum(h.sum(1), 1.0)
"""


def pytest_unguarded_exp_log_div(tmp_path):
    bad = _lint(
        tmp_path, {"models/act.py": _EXPLOG_BAD},
        select={"unguarded-exp-log-div"},
    )
    assert len(bad) == 4, [(f.line, f.message) for f in bad]
    good = _lint(
        tmp_path, {"models/act_ok.py": _EXPLOG_GOOD},
        select={"unguarded-exp-log-div"},
    )
    assert [f for f in good if f.path.endswith("act_ok.py")] == []


def pytest_div_by_builtin_sum_is_exempt(tmp_path):
    # host-side config math: the Python builtin sum() is not an array
    # reduction that can hit zero on padded slots
    found = _lint(
        tmp_path,
        {
            "models/weights.py": """
            def norm(ws):
                s = sum(abs(w) for w in ws)
                return [w / s for w in ws]
            """,
        },
        select={"unguarded-exp-log-div"},
    )
    assert found == []


# ---- nan-unsafe-where -----------------------------------------------------


def pytest_nan_unsafe_where(tmp_path):
    bad = _lint(
        tmp_path,
        {
            "models/safe.py": """
            import jax.numpy as jnp

            def f(x):
                return jnp.where(x > 0, jnp.sqrt(x), 0.0)
            """,
        },
        select={"nan-unsafe-where"},
    )
    assert len(bad) == 1
    good = _lint(
        tmp_path,
        {
            "models/safe_ok.py": """
            import jax.numpy as jnp

            def f(x):
                p = x > 0
                return jnp.where(p, jnp.sqrt(jnp.where(p, x, 1.0)), 0.0)
            """,
        },
        select={"nan-unsafe-where"},
    )
    assert [f for f in good if f.path.endswith("safe_ok.py")] == []


# ---- unmasked-gather-id ---------------------------------------------------

_GATHER_BAD = """
    import jax
    import jax.numpy as jnp

    def gather(x, nbr_idx):
        rows = x[nbr_idx]
        return rows

    def scatter(x, gid):
        return jax.ops.segment_sum(x, gid)
"""

_GATHER_GOOD = """
    import jax
    import jax.numpy as jnp

    def gather(x, nbr_idx, nbr_mask):
        rows = jnp.where(nbr_mask[..., None], x[nbr_idx], 0.0)
        return rows

    def clipped(x, raw_idx, n):
        idx = jnp.clip(raw_idx, 0, n - 1)
        return x[idx]

    def consumed(x, nbr_idx, nbr_mask):
        return dense_sum(x[nbr_idx], nbr_mask)

    def scatter(x, gid, n):
        return jax.ops.segment_sum(x, gid, num_segments=n)
"""


def pytest_unmasked_gather_id(tmp_path):
    bad = _lint(
        tmp_path, {"ops/gath.py": _GATHER_BAD},
        select={"unmasked-gather-id"},
    )
    assert len(bad) == 2, [(f.line, f.message) for f in bad]
    good = _lint(
        tmp_path, {"ops/gath_ok.py": _GATHER_GOOD},
        select={"unmasked-gather-id"},
    )
    assert [f for f in good if f.path.endswith("gath_ok.py")] == []


def pytest_gather_rule_scoped_to_ops(tmp_path):
    # models/ gathers go through the graph/segment wrappers; the raw-id
    # contract is an ops/-only discipline
    found = _lint(
        tmp_path, {"models/net.py": _GATHER_BAD},
        select={"unmasked-gather-id"},
    )
    assert _rules_of(found) == []


# ---- pallas-vmem-unbounded ------------------------------------------------

_PALLAS_BAD = """
    from jax.experimental import pallas as pl

    def run(x):
        return pl.pallas_call(_kern, out_shape=x)(x)
"""

_PALLAS_GOOD = """
    from jax.experimental import pallas as pl

    _VMEM_BUDGET = 64 * 1024 * 1024

    def run_enabled(working_set):
        return working_set < _VMEM_BUDGET

    def run(x):
        return pl.pallas_call(_kern, out_shape=x)(x)
"""


def pytest_pallas_vmem_unbounded(tmp_path):
    bad = _lint(
        tmp_path, {"ops/kern.py": _PALLAS_BAD},
        select={"pallas-vmem-unbounded"},
    )
    assert len(bad) == 1
    good = _lint(
        tmp_path, {"ops/kern_ok.py": _PALLAS_GOOD},
        select={"pallas-vmem-unbounded"},
    )
    assert [f for f in good if f.path.endswith("kern_ok.py")] == []


# ---- suppression ----------------------------------------------------------


def pytest_numlint_suppression_scope(tmp_path):
    # trailing on the flagged line and standalone directly above both
    # suppress; a directive two lines up does NOT leak downward
    found = _lint(
        tmp_path,
        {
            "models/sup.py": """
            import jax.numpy as jnp

            def f(x):
                a = jnp.exp(x)  # numlint: disable=unguarded-exp-log-div
                # numlint: disable=unguarded-exp-log-div
                b = jnp.exp(x)
                # numlint: disable=unguarded-exp-log-div
                pass
                c = jnp.exp(x)
                return a + b + c
            """,
        },
        select={"unguarded-exp-log-div"},
    )
    assert len(found) == 1 and found[0].line == 10


def pytest_suppressing_a_different_rule_does_not_cover(tmp_path):
    found = _lint(
        tmp_path,
        {
            "models/tag.py": """
            import jax.numpy as jnp

            def f(x):
                return jnp.exp(x)  # numlint: disable=nan-unsafe-where
            """,
        },
        select={"unguarded-exp-log-div"},
    )
    assert len(found) == 1


# ---- CLI: fourth suite, baseline ratchet ----------------------------------


def pytest_numerics_cli_gate_and_baseline(tmp_path, capsys):
    bad = tmp_path / "models" / "m.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(
        "import jax.numpy as jnp\n\n"
        "def f(x):\n"
        "    return jnp.exp(x)\n"
    )
    # the suite gates on its findings
    assert lint_main([str(bad), "--suite=numerics"]) == 1
    capsys.readouterr()
    # a written baseline absorbs them...
    bl = tmp_path / "bl.json"
    assert (
        lint_main(
            [str(bad), "--suite=numerics", f"--write-baseline={bl}"]
        )
        == 0
    )
    assert (
        lint_main([str(bad), "--suite=numerics", f"--baseline={bl}"]) == 0
    )
    capsys.readouterr()
    # ...but a reintroduced NEW finding still fails the gate, named
    bad.write_text(
        bad.read_text() + "\n\ndef g(x):\n    return jnp.log(x)\n"
    )
    assert (
        lint_main(
            [
                str(bad), "--suite=numerics", f"--baseline={bl}",
                "--format=github",
            ]
        )
        == 1
    )
    out = capsys.readouterr().out
    assert "unguarded-exp-log-div" in out
    assert "log" in out


def pytest_list_rules_includes_numerics(capsys):
    assert lint_main(["--list-rules", "--suite=numerics"]) == 0
    listed = capsys.readouterr().out
    assert "suite numerics (numlint gate" in listed
    for name in NUMERICS_RULES:
        assert name in listed, name
    assert "suite jax" not in listed


# ---- acceptance -----------------------------------------------------------


def pytest_merged_tree_is_clean_for_numerics_suite():
    """`--suite=numerics` exits 0 on the committed tree: every true
    positive (unclamped exp in schnet, bare sqrt in dimenet/common,
    bf16-reachable accumulations in dense_agg) was FIXED, the
    two deliberate raw gathers carry justified suppressions, and the
    committed baseline is EMPTY."""
    paths = [
        os.path.join(REPO_ROOT, d)
        for d in ("hydragnn_tpu", "examples", "benchmarks")
    ]
    result = analyze_paths(
        paths, select=rules_in_suite("numerics"), root=REPO_ROOT
    )
    assert not result.findings, [
        f"{f.path}:{f.line}: {f.rule}" for f in result.findings
    ]
    bl = json.load(open(os.path.join(REPO_ROOT, ".numlint-baseline.json")))
    assert bl["findings"] == []
