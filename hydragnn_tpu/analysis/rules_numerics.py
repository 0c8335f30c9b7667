"""Numerics & kernel-safety rules (numlint, ``--suite=numerics``).

The ROADMAP's MFU phase 2 (superblock Pallas kernels, int8 aggregation,
wider bf16) makes precision and on-chip memory MORE dangerous to get
wrong: a bf16 accumulation, an unclamped ``exp``, or an unmasked gather
in a padded-edge kernel all pass tier-1 on CPU f32 and land as silent
per-head accuracy loss, not a crash. These rules are the lint half of
numlint; the compiled-memory ratchet (``analysis/mem.py``) and the
``nan_sentinel`` runtime harness (``analysis/guards.py``) are the
post-compile and runtime halves.

Every rule here is a heuristic over dataflow the AST can see — a
per-function map of reaching assignments, so ``count = jnp.maximum(
count, 1.0)`` upstream of ``x / count`` reads as guarded. Sites the
pass cannot prove safe but a human can are suppressed in place with
``# numlint: disable=rule-name`` plus a justification (the CI gate
diffs are reviewed; a bare disable is a smell).
"""

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from hydragnn_tpu.analysis.core import (
    Finding,
    ModuleInfo,
    Rule,
    dotted_name,
    function_defs,
    matches_any,
    register,
    walk_no_nested_functions,
)

# numeric model/kernel code — where an accumulation or an unclamped
# transcendental turns into per-head accuracy loss
_NUMERIC_PATTERNS = (
    "hydragnn_tpu/models/*", "models/*", "*/models/*",
    "hydragnn_tpu/graph/*", "graph/*", "*/graph/*",
    "hydragnn_tpu/ops/*", "ops/*", "*/ops/*",
)
# the padded-edge kernels: gathers here must honor the padded-edge masking
# contract (clip / explicit where-mask of every padded slot, as
# ops/dense_agg.py gather_neighbors does)
_OPS_PATTERNS = (
    "hydragnn_tpu/ops/*", "ops/*", "*/ops/*",
)
# the ONE sanctioned precision-decision point plus the step builder
# that applies it (train/steps.py casts batches/params per the policy)
_PRECISION_SANCTIONED = (
    "hydragnn_tpu/models/create.py", "models/create.py",
    "*/models/create.py",
    "hydragnn_tpu/train/steps.py", "train/steps.py", "*/train/steps.py",
)

_F32_DTYPES = {
    "jnp.float32", "jnp.float64", "jax.numpy.float32",
    "jax.numpy.float64", "np.float32", "np.float64", "numpy.float32",
    "numpy.float64",
}
_LOW_DTYPES = {
    "jnp.bfloat16", "jnp.float16", "jax.numpy.bfloat16",
    "jax.numpy.float16", "np.float16", "numpy.float16",
}
_CREATION_TAILS = {
    "array", "asarray", "zeros", "ones", "full", "empty", "arange",
    "linspace", "zeros_like", "ones_like", "full_like",
}


def _tail(callee: str) -> str:
    return callee.rsplit(".", 1)[-1]


def _call_tail(node: ast.Call) -> str:
    # an Attribute callee keeps its method name even when the receiver
    # is itself a call (`jnp.where(...).sum(...)` — dotted_name returns
    # '' there, and the `.sum` is exactly the accumulation to check)
    if isinstance(node.func, ast.Attribute):
        return node.func.attr
    return _tail(dotted_name(node.func))


def _is_dtype(node: ast.AST, names: Set[str], strings: Tuple[str, ...]):
    if isinstance(node, ast.Constant) and node.value in strings:
        return True
    return dotted_name(node) in names


def _is_f32_dtype(node: ast.AST) -> bool:
    return _is_dtype(node, _F32_DTYPES, ("float32", "float64"))


def _is_low_dtype(node: ast.AST) -> bool:
    return _is_dtype(node, _LOW_DTYPES, ("bfloat16", "float16"))


# ---- per-function reaching-assignment dataflow ----------------------------

Env = Dict[str, List[Tuple[int, ast.AST]]]


def _env_of(scope: ast.AST) -> Env:
    """name -> ordered [(lineno, rhs expr)] for simple assignments in a
    function (or module) body, nested defs excluded."""
    env: Env = {}
    for node in walk_no_nested_functions(scope):
        target = None
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
        ):
            target = node.targets[0].id
        elif (
            isinstance(node, ast.AnnAssign)
            and isinstance(node.target, ast.Name)
            and node.value is not None
        ):
            target = node.target.id
        if target is not None:
            env.setdefault(target, []).append((node.lineno, node.value))
    for entries in env.values():
        entries.sort(key=lambda e: e[0])
    return env


def _reaching(
    env: Env, name: str, line: int
) -> Optional[Tuple[int, ast.AST]]:
    """The LAST assignment to ``name`` strictly before ``line`` — so a
    clamp reassignment (``count = jnp.maximum(count, 1.0)``) wins over
    the raw reduction it replaced."""
    best = None
    for ln, val in env.get(name, ()):
        if ln < line and (best is None or ln > best[0]):
            best = (ln, val)
    return best


def _scopes(module: ModuleInfo):
    """(scope_node, env, is_kernel) for module top level and every
    function. Pallas kernel bodies (``def kernel``/``*_kernel``) are
    exempt from the accumulation rules — the WRAPPER's visible upcast is
    the contract; inside the kernel everything is already f32 refs."""
    yield module.tree, _env_of(module.tree), False
    for fn in function_defs(module):
        kernel = fn.name == "kernel" or fn.name.endswith("_kernel")
        yield fn, _env_of(fn), kernel


def _has_f32_marker(expr: ast.AST) -> bool:
    for sub in ast.walk(expr):
        if isinstance(sub, ast.Call):
            if (
                isinstance(sub.func, ast.Attribute)
                and sub.func.attr == "astype"
                and sub.args
                and _is_f32_dtype(sub.args[0])
            ):
                return True
            for kw in sub.keywords:
                if kw.arg == "dtype" and _is_f32_dtype(kw.value):
                    return True
        if _is_f32_dtype(sub):  # positional dtype arg / bare reference
            return True
    return False


def _f32_safe(
    expr: Optional[ast.AST], env: Env, line: int, depth: int = 4
) -> bool:
    """Can the AST PROVE this expression is f32 (or wider)? Constants
    and unknowns are NOT safe — in a bf16 forward they inherit bf16."""
    if depth <= 0 or expr is None:
        return False
    if _has_f32_marker(expr):
        return True
    if isinstance(expr, ast.Name):
        prev = _reaching(env, expr.id, line)
        return prev is not None and _f32_safe(
            prev[1], env, prev[0], depth - 1
        )
    if isinstance(expr, (ast.Subscript, ast.Attribute, ast.Starred)):
        return _f32_safe(expr.value, env, line, depth - 1)
    if isinstance(expr, ast.UnaryOp):
        return _f32_safe(expr.operand, env, line, depth - 1)
    if isinstance(expr, ast.BinOp):
        return _f32_safe(expr.left, env, line, depth - 1) or _f32_safe(
            expr.right, env, line, depth - 1
        )
    if isinstance(expr, ast.Call):
        tail = _call_tail(expr)
        if tail == "where" and len(expr.args) >= 3:
            return _f32_safe(
                expr.args[1], env, line, depth - 1
            ) or _f32_safe(expr.args[2], env, line, depth - 1)
        if tail in (
            "reshape", "transpose", "squeeze", "sum", "mean",
        ) and isinstance(expr.func, ast.Attribute):
            return _f32_safe(expr.func.value, env, line, depth - 1)
    return False


# ---- guard-expression helpers ---------------------------------------------


def _contains_call_tail(expr: ast.AST, tails: Set[str]) -> bool:
    for sub in ast.walk(expr):
        if isinstance(sub, ast.Call) and _call_tail(sub) in tails:
            return True
    return False


def _contains_add_const(expr: ast.AST) -> bool:
    """``x + 1.0``-style eps offsets — the additive guard idiom."""
    for sub in ast.walk(expr):
        if isinstance(sub, ast.BinOp) and isinstance(sub.op, ast.Add):
            for side in (sub.left, sub.right):
                if (
                    isinstance(side, ast.Constant)
                    and isinstance(side.value, (int, float))
                    and side.value > 0
                ):
                    return True
    return False


def _names_mention(expr: ast.AST, fragment: str) -> bool:
    for sub in ast.walk(expr):
        ident = ""
        if isinstance(sub, ast.Name):
            ident = sub.id
        elif isinstance(sub, ast.Attribute):
            ident = sub.attr
        if fragment in ident.lower():
            return True
    return False


_CLAMP_TAILS = {"maximum", "clip", "clamp"}

_COUNT_FRAGMENTS = (
    "mask", "valid", "n_node", "n_edge", "deg", "count", "cnt",
    "length", "size",
)


def _is_count_operand(expr: ast.AST) -> bool:
    """Bool masks and integer counts — their reductions accumulate in
    int, never bf16. Unwraps trailing subscripts/attribute chains."""
    while isinstance(expr, (ast.Subscript,)):
        expr = expr.value
    ident = ""
    if isinstance(expr, ast.Name):
        ident = expr.id
    elif isinstance(expr, ast.Attribute):
        ident = expr.attr
    low = ident.lower()
    return any(f in low for f in _COUNT_FRAGMENTS)


# ---- rule 1: low-precision accumulation -----------------------------------


@register
class LowPrecisionAccum(Rule):
    name = "low-precision-accum"
    suite = "numerics"
    description = (
        "segment_sum/cumsum/matmul/long-axis .sum whose operand can be "
        "bf16 without an f32 upcast or preferred_element_type — a "
        "K-neighbor accumulation in bf16 loses ~3 decimal digits; "
        "upcast the masked operand (.astype(jnp.float32)) and cast the "
        "result back, like ops/dense_agg.py"
    )

    def applies_to(self, module: ModuleInfo) -> bool:
        return matches_any(module.rel_path, _NUMERIC_PATTERNS)

    def check(self, module: ModuleInfo) -> Iterable[Finding]:
        in_ops = matches_any(module.rel_path, _OPS_PATTERNS)
        findings: List[Finding] = []
        for scope, env, kernel in _scopes(module):
            if kernel:
                continue  # the wrapper's visible upcast is the contract
            for node in walk_no_nested_functions(scope):
                if not isinstance(node, ast.Call):
                    continue
                callee = dotted_name(node.func)
                tail = _call_tail(node)
                if tail == "segment_sum" and "." in callee:
                    # bare-name segment_sum is graph/segment.py's
                    # upcasting wrapper — only raw jax.ops dispatch
                    # needs its operand proven f32
                    data = node.args[0] if node.args else None
                    if data is not None and not _f32_safe(
                        data, env, node.lineno
                    ):
                        findings.append(
                            module.finding(
                                self.name,
                                node,
                                f"{callee} accumulates its data operand "
                                "at the operand's dtype — under the "
                                "bf16 policy that is a bf16 scatter-"
                                "add; upcast (.astype(jnp.float32)) "
                                "before the segment op (or call the "
                                "graph.segment wrapper, which does)",
                            )
                        )
                elif tail == "cumsum":
                    if any(kw.arg == "dtype" for kw in node.keywords):
                        continue
                    if callee.startswith(("np.", "numpy.")):
                        continue  # host-side numpy (f64 accumulators)
                    operand = (
                        node.func.value
                        if isinstance(node.func, ast.Attribute)
                        and callee not in ("jnp.cumsum",)
                        else (node.args[0] if node.args else None)
                    )
                    if operand is not None and _is_count_operand(operand):
                        continue  # integer offset/count prefix sums
                    if operand is not None and not _f32_safe(
                        operand, env, node.lineno
                    ):
                        findings.append(
                            module.finding(
                                self.name,
                                node,
                                "cumsum without dtype= runs the prefix "
                                "sum at the operand dtype — pass "
                                "dtype=jnp.float32 (bf16 prefix sums "
                                "drift with length)",
                            )
                        )
                elif in_ops and tail in ("dot", "matmul", "dot_general"):
                    if any(
                        kw.arg == "preferred_element_type"
                        for kw in node.keywords
                    ):
                        continue
                    if all(
                        _f32_safe(a, env, node.lineno) for a in node.args
                    ) and node.args:
                        continue
                    findings.append(
                        module.finding(
                            self.name,
                            node,
                            f"{callee} without preferred_element_type "
                            "accumulates at the operand dtype — on the "
                            "MXU a bf16 contraction should accumulate "
                            "f32; pass preferred_element_type="
                            "jnp.float32",
                        )
                    )
                elif in_ops and tail == "sum":
                    axis = None
                    for kw in node.keywords:
                        if kw.arg == "axis":
                            axis = kw.value
                    if axis is None and node.args and not isinstance(
                        node.func, ast.Attribute
                    ):
                        pass  # jnp.sum(x) full reduce — skip
                    if axis is None and isinstance(
                        node.func, ast.Attribute
                    ) and node.args:
                        axis = node.args[0]
                    elif axis is None and not isinstance(
                        node.func, ast.Attribute
                    ) and len(node.args) >= 2:
                        axis = node.args[1]
                    # only leading/neighbor axes: axis=-1 is the short
                    # feature axis (cheap, error-bounded); no axis is a
                    # scalar reduce outside the hot aggregation shape
                    if not (
                        isinstance(axis, ast.Constant)
                        and axis.value in (0, 1)
                    ):
                        continue
                    operand = (
                        node.func.value
                        if isinstance(node.func, ast.Attribute)
                        else (node.args[0] if node.args else None)
                    )
                    if operand is not None and _is_count_operand(operand):
                        continue  # bool-mask/count sums reduce to int
                    if operand is not None and not _f32_safe(
                        operand, env, node.lineno
                    ):
                        findings.append(
                            module.finding(
                                self.name,
                                node,
                                ".sum over the neighbor axis at the "
                                "operand dtype — in the dense bf16 "
                                "path this is a K-length bf16 "
                                "accumulation; upcast the masked "
                                "operand to f32 and cast the result "
                                "back to the input dtype",
                            )
                        )
        return findings


# ---- rule 2: precision-policy bypass --------------------------------------


@register
class PrecisionPolicyBypass(Rule):
    name = "precision-policy-bypass"
    suite = "numerics"
    description = (
        "bf16/f16 dtype literal in a cast/creation outside the "
        "sanctioned precision sites (models/create.resolve_precision "
        "decides, train/steps.py applies) — a stray low-precision cast "
        "silently overrides the policy the MFU ledger accounts against"
    )

    def applies_to(self, module: ModuleInfo) -> bool:
        return not matches_any(module.rel_path, _PRECISION_SANCTIONED)

    def check(self, module: ModuleInfo) -> Iterable[Finding]:
        findings: List[Finding] = []
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            hit = None
            if (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "astype"
                and node.args
                and _is_low_dtype(node.args[0])
            ):
                hit = "astype cast"
            else:
                for kw in node.keywords:
                    if kw.arg == "dtype" and _is_low_dtype(kw.value):
                        hit = "dtype= argument"
                        break
                if hit is None and _call_tail(node) in _CREATION_TAILS:
                    for arg in node.args:
                        if _is_low_dtype(arg):
                            hit = "creation dtype"
                            break
            if hit is not None:
                findings.append(
                    module.finding(
                        self.name,
                        node,
                        f"low-precision {hit} outside the precision "
                        "policy — models/create.resolve_precision is "
                        "the ONE decision point and train/steps.py the "
                        "one application site; route through the "
                        "policy (or justify with a numlint suppression)",
                    )
                )
        return findings


# ---- rule 3: unguarded exp/log/sqrt/division ------------------------------


def _exp_guarded(arg: ast.AST, env: Env, line: int) -> bool:
    if isinstance(arg, ast.Constant):
        return True
    if _contains_call_tail(arg, _CLAMP_TAILS | {"minimum", "where"}):
        return True
    # max-shifted softmax idiom: exp(logits - seg_max[...]) / exp(a - amax)
    for sub in ast.walk(arg):
        if isinstance(sub, ast.BinOp) and isinstance(sub.op, ast.Sub):
            if _names_mention(sub.right, "max"):
                return True
    # exp(-x) where x is provably nonnegative-ish (clamped/abs/squared)
    if isinstance(arg, ast.UnaryOp) and isinstance(arg.op, ast.USub):
        inner = arg.operand
        if _contains_call_tail(
            inner, _CLAMP_TAILS | {"abs", "square", "softplus"}
        ):
            return True
        if isinstance(inner, ast.Name):
            prev = _reaching(env, inner.id, line)
            if prev is not None and _contains_call_tail(
                prev[1], _CLAMP_TAILS | {"abs", "square", "softplus"}
            ):
                return True
    return False


def _log_guarded(arg: ast.AST, env: Env, line: int) -> bool:
    if isinstance(arg, ast.Constant):
        return True
    if _contains_call_tail(
        arg, _CLAMP_TAILS | {"abs", "exp", "where", "finfo"}
    ):
        return True
    if _contains_add_const(arg) or _names_mention(arg, "eps"):
        return True
    if isinstance(arg, ast.Name):
        prev = _reaching(env, arg.id, line)
        if prev is not None:
            return _log_guarded(prev[1], env, prev[0])
    return False


def _reduction_like(expr: ast.AST) -> bool:
    """A computed ARRAY reduction that can legitimately hit exactly
    zero — masked sums, segment scatters, padded counts. The Python
    builtin ``sum(...)`` (host-side config math) does not count."""
    for sub in ast.walk(expr):
        if not isinstance(sub, ast.Call):
            continue
        tail = _call_tail(sub)
        if tail in ("segment_sum", "segment_count", "count_nonzero"):
            return True
        if tail == "sum" and (
            isinstance(sub.func, ast.Attribute)
            or "." in dotted_name(sub.func)
        ):
            return True
    return False


def _div_guarded(expr: ast.AST) -> bool:
    return (
        _contains_call_tail(expr, _CLAMP_TAILS)
        or _contains_add_const(expr)
        or _names_mention(expr, "eps")
    )


def _sqrt_trigger(expr: ast.AST) -> bool:
    """sqrt args that can reach zero/negative: differences, ratios,
    powers-of-differences, reductions. Plain widths/fan-ins (init
    bounds) never trigger."""
    for sub in ast.walk(expr):
        if isinstance(sub, ast.BinOp) and isinstance(
            sub.op, (ast.Sub, ast.Pow, ast.Div)
        ):
            return True
    return _reduction_like(expr)


def _sqrt_guarded(expr: ast.AST) -> bool:
    return (
        _contains_call_tail(expr, _CLAMP_TAILS | {"abs", "where"})
        or _contains_add_const(expr)
        or _names_mention(expr, "eps")
    )


@register
class UnguardedExpLogDiv(Rule):
    name = "unguarded-exp-log-div"
    suite = "numerics"
    description = (
        "exp/log/sqrt/division on an unbounded computed input in model/"
        "kernel code without a clamp/eps — exp overflows bf16 at ~88, "
        "log(0)/x÷0 poison the loss, sqrt(0) has an infinite gradient; "
        "clamp the argument (jnp.maximum/minimum/+eps) or use the "
        "double-where _safe_sqrt idiom"
    )

    def applies_to(self, module: ModuleInfo) -> bool:
        return matches_any(module.rel_path, _NUMERIC_PATTERNS)

    def check(self, module: ModuleInfo) -> Iterable[Finding]:
        findings: List[Finding] = []
        seen: Set[Tuple[int, int]] = set()

        def flag(node, msg):
            key = (node.lineno, node.col_offset)
            if key in seen:
                return
            seen.add(key)
            findings.append(module.finding(self.name, node, msg))

        for scope, env, _kernel in _scopes(module):
            for node in walk_no_nested_functions(scope):
                if isinstance(node, ast.Call):
                    tail = _call_tail(node)
                    arg = node.args[0] if node.args else None
                    if arg is None:
                        continue
                    if tail == "exp" and not _exp_guarded(
                        arg, env, node.lineno
                    ):
                        flag(
                            node,
                            "exp of an unbounded argument — overflows "
                            "to inf (bf16 at ~88); clamp with "
                            "jnp.minimum(arg, 0.0)/max-shift before "
                            "exponentiating",
                        )
                    elif tail in ("log", "log2", "log10") and (
                        not _log_guarded(arg, env, node.lineno)
                    ):
                        flag(
                            node,
                            "log of an unclamped argument — log(0) is "
                            "-inf and poisons every reduction it "
                            "touches; add an eps (jnp.log(x + eps) / "
                            "jnp.maximum(x, eps))",
                        )
                    elif tail == "sqrt":
                        expr = arg
                        if isinstance(arg, ast.Name):
                            prev = _reaching(env, arg.id, node.lineno)
                            if prev is None:
                                continue
                            expr = prev[1]
                        if _sqrt_trigger(expr) and not (
                            _sqrt_guarded(arg) or _sqrt_guarded(expr)
                        ):
                            flag(
                                node,
                                "sqrt of a difference/reduction that "
                                "can reach exactly zero — the gradient "
                                "is inf at 0 and NaNs the backward "
                                "pass; use the double-where _safe_sqrt "
                                "idiom (models/schnet.py) or add an eps",
                            )
                elif isinstance(node, ast.BinOp) and isinstance(
                    node.op, ast.Div
                ):
                    den = node.right
                    if _div_guarded(den):
                        continue
                    expr = den
                    if isinstance(den, ast.Name):
                        prev = _reaching(env, den.id, node.lineno)
                        if prev is None:
                            continue
                        expr = prev[1]
                        if _div_guarded(expr):
                            continue
                    if _reduction_like(expr):
                        flag(
                            node,
                            "division by a computed reduction — masked "
                            "sums/segment counts hit exactly zero on "
                            "padded slots; guard the denominator "
                            "(jnp.maximum(den, 1.0) or + eps)",
                        )
        return findings


# ---- rule 4: the jnp.where grad-NaN trap ----------------------------------

_TRAP_TAILS = {"sqrt", "rsqrt", "log", "log1p", "log2", "log10"}


def _branch_guarded(inner: ast.AST, env: Env, line: int) -> bool:
    if isinstance(inner, ast.Constant):
        return True
    if _contains_call_tail(inner, _CLAMP_TAILS | {"abs", "where"}):
        return True
    if _contains_add_const(inner) or _names_mention(inner, "eps"):
        return True
    if isinstance(inner, ast.Name):
        prev = _reaching(env, inner.id, line)
        if prev is not None:
            return _branch_guarded(prev[1], env, prev[0])
    return False


@register
class NanUnsafeWhere(Rule):
    name = "nan-unsafe-where"
    suite = "numerics"
    description = (
        "jnp.where selecting away from a NaN-producing branch — BOTH "
        "branches are evaluated AND differentiated, so sqrt/log/÷0 in "
        "the unselected branch still NaNs the gradient; sanitize the "
        "argument with an INNER where first (double-where idiom)"
    )

    def applies_to(self, module: ModuleInfo) -> bool:
        return matches_any(module.rel_path, _NUMERIC_PATTERNS)

    def check(self, module: ModuleInfo) -> Iterable[Finding]:
        findings: List[Finding] = []
        for scope, env, _kernel in _scopes(module):
            for node in walk_no_nested_functions(scope):
                if not (
                    isinstance(node, ast.Call)
                    and _call_tail(node) == "where"
                    and len(node.args) >= 3
                ):
                    continue
                hit = None
                for branch in (node.args[1], node.args[2]):
                    for sub in ast.walk(branch):
                        if (
                            isinstance(sub, ast.Call)
                            and _call_tail(sub) in _TRAP_TAILS
                            and sub.args
                            and not _branch_guarded(
                                sub.args[0], env, node.lineno
                            )
                        ):
                            hit = _call_tail(sub)
                            break
                        if (
                            isinstance(sub, ast.BinOp)
                            and isinstance(sub.op, ast.Div)
                            and _reduction_like(sub.right)
                            and not _div_guarded(sub.right)
                        ):
                            hit = "division"
                            break
                    if hit:
                        break
                if hit:
                    findings.append(
                        module.finding(
                            self.name,
                            node,
                            f"where branch computes {hit} on an "
                            "unsanitized argument — jnp.where "
                            "evaluates (and differentiates) BOTH "
                            "branches, so the masked-out NaN still "
                            "reaches the gradient; wrap the argument "
                            "in an inner where (double-where idiom)",
                        )
                    )
        return findings


# ---- rule 5: unmasked gather ids in the padded-edge kernels ---------------

_ID_HINTS = ("idx", "ids", "snd", "rcv", "gid", "seg", "nbr")
_SANCTIONED_PRODUCERS = {
    "_pad_edges", "_pad_ids", "_safe_gather", "clip", "where",
    "minimum", "mod", "arange", "clamp",
}
_SEGMENT_TAILS = {
    "segment_sum", "segment_max", "segment_min", "segment_prod",
}


def _index_name(sub: ast.Subscript) -> Optional[str]:
    s = sub.slice
    if isinstance(s, ast.Name):
        low = s.id.lower()
        if any(h in low for h in _ID_HINTS):
            return s.id
    return None


@register
class UnmaskedGatherId(Rule):
    name = "unmasked-gather-id"
    suite = "numerics"
    description = (
        "gather/segment op in ops/ whose index operand is not provably "
        "routed through the padded-edge masking contract (clip+where, "
        "as dense_agg.gather_neighbors) — a padded or stale id reads (or "
        "scatters) out of contract silently; mask the ids or the result"
    )

    def applies_to(self, module: ModuleInfo) -> bool:
        return matches_any(module.rel_path, _OPS_PATTERNS)

    def check(self, module: ModuleInfo) -> Iterable[Finding]:
        findings: List[Finding] = []
        for scope, env, kernel in _scopes(module):
            if kernel:
                continue  # kernels see pre-masked refs by contract
            # names that flow through ANY where() in this scope count
            # as mask-consumed (the gather result is neutralized there)
            masked_names: Set[str] = set()
            for node in walk_no_nested_functions(scope):
                if (
                    isinstance(node, ast.Call)
                    and _call_tail(node) == "where"
                ):
                    for sub in ast.walk(node):
                        if isinstance(sub, ast.Name):
                            masked_names.add(sub.id)
            for stmt in walk_no_nested_functions(scope):
                if not isinstance(stmt, (ast.Assign, ast.Return)):
                    continue
                value = stmt.value
                if value is None:
                    continue
                # where-wrapped inline gathers are mask-consumed
                wrapped: Set[int] = set()
                for sub in ast.walk(value):
                    if (
                        isinstance(sub, ast.Call)
                        and _call_tail(sub) == "where"
                    ):
                        wrapped.update(id(s) for s in ast.walk(sub))
                targets: Set[str] = set()
                if isinstance(stmt, ast.Assign):
                    for t in stmt.targets:
                        if isinstance(t, ast.Name):
                            targets.add(t.id)
                # a gather passed to a callee ALONGSIDE a mask arg is
                # mask-consumed there (dense_sum(x[nbr], nmask))
                for sub in ast.walk(value):
                    if not isinstance(sub, ast.Call):
                        continue
                    if any(
                        _names_mention(a, "mask")
                        for a in [*sub.args,
                                  *[k.value for k in sub.keywords]]
                    ):
                        wrapped.update(id(s) for s in ast.walk(sub))
                for sub in ast.walk(value):
                    if not isinstance(sub, ast.Subscript):
                        continue
                    idx = _index_name(sub)
                    if idx is None or id(sub) in wrapped:
                        continue
                    prev = _reaching(env, idx, stmt.lineno)
                    if prev is not None and _contains_call_tail(
                        prev[1], _SANCTIONED_PRODUCERS
                    ):
                        continue
                    if targets and targets <= masked_names:
                        continue  # result is masked downstream
                    findings.append(
                        module.finding(
                            self.name,
                            sub,
                            f"gather by {idx!r} with no visible "
                            "masking contract — ids must come from "
                            "_pad_edges/_safe_gather/clip, or the "
                            "gathered rows must be neutralized in a "
                            "jnp.where before accumulation",
                        )
                    )
            for node in walk_no_nested_functions(scope):
                if (
                    isinstance(node, ast.Call)
                    and _call_tail(node) in _SEGMENT_TAILS
                    and "." in dotted_name(node.func)
                    and not any(
                        kw.arg == "num_segments" for kw in node.keywords
                    )
                    and len(node.args) < 3
                ):
                    findings.append(
                        module.finding(
                            self.name,
                            node,
                            "segment op without num_segments — the "
                            "output length becomes data-dependent "
                            "(max(ids)+1), so a padded id silently "
                            "grows the output; pass num_segments "
                            "explicitly",
                        )
                    )
        return findings


# ---- rule 6: Pallas calls outside a VMEM-budget gate ----------------------


@register
class PallasVmemUnbounded(Rule):
    name = "pallas-vmem-unbounded"
    suite = "numerics"
    description = (
        "pl.pallas_call in a module with no *_enabled VMEM-budget gate "
        "— local_gather.window_halo sizes the working set against "
        "_VMEM_BLOCK_BUDGET before dispatching; an ungated kernel OOMs "
        "VMEM at a shape the CPU tests never see"
    )

    def check(self, module: ModuleInfo) -> Iterable[Finding]:
        calls = [
            n
            for n in ast.walk(module.tree)
            if isinstance(n, ast.Call)
            and _call_tail(n) == "pallas_call"
        ]
        if not calls:
            return []
        for node in module.tree.body:
            if not (
                isinstance(node, ast.FunctionDef)
                and node.name.endswith("_enabled")
            ):
                continue
            for sub in ast.walk(node):
                ident = ""
                if isinstance(sub, ast.Name):
                    ident = sub.id
                elif isinstance(sub, ast.Attribute):
                    ident = sub.attr
                up = ident.upper()
                if "VMEM" in up or "BUDGET" in up:
                    return []  # the module carries a budget gate
        return [
            module.finding(
                self.name,
                node,
                "pallas_call with no module-level *_enabled gate "
                "referencing a VMEM/BUDGET constant — size the "
                "kernel's working set against a budget (see "
                "ops/local_gather.window_halo) before dispatching",
            )
            for node in calls
        ]
