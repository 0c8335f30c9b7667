"""host-sync-in-hot-loop: per-batch device->host round trips.

``float(metrics["loss"])`` on a jit output blocks the host until the
dispatched program finishes AND serializes the async pipeline (the device
idles while the host turns each result around), which is exactly why the
trainer accumulates packed device vectors and reads them back once per
epoch (``Trainer._acc_add`` / ``_acc_read``). This rule
fails CI when someone reintroduces the per-batch sync.

Scope: the per-step loops live in a handful of files (the hot set below);
everything else — epoch drivers doing once-per-epoch host work, data
pipelines operating on host numpy — does host conversions legitimately,
so the rule stays narrow rather than drowning the tree in suppressions.

A loop is **hot** when its body dispatches device work — it calls
something that looks like a compiled step (``*_step`` / ``*_multi`` /
``*_scan`` / ``put_batch*`` / ``_dispatch*`` / ``.apply``). Host-side
collection loops (masking already-fetched numpy arrays) never dispatch,
so they stay out of scope by construction.

Detection, two tiers:

- **hot loop bodies**: ``float(x)`` / ``int(x)`` on non-trivial
  expressions, ``.item()``, and ``np.asarray(x)`` / ``np.array(x)`` — the
  implicit-transfer spellings. Explicit ``jax.device_get`` is allowed: it
  is the documented way to do an INTENTIONAL bulk fetch (and the
  transfer-guard test enforces that only explicit fetches happen).
- **helpers called from hot loops** (same-file resolution, depth 1):
  ``float``/``int``/``.item()`` only — numpy conversions inside helpers
  routinely operate on host data (collate, mask collection) and are
  checked by the runtime transfer guard instead.
"""

import ast
import re
from typing import Dict, Iterable, List, Set

from hydragnn_tpu.analysis.core import (
    Finding,
    ModuleInfo,
    Rule,
    dotted_name,
    matches_any,
    register,
    walk_no_nested_functions,
)

# the files holding per-step dispatch loops (see module docstring for why
# this is a narrow, named set; extend it when a new per-batch loop lands)
HOT_FILE_PATTERNS = (
    "*/train/trainer.py",
    "*/train/predict.py",
    "*/train/partitioned.py",
    "*/serve/server.py",
    "train/trainer.py",
    "train/predict.py",
    "train/partitioned.py",
    "serve/server.py",
)

# a call whose terminal name matches marks its enclosing loop as
# device-dispatching ("hot")
_DISPATCH_HINT = re.compile(
    r"(_step|_multi|_scan|put_batch|_dispatch|train_epoch|^apply$)"
)

# int()/float() on these is host-side bookkeeping, not a device sync
_TRIVIAL_CALLEES = {
    "len",
    "round",
    "min",
    "max",
    "abs",
    "os.getenv",
    "getattr",
    "time.time",
    "time.monotonic",
    "time.perf_counter",
    "str",
    "repr",
    "input",
}

_NUMPY_CONVERTERS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}


def _is_trivial_scalar_arg(arg: ast.AST) -> bool:
    """True for arguments that cannot be device values."""
    if isinstance(arg, ast.Constant):
        return True
    if isinstance(arg, ast.Call):
        return dotted_name(arg.func) in _TRIVIAL_CALLEES
    if isinstance(arg, ast.JoinedStr):
        return True
    return False


def _terminal_name(func: ast.AST) -> str:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return ""


@register
class HostSyncInHotLoop(Rule):
    name = "host-sync-in-hot-loop"
    description = (
        "Per-batch host synchronization (float()/int()/.item()/np.asarray "
        "on device values) inside a per-step dispatch loop — accumulate on "
        "device and read back once per epoch (Trainer._acc_add/_acc_read)"
    )

    def applies_to(self, module: ModuleInfo) -> bool:
        return matches_any(module.rel_path, HOT_FILE_PATTERNS)

    def check(self, module: ModuleInfo) -> Iterable[Finding]:
        defs = self._collect_defs(module)
        findings: List[Finding] = []
        seen: Set[int] = set()  # node ids — loops nest, report each once
        hot_helpers: Dict[str, str] = {}  # helper name -> reached-from

        for fn in self._functions(module):
            for loop in walk_no_nested_functions(fn):
                if not isinstance(loop, (ast.For, ast.While)):
                    continue
                body = list(self._loop_body_nodes(loop))
                if not self._dispatches(body):
                    continue
                for node in body:
                    if id(node) in seen:
                        continue
                    seen.add(id(node))
                    hit = self._classify(node, in_loop=True)
                    if hit:
                        findings.append(
                            module.finding(
                                self.name,
                                node,
                                f"{hit} inside the per-step loop of "
                                f"`{fn.name}` — this is a device->host "
                                "sync per batch; accumulate on device "
                                "and fetch once per epoch",
                            )
                        )
                    if isinstance(node, ast.Call):
                        helper = self._called_helper(node)
                        if helper and helper in defs:
                            hot_helpers.setdefault(helper, fn.name)

        for helper, reached_from in hot_helpers.items():
            for node in walk_no_nested_functions(defs[helper]):
                if id(node) in seen:
                    continue
                hit = self._classify(node, in_loop=False)
                if hit:
                    seen.add(id(node))
                    findings.append(
                        module.finding(
                            self.name,
                            node,
                            f"{hit} in `{helper}`, reached from the "
                            f"per-step loop of `{reached_from}` — this "
                            "runs once per batch; keep the value on "
                            "device",
                        )
                    )
        return findings

    # ---- helpers -------------------------------------------------------
    @staticmethod
    def _functions(module: ModuleInfo):
        for node in ast.walk(module.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield node

    @staticmethod
    def _collect_defs(module: ModuleInfo) -> Dict[str, ast.FunctionDef]:
        """name -> def, for same-file helper resolution (methods resolve
        by bare name: ``self._acc_add`` -> ``_acc_add``)."""
        defs: Dict[str, ast.FunctionDef] = {}
        for node in ast.walk(module.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault(node.name, node)
        return defs

    @staticmethod
    def _loop_body_nodes(loop):
        """Every node in the loop's body (not its iterator — that runs
        once) without crossing nested def boundaries."""
        for stmt in loop.body + getattr(loop, "orelse", []):
            yield stmt
            yield from walk_no_nested_functions(stmt)

    @staticmethod
    def _dispatches(body_nodes) -> bool:
        for node in body_nodes:
            if isinstance(node, ast.Call) and _DISPATCH_HINT.search(
                _terminal_name(node.func)
            ):
                return True
        return False

    @staticmethod
    def _called_helper(call: ast.Call):
        """'self.helper(...)' or 'helper(...)' -> 'helper'."""
        f = call.func
        if (
            isinstance(f, ast.Attribute)
            and isinstance(f.value, ast.Name)
            and f.value.id in ("self", "cls")
        ):
            return f.attr
        if isinstance(f, ast.Name):
            return f.id
        return None

    @staticmethod
    def _classify(node: ast.AST, in_loop: bool):
        if not isinstance(node, ast.Call):
            return None
        name = dotted_name(node.func)
        if name in ("float", "int") and len(node.args) == 1:
            if not _is_trivial_scalar_arg(node.args[0]):
                return f"`{name}(...)`"
            return None
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr == "item"
            and not node.args
            and not node.keywords
        ):
            return "`.item()`"
        if in_loop and name in _NUMPY_CONVERTERS and node.args:
            if not _is_trivial_scalar_arg(node.args[0]) and not isinstance(
                node.args[0], (ast.List, ast.Tuple, ast.Dict)
            ):
                return f"`{name}(...)`"
        return None
