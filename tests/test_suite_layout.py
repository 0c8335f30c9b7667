"""Static guard of the suite's own layout (``ast`` only, milliseconds).

The driver distributes tier-1 by file, so the end-to-end trainings are
spread over ``test_graphs*.py`` and their helper lives in ONE uncollected
module (``tests/e2e_train.py``). A test file that imports from a
``test_graphs*`` module would make it a library again (and, by two names,
two copies of its work directory in one process).
"""

import ast
import functools
import pathlib

import pytest

TESTS = pathlib.Path(__file__).parent


@functools.lru_cache
def _trees(root):
    return {p: ast.parse(p.read_text()) for p in sorted(root.glob("*.py"))}


def _imports_from_test_graphs(root):
    """``file:line`` of every import of a ``test_graphs*`` module, by either
    name (``test_graphs…`` or ``tests.test_graphs…``)."""
    found = []
    for path, tree in _trees(root).items():
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                module = node.module or ""
                names = [module] + [f"{module}.{a.name}" for a in node.names]
            elif isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            else:
                continue
            if any(
                n.removeprefix("tests.").startswith("test_graphs")
                for n in names
            ):
                found.append(f"{path.name}:{node.lineno}")
    return found


def _fast_tier_ignored(root):
    """The ``collect_ignore`` list ``conftest.py`` sets under
    ``HYDRAGNN_FAST_TEST=1``."""
    for node in ast.walk(ast.parse((root / "conftest.py").read_text())):
        if isinstance(node, ast.Assign) and any(
            isinstance(t, ast.Name) and t.id == "collect_ignore"
            for t in node.targets
        ):
            return ast.literal_eval(node.value)
    raise AssertionError("conftest.py sets no collect_ignore")


def _calls_helper(node):
    return any(
        isinstance(n, ast.Call)
        and isinstance(n.func, ast.Name)
        and n.func.id.startswith("unittest_train_model")
        for n in ast.walk(node)
    )


def pytest_no_test_file_imports_from_test_graphs():
    assert _imports_from_test_graphs(TESTS) == []


@pytest.mark.parametrize(
    "line",
    [
        "from test_graphs import unittest_train_model",
        "from tests.test_graphs import FULL",
        "from tests import test_graphs_lengths",
        "import test_graphs_multihead",
    ],
)
def pytest_guard_sees_an_import_put_back(tmp_path, line):
    (tmp_path / "test_x.py").write_text(f"def pytest_x():\n    {line}\n")
    assert _imports_from_test_graphs(tmp_path) == ["test_x.py:2"]


def pytest_helper_defined_once_in_an_uncollected_module():
    homes = [
        path.name
        for path, tree in _trees(TESTS).items()
        for node in ast.walk(tree)
        if isinstance(node, ast.FunctionDef)
        and node.name == "unittest_train_model"
    ]
    assert len(homes) == 1 and not homes[0].startswith("test_"), homes


def pytest_fast_tier_ignore_list_names_existing_files():
    missing = [f for f in _fast_tier_ignored(TESTS) if not (TESTS / f).is_file()]
    assert missing == []


def pytest_fast_tier_skips_every_training():
    """A file that calls the helper is in the FAST tier's ignore list, or
    the calling case carries its own ``skipif`` on ``HYDRAGNN_FAST_TEST``
    (``test_bucketed_layouts.py``: one training among unit tests)."""
    ignored = set(_fast_tier_ignored(TESTS))
    unguarded = []
    for path, tree in _trees(TESTS).items():
        if path.name in ignored or not path.name.startswith("test_"):
            continue
        for node in ast.walk(tree):
            if isinstance(node, ast.FunctionDef) and _calls_helper(node):
                marks = " ".join(ast.unparse(d) for d in node.decorator_list)
                if "skipif" not in marks or "HYDRAGNN_FAST_TEST" not in marks:
                    unguarded.append(f"{path.name}::{node.name}")
    assert unguarded == []
    assert {p.name for p in TESTS.glob("test_graphs*.py")} <= ignored
