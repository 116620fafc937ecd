"""The library imports nothing that stands above it.

``dtc_tpu`` is what ``main.py``, ``chip_smoke.py``, ``benchmark/run.py`` and
the scripts import; a module of it that imports one of them back works only
from the repo's root with the working directory on ``sys.path`` (as
``analysis/kernels.py`` did with ``bench`` until PR 34). One case per
top-level subpackage, so a failure names the package.
"""

import ast
import os

import pytest

PACKAGE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "dtc_tpu")
ABOVE = {"bench", "chip_smoke", "main", "plot", "scripts", "benchmark", "tests"}
#: "" is the package's own top-level modules (``__init__.py``, ``generate.py``).
GROUPS = [""] + sorted(d for d in os.listdir(PACKAGE)
                       if os.path.isfile(os.path.join(PACKAGE, d, "__init__.py")))


def _modules(group: str) -> list[str]:
    if not group:
        return [os.path.join(PACKAGE, f) for f in sorted(os.listdir(PACKAGE)) if f.endswith(".py")]
    return [os.path.join(d, f) for d, _, files in sorted(os.walk(os.path.join(PACKAGE, group)))
            for f in sorted(files) if f.endswith(".py")]


def _imported_roots(path: str) -> set[tuple[str, int]]:
    """(first segment, line) of every absolute import, wherever in the module it stands."""
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    roots = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            roots |= {(alias.name.split(".")[0], node.lineno) for alias in node.names}
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            roots.add((node.module.split(".")[0], node.lineno))
    return roots


@pytest.mark.parametrize("group", GROUPS, ids=[g or "top_level" for g in GROUPS])
def test_library_imports_nothing_above_it(group):
    modules = _modules(group)
    assert modules
    upward = [f"{os.path.relpath(path, PACKAGE)}:{line} imports {root}"
              for path in modules for root, line in sorted(_imported_roots(path)) if root in ABOVE]
    assert not upward, upward
