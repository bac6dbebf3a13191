"""What `benchmark/` calls of the package exists under the names it uses.

The benchmark's own CPU tests are not tier-1, so a PR that renames or
deletes a name a cell calls would learn it on the chip.  The cases are
read from the benchmark's files with ``ast``, never typed by hand: a new
family's imports join them by themselves.
"""

import ast
import importlib
import inspect
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
PKG = "ddl25spring_tpu"


def _surface() -> list[tuple[str, str]]:
    """Every (module, name) of the package that a file of `benchmark/`
    outside its tests imports, and every (module, name.attribute) it
    reads off a name it imported."""
    pairs = set()
    for path in sorted((ROOT / "benchmark").rglob("*.py")):
        if "tests" in path.relative_to(ROOT).parts:
            continue
        tree = ast.parse(path.read_text())
        bound = {}  # local name -> (module, name) it was imported as
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.level == 0 and (
                node.module == PKG or (node.module or "").startswith(PKG + ".")
            ):
                for a in node.names:
                    bound[a.asname or a.name] = (node.module, a.name)
        pairs.update(bound.values())
        for node in ast.walk(tree):
            if (
                isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id in bound
            ):
                module, name = bound[node.value.id]
                pairs.add((module, f"{name}.{node.attr}"))
    return sorted(pairs)


@pytest.mark.parametrize("module,name", _surface())
def test_package_has_what_the_benchmark_calls(module, name):
    first, _, attr = name.partition(".")
    mod = importlib.import_module(module)
    if not hasattr(mod, first):
        importlib.import_module(f"{module}.{first}")  # a submodule not yet bound
    if attr:
        assert hasattr(getattr(mod, first), attr)


def _serving_cells() -> list[str]:
    return sorted(
        p.name for p in (ROOT / "benchmark" / "workloads").glob("*.json")
        if isinstance(json.loads(p.read_text()).get("engine"), dict)
    )


@pytest.mark.parametrize("cell_file", _serving_cells())
def test_engine_knobs_cover_every_serving_cell(cell_file):
    """`runners/serve.py` hands `{**engine_knobs(), **cell["engine"]}` to
    `_build_engine`, which passes them on as keywords: each must be one."""
    from ddl25spring_tpu.serve import driver
    from ddl25spring_tpu.serve.engine import ServeEngine

    cell = json.loads((ROOT / "benchmark" / "workloads" / cell_file).read_text())
    accepted = set(inspect.signature(ServeEngine.__init__).parameters)
    knobs = {**driver.engine_knobs(), **cell["engine"]}
    assert set(knobs) <= accepted, sorted(set(knobs) - accepted)
