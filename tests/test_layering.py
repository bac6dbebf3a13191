"""The engine does not import its instruments.

`parallel/`, `models/`, `ops/` and the serving engine's modules are what
a cell runs; `tools/`, `bench.py`, `benchmark/`, `analysis/` and most of
`obs/` watch them.  One case a file (found by glob), by ``ast`` alone.
"""

import ast
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
PKG = "ddl25spring_tpu"

# what the engine may name of `obs/` and `analysis/`: the set that holds
# today.  It may only shrink.
OBS_ALLOWED = {
    "obs",  # the package itself, for the in-jit `obs.emit`
    "spans", "state", "counters", "sentinels", "flight", "recorder",
    "timeline", "memscope",
}
ANALYSIS_ALLOWED = {"host_sanitizer"}
OUTSIDE = {"tools", "bench", "benchmark"}


def _engine_files() -> list[str]:
    pkg = ROOT / PKG
    files = [
        p for d in ("parallel", "models", "ops") for p in (pkg / d).glob("*.py")
    ] + [
        pkg / "serve" / f"{n}.py"
        for n in ("engine", "kv_pages", "paged_model", "prefix", "spec", "traffic")
    ]
    return sorted(str(p.relative_to(ROOT)) for p in files)


def _imported(path: Path) -> list[tuple[str, ...]]:
    """Every module path the file imports, as a tuple of its parts with
    the imported name last (`from a.b import c` -> a, b, c)."""
    package = path.relative_to(ROOT).parts[:-1]
    out = []
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            out += [tuple(a.name.split(".")) for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = package[: len(package) - node.level + 1] if node.level else ()
            base += tuple(node.module.split(".")) if node.module else ()
            out += [base + (a.name,) for a in node.names]
    return out


@pytest.mark.parametrize("relpath", _engine_files())
def test_engine_module_imports_no_instrument(relpath):
    path = ROOT / relpath
    assert path.exists(), relpath
    for parts in _imported(path):
        assert parts[0] not in OUTSIDE, parts
        if parts[0] != PKG or len(parts) < 2:
            continue
        if parts[1] == "obs":
            unit = parts[2] if len(parts) > 2 else "obs"
            assert unit in OBS_ALLOWED, parts
        if parts[1] == "analysis":
            assert len(parts) > 2 and parts[2] in ANALYSIS_ALLOWED, parts
