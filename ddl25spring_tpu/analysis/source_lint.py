"""JAX-pitfall source linter: AST rules over the repo's own Python.

The HLO rules (H*) judge what XLA *compiled*; these rules catch the
Python idioms that produce those hazards before a trace ever runs.  The
pack mirrors the failure modes this codebase has actually hit:

========  ========  ====================================================
rule      severity  pitfall
========  ========  ====================================================
S101      warn      ``os.environ`` / ``os.getenv`` read inside a
                    function of a traced-code module (``parallel/``,
                    ``ops/``, ``models/``, ``benchmarks.py``) — compiled
                    program structure silently depends on ambient
                    process state; route through
                    ``utils.config.env_flag``
S102      warn      a ``jax.jit`` / ``pjit`` call site in ``parallel/``
                    or ``benchmarks.py`` without ``donate_argnums`` /
                    ``donate_argnames`` — the PR-3 donation contract
                    says every step builder decides explicitly
S103      error     raw ``numpy`` (``np.*``) calls inside a jit- or
                    shard_map-decorated function (or a function nested
                    in one) — constant-folds at trace time on shapes,
                    silently wrong or host-synced on values
========  ========  ====================================================

Waivers use the shared file (``analysis/waivers.toml``) keyed on
``path`` + ``symbol``.  The walker is deliberately syntactic: it
resolves nothing across modules, so it can run on any file in
milliseconds as a CI gate (``tools/graft_lint.py``).
"""

from __future__ import annotations

import ast
import os
from typing import Iterable

from ddl25spring_tpu.analysis.rules import Finding

# module scopes per rule: path substrings relative to the repo root.
# ft/ builds the auto-resume/checkpoint steps that trace on the hot
# path, and sentinels compiles guards INTO programs — an env read
# inside any of them silently forks compiled program structure on
# ambient process state (PR-9 satellite: scope
# grown from parallel/+benchmarks to the ft and obs trace surfaces;
# PR-12 satellite: serve/ joins — the driver/engine resolve every
# DDL25_SERVE_* knob through utils.config.env_int at the entry point,
# and this scope keeps raw os.environ reads from creeping back into
# the compiled prefill/decode build path; PR-19 satellite: the obs
# modules grown since — timeline and memscope both gate behavior that
# serve/ft call sites reach, so their env resolution goes through the
# boundary too.  ft/elastic.py and serve/spec.py ride the ft/ and
# serve/ prefixes already.)
_TRACED_CODE_DIRS = (
    "ddl25spring_tpu/parallel/",
    "ddl25spring_tpu/ops/",
    "ddl25spring_tpu/models/",
    "ddl25spring_tpu/benchmarks.py",
    "ddl25spring_tpu/ft/",
    "ddl25spring_tpu/serve/",
    "ddl25spring_tpu/obs/sentinels.py",
    "ddl25spring_tpu/obs/timeline.py",
    "ddl25spring_tpu/obs/memscope.py",
)
_DONATE_SCOPE = (
    "ddl25spring_tpu/parallel/",
    "ddl25spring_tpu/benchmarks.py",
)

_JIT_NAMES = {"jit", "pjit"}
_TRACED_DECORATOR_NAMES = _JIT_NAMES | {"shard_map"}


def _dotted(node: ast.AST) -> str:
    """``jax.jit`` -> "jax.jit"; best-effort for Name/Attribute chains."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _is_jit_like(node: ast.AST) -> bool:
    """Does this expression denote jax.jit / pjit (any import spelling)?"""
    last = _dotted(node).rsplit(".", 1)[-1]
    return last in _JIT_NAMES


def _decorator_is_traced(dec: ast.AST) -> bool:
    """True for @jax.jit, @jit, @partial(jax.jit, ...), @partial(
    shard_map, ...), @shard_map(...), @jax.jit(...)-style decorators."""
    if isinstance(dec, ast.Call):
        fn = _dotted(dec.func).rsplit(".", 1)[-1]
        if fn == "partial" and dec.args:
            return _decorator_is_traced(dec.args[0])
        return fn in _TRACED_DECORATOR_NAMES
    return _dotted(dec).rsplit(".", 1)[-1] in _TRACED_DECORATOR_NAMES


def _in_scope(relpath: str, scopes: tuple[str, ...]) -> bool:
    rp = relpath.replace(os.sep, "/")
    return any(rp.startswith(s) or rp == s for s in scopes)


class _Walker(ast.NodeVisitor):
    def __init__(self, relpath: str, numpy_aliases: set[str]):
        self.relpath = relpath
        self.numpy_aliases = numpy_aliases
        self.findings: list[Finding] = []
        # (function name, is-traced-context) stack
        self.stack: list[tuple[str, bool]] = []

    # ------------------------------------------------------------ scopes

    @property
    def qualname(self) -> str:
        return ".".join(n for n, _ in self.stack) or "<module>"

    @property
    def in_function(self) -> bool:
        return bool(self.stack)

    @property
    def in_traced(self) -> bool:
        return any(traced for _, traced in self.stack)

    def visit_FunctionDef(self, node):
        traced = any(_decorator_is_traced(d) for d in node.decorator_list)
        self.stack.append((node.name, traced))
        # S102: a bare @jax.jit decorator is a jit call site with no
        # donate_argnums at all
        for dec in node.decorator_list:
            if not isinstance(dec, ast.Call) and _is_jit_like(dec):
                self._s102(node.lineno, f"@{_dotted(dec)} on {node.name}")
        self.generic_visit(node)
        self.stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    # ------------------------------------------------------------- rules

    def _emit(self, **kw):
        self.findings.append(Finding(
            source=f"{self.relpath}:{kw.pop('lineno')}",
            op=self.qualname, **kw,
        ))

    def _s102(self, lineno: int, what: str):
        if not _in_scope(self.relpath, _DONATE_SCOPE):
            return
        self._emit(
            rule="S102", severity="warn", lineno=lineno,
            message=(
                f"{what} compiles without donate_argnums/donate_argnames"
                " — params/opt-state double-reside in HBM unless the "
                "builder decided otherwise on purpose"
            ),
            fix_hint=(
                "pass donate_argnums=bucketing.donate_argnums(donate) "
                "like every other step builder, or waive with the reason "
                "donation cannot apply here"
            ),
        )

    def visit_Call(self, node):
        # S102: jax.jit(...) / pjit(...) and partial(jax.jit, ...) sites
        target = None
        if _is_jit_like(node.func):
            target = node
        elif (
            _dotted(node.func).rsplit(".", 1)[-1] == "partial"
            and node.args
            and _is_jit_like(node.args[0])
        ):
            target = node
        if target is not None:
            kws = {k.arg for k in target.keywords}
            if not kws & {"donate_argnums", "donate_argnames"}:
                self._s102(node.lineno, _dotted(node.func) + "(...)")
        # S101: os.getenv(...) calls
        if _dotted(node.func) == "os.getenv":
            self._s101(node.lineno, "os.getenv")
        # S103: np.*(...) calls in traced context
        fn = _dotted(node.func)
        base = fn.split(".", 1)[0]
        if (
            base in self.numpy_aliases
            and "." in fn
            and self.in_traced
        ):
            self._emit(
                rule="S103", severity="error", lineno=node.lineno,
                message=(
                    f"raw numpy call {fn}(...) inside a jit/shard_map-"
                    "traced function — it constant-folds at trace time "
                    "(or host-syncs) instead of entering the compiled "
                    "program"
                ),
                fix_hint="use jnp (or hoist the computation out of the "
                         "traced function if it really is static "
                         "metadata)",
            )
        self.generic_visit(node)

    def _s101(self, lineno: int, what: str):
        if not self.in_function:
            return  # module-level env read at import time: the boundary
        if not _in_scope(self.relpath, _TRACED_CODE_DIRS):
            return
        self._emit(
            rule="S101", severity="warn", lineno=lineno,
            message=(
                f"{what} read inside {self.qualname}() of a traced-code "
                "module — the compiled program's structure now depends "
                "on ambient process state at trace/build time"
            ),
            fix_hint=(
                "resolve the env var through "
                "ddl25spring_tpu.utils.config.env_flag at the entry "
                "point and pass the value in explicitly"
            ),
        )

    def visit_Attribute(self, node):
        # catches os.environ.get/os.environ[...] (the subscript's value
        # is this attribute) and bare os.environ references, exactly once
        if _dotted(node) == "os.environ":
            self._s101(node.lineno, "os.environ")
        else:
            self.generic_visit(node)


def _numpy_aliases(tree: ast.Module) -> set[str]:
    """Names the module binds to the real numpy (``import numpy as np``)
    — NOT jax.numpy, whose ops are exactly what S103 recommends."""
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "numpy":
                    out.add(a.asname or "numpy")
    return out


def lint_source(
    text: str, relpath: str
) -> list[Finding]:
    """Run the S-rules over one file's source."""
    try:
        tree = ast.parse(text, filename=relpath)
    except SyntaxError as e:
        return [Finding(
            rule="S000", severity="error", op=relpath,
            source=f"{relpath}:{e.lineno or 0}",
            message=f"file does not parse: {e.msg}",
            fix_hint="fix the syntax error",
        )]
    w = _Walker(relpath, _numpy_aliases(tree))
    w.visit(tree)
    return w.findings


def lint_paths(
    paths: Iterable[str], root: str | None = None
) -> list[Finding]:
    """Lint files given absolute or root-relative paths; findings carry
    root-relative sources so waiver ``path`` globs are portable."""
    root = os.path.abspath(root or os.getcwd())
    out: list[Finding] = []
    for p in paths:
        ap = p if os.path.isabs(p) else os.path.join(root, p)
        rel = os.path.relpath(ap, root)
        with open(ap) as f:
            out.extend(lint_source(f.read(), rel))
    return out


def repo_python_files(root: str) -> list[str]:
    """The source set the repo gate lints: the installable package plus
    the bench driver (tools/tests/lab stay out — they run on the host,
    where env reads and numpy are the point)."""
    out = []
    pkg = os.path.join(root, "ddl25spring_tpu")
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        out.extend(
            os.path.join(dirpath, f)
            for f in filenames
            if f.endswith(".py")
        )
    bench = os.path.join(root, "bench.py")
    if os.path.exists(bench):
        out.append(bench)
    return sorted(out)


def lint_repo(root: str | None = None) -> list[Finding]:
    root = os.path.abspath(root or os.getcwd())
    return lint_paths(repo_python_files(root), root)
