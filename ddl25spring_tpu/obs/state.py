"""The one global switch for run telemetry.

What the flag governs: everything that writes a file or enters a compiled
program — the ``SpanRecorder``'s Chrome-trace JSON and ``instant()``, the
timeline, memscope, the metrics logger's consumers, and the in-jit
``counters.emit`` / ``counters.mark`` callbacks.  What it does NOT govern
(always on, host-only, bounded): ``spans.span()``'s profiler annotation and
its ``(t_start, duration)`` sample in the ``counters`` rings, and those of
the serving scheduler's per-pass counts that a reader windows, in the same
rings.

The gated helpers key off this flag **at trace
time**: when disabled, the instrumentation helpers are Python-level no-ops
that insert nothing into jitted programs, so an instrumented step function
lowers to HLO *identical* to an uninstrumented one (asserted in
``tests/test_obs.py``).  Flipping the flag therefore requires re-tracing
(clear the jit cache or rebuild the step) — the price of true zero cost
when off, which matters more: the bench headline must not carry telemetry
overhead it didn't ask for.

Enable via ``DDL25_OBS=1`` in the environment, :func:`enable`, or the
:func:`scoped` context manager (tests).
"""

from __future__ import annotations

import contextlib
import os

_enabled: bool = os.environ.get("DDL25_OBS", "") not in ("", "0", "false")


def enabled() -> bool:
    """Is telemetry on?  Checked at TRACE time by every obs helper."""
    return _enabled


def enable(on: bool = True) -> None:
    """Turn telemetry on/off globally (affects subsequent traces only)."""
    global _enabled
    _enabled = bool(on)


@contextlib.contextmanager
def scoped(on: bool = True):
    """Temporarily set the telemetry flag (test harness use)."""
    global _enabled
    prev = _enabled
    _enabled = bool(on)
    try:
        yield
    finally:
        _enabled = prev
