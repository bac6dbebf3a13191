"""Compiled-program probes, normalized across BACKENDS.

The compile-time analytics (obs/xla_analytics.py) lean on two
``Compiled`` APIs whose answers depend on the backend that compiled the
program:

- ``compiled.cost_analysis()``: one dict of counters, or nothing where
  the backend has no cost model;
- ``compiled.memory_analysis()``: a ``CompiledMemoryStats`` whose
  ``peak_memory_in_bytes`` some backends leave at 0 (the peak is then
  assembled from argument/output/temp sizes), and which some backends
  don't implement at all.

These two helpers are the single call-sites for both APIs — everything
else (utils/flops.compiled_flops included) goes through them.  The
manual-SPMD primitives need no wrapper: the code imports
``jax.shard_map``, ``lax.pcast`` and ``jax.typeof`` directly (jax 0.9.0,
the one installation there is; ``tests/test_compat.py`` pins the typing
rules of theirs the parallel stack relies on).
"""

from __future__ import annotations

# CompiledMemoryStats fields worth surfacing
_MEMORY_FIELDS = (
    "argument_size_in_bytes",
    "output_size_in_bytes",
    "temp_size_in_bytes",
    "alias_size_in_bytes",
    "generated_code_size_in_bytes",
    "peak_memory_in_bytes",
)


def compiled_cost_analysis(compiled) -> dict | None:
    """``compiled.cost_analysis()`` normalized to ONE flat dict (or None
    where the backend exposes no cost model)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 — no cost model on this backend
        return None
    if not ca:
        return None
    return dict(ca)


def compiled_memory_stats(compiled) -> dict | None:
    """``compiled.memory_analysis()`` normalized to a plain dict, with a
    ``peak_hbm_bytes`` estimate that works on every backend: its own
    ``peak_memory_in_bytes`` where it reports one, else
    ``arguments + outputs + temps + generated code - aliased`` (the
    compiled buffers that must coexist)."""
    ma = getattr(compiled, "memory_analysis", None)
    if ma is None:
        return None
    try:
        ma = ma()
    except Exception:  # noqa: BLE001 — backend without memory stats
        return None
    if ma is None:
        return None
    out = {
        k: int(getattr(ma, k)) for k in _MEMORY_FIELDS
        if getattr(ma, k, None) is not None
    }
    if not out:
        return None
    peak = out.get("peak_memory_in_bytes")
    if not peak:
        peak = (
            out.get("argument_size_in_bytes", 0)
            + out.get("output_size_in_bytes", 0)
            + out.get("temp_size_in_bytes", 0)
            + out.get("generated_code_size_in_bytes", 0)
            - out.get("alias_size_in_bytes", 0)
        )
    out["peak_hbm_bytes"] = int(peak)
    return out
