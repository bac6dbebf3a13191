"""FLOPs accounting and MFU (model-FLOPs utilization).

The reference publishes no utilization numbers — its only perf instrument is
wall-clock (``lab/run-b2.sh:16-17``).  On TPU the honest headline is
achieved FLOP/s against the chip's bf16 peak; this module derives the
per-step FLOP count from the *compiled* XLA program (the compiler's own cost
model, not a hand napkin) and maps ``device_kind`` to the public per-chip
peak so drivers can print an MFU line next to samples/sec.
"""

from __future__ import annotations

import logging
from typing import Any

import jax

_log = logging.getLogger(__name__)

# Public per-chip dense bf16 peaks (FLOP/s).  Matched by prefix against
# ``jax.Device.device_kind`` (e.g. "TPU v5 lite" -> v5e).  Longest prefix
# wins so "TPU v5 lite" does not match the "TPU v5" (v5p) entry.
PEAK_BF16_FLOPS: dict[str, float] = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v5": 459e12,        # v5p reports kind "TPU v5"
    "TPU v6 lite": 918e12,   # Trillium / v6e
    "TPU v6e": 918e12,
    "TPU7x": 2307e12,        # Ironwood (dense fp8 is higher; bf16 peak)
}

# Fuller per-chip roofline specs for the compile-time projections
# (obs/xla_analytics.py): bf16 peak, HBM bandwidth, and aggregate
# per-chip ICI bandwidth.  Public datasheet numbers, approximate — the
# projection is a planning instrument, not a measurement.
CHIP_SPECS: dict[str, dict[str, float]] = {
    "TPU v4": {
        "peak_bf16_flops": 275e12,
        "hbm_bytes_per_s": 1.228e12,
        "ici_bytes_per_s": 0.30e12,    # 6 links x ~50 GB/s
    },
    "TPU v5e": {
        "peak_bf16_flops": 197e12,
        "hbm_bytes_per_s": 0.819e12,
        "ici_bytes_per_s": 0.20e12,    # 4 links x ~50 GB/s
    },
    "TPU v5p": {
        "peak_bf16_flops": 459e12,
        "hbm_bytes_per_s": 2.765e12,
        "ici_bytes_per_s": 0.60e12,
    },
    "TPU v6e": {
        "peak_bf16_flops": 918e12,
        "hbm_bytes_per_s": 1.64e12,
        "ici_bytes_per_s": 0.448e12,
    },
}


def chip_peak_flops(device: jax.Device | None = None) -> float | None:
    """Per-chip bf16 peak FLOP/s for ``device`` (default:
    ``jax.devices()[0]`` — a backend that cannot be reached raises).
    On a TPU the datasheet peak of its ``device_kind``; a kind that is
    not in :data:`PEAK_BF16_FLOPS` is an error, not a default.  Off a
    TPU there is no peak (None): a host never stands in for a chip."""
    d = device if device is not None else jax.devices()[0]
    if d.platform != "tpu":
        return None
    kind = getattr(d, "device_kind", "") or ""
    best = None
    for prefix, peak in PEAK_BF16_FLOPS.items():
        if kind.startswith(prefix) and (best is None or len(prefix) > best[0]):
            best = (len(prefix), peak)
    if best is None:
        raise KeyError(
            f"no bf16 peak known for device_kind {kind!r}: add it to "
            "PEAK_BF16_FLOPS with its source"
        )
    return best[1]


def compiled_flops(jitted_fn: Any, *args: Any, **kwargs: Any) -> float | None:
    """Total FLOPs of one invocation per XLA's cost analysis of the compiled
    program (fwd + bwd + optimizer — everything inside the jit boundary).

    Thin wrapper over :func:`ddl25spring_tpu.utils.compat.
    compiled_cost_analysis` — the one shared ``cost_analysis()``
    call-site, so the backends' differences are handled in one place
    (obs/xla_analytics.py rides the same helper).  Hits the jit cache
    when the function was already called with these shapes.  Returns
    None where the backend exposes no cost model — with a one-line
    warning naming why, so an MFU-less bench line is explained in the
    log instead of silently blank.
    """
    from ddl25spring_tpu.utils.compat import compiled_cost_analysis

    try:
        compiled = jitted_fn.lower(*args, **kwargs).compile()
    except Exception as e:  # noqa: BLE001 — degrade to None, but say why
        _log.warning(
            "lower/compile for cost analysis failed (%s: %s); MFU will "
            "be reported as None",
            type(e).__name__,
            e,
        )
        return None
    ca = compiled_cost_analysis(compiled)
    flops = float(ca.get("flops", 0.0)) if ca else 0.0
    if flops <= 0:
        _log.warning(
            "XLA cost analysis returned no flops count for %s; "
            "MFU will be reported as None",
            getattr(jitted_fn, "__name__", jitted_fn),
        )
        return None
    return flops


def mfu(
    flops_per_step: float | None,
    step_time_s: float,
    n_chips: int = 1,
    device: jax.Device | None = None,
) -> tuple[float | None, float | None]:
    """Return ``(achieved_tflops_per_chip, mfu_fraction)``.

    ``flops_per_step`` is the whole-mesh program's FLOPs (XLA cost analysis
    counts the full sharded computation); both outputs are per chip.  Either
    element is None when its ingredient is unavailable.
    """
    if flops_per_step is None or step_time_s <= 0:
        return None, None
    achieved = flops_per_step / step_time_s / max(n_chips, 1)
    peak = chip_peak_flops(device)
    frac = achieved / peak if peak else None
    return achieved / 1e12, frac
