"""Percentiles, medians and window arithmetic shared by the readers.

Kept here, under the benchmark's own directory, so that no PR that claims
a gain can change how a number is reduced.
"""

from __future__ import annotations

import math
from typing import Iterable


def percentile(xs: Iterable[float], q: float) -> float | None:
    """``q``-th percentile (0..100) by linear interpolation between the
    closest ranks (numpy's default); ``None`` for an empty sample."""
    s = sorted(xs)
    if not s:
        return None
    pos = (len(s) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def median(xs: Iterable[float]) -> float | None:
    return percentile(xs, 50.0)


def in_window(t: float | None, t_open: float, t_close: float) -> bool:
    """Whether stamp ``t`` lies inside the closed window."""
    return t is not None and t_open <= t <= t_close


def distinct_prefills(
    requests: Iterable[dict], t_open: float, t_close: float
) -> list[tuple[float, float]]:
    """One ``(start, wall)`` per prefill DISPATCH that began inside the
    window.  A dispatch serves up to ``prefill_batch`` requests, which
    all carry its ``prefill_start_t``: it is counted once."""
    seen: dict[float, float] = {}
    for r in requests:
        t0 = r.get("prefill_start_t")
        if in_window(t0, t_open, t_close) and r.get("prefill_s") is not None:
            seen[t0] = r["prefill_s"]
    return sorted(seen.items())


def ttft_samples_ms(record: dict) -> list[float]:
    """``first_token_t - arrival_t`` of every request SENT inside the
    window.  A request that was rejected, or has no first token when the
    grace ends, enters as the grace's end: never as a missing sample."""
    out = []
    for r in record["requests"]:
        if not r["sent_in_window"]:
            continue
        end = r["first_token_t"]
        if r["rejected"] is not None or end is None:
            end = record["t_grace_end"]
        out.append((end - r["arrival_t"]) * 1e3)
    return out


def tpot_samples_ms(record: dict) -> list[float]:
    """``(done_t - first_token_t) / (tokens - 1)`` of every request sent
    AND completed inside the window: the gap a streaming client sees,
    other requests' prefill stalls included.  Requests still decoding at
    the close give no sample (the measurement cut them, not the system),
    which flatters the figure slightly: long requests are the ones cut."""
    return [
        (r["done_t"] - r["first_token_t"]) / (r["n_tokens"] - 1) * 1e3
        for r in record["requests"]
        if r["sent_in_window"] and r["done_t"] is not None
        and r["done_t"] <= record["t_close"] and r["n_tokens"] > 1
    ]
