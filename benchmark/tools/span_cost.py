#!/usr/bin/env python3
"""What one ``obs.spans.span`` costs on this host with no profiler session
open, and one ``obs.counters.sample``: microseconds each, as JSON.  Also
what a span costs when the instrumentation is ON: inside an open
``jax.profiler`` session (``span_profiler_open_us``) and with telemetry
enabled, the Chrome-trace recorder included (``span_obs_on_us``).

    JAX_PLATFORMS=cpu python3 benchmark/tools/span_cost.py

A host number (no device is touched): run it on the machine whose
scheduler it taxes, beside the cell whose tick it is compared with.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from ddl25spring_tpu.obs import counters, spans, state  # noqa: E402

N = 200_000


def per_call_us(fn, n: int = N) -> float:
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for i in range(n):
            fn(i)
        best = min(best, (time.perf_counter() - t0) / n * 1e6)
    return best


def spanned(i: int) -> None:
    with spans.span("cost.span", cat="serve", active=i, queue=2, pages_used=3):
        pass


def profiler_open_us(n: int = 20_000) -> float:
    """A span inside an open profiler session (every span is an event of
    the trace, so few of them: the session keeps what it is given)."""
    import jax

    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            return per_call_us(spanned, n)
        finally:
            jax.profiler.stop_trace()


def obs_on_us(n: int = 20_000) -> float:
    """A span with telemetry enabled: the ``SpanRecorder`` keeps a Chrome
    event for it as well."""
    old = spans.set_recorder(spans.SpanRecorder())
    try:
        with state.scoped(True):
            return per_call_us(spanned, n)
    finally:
        spans.set_recorder(old)


def main() -> int:
    print(json.dumps({
        "span_us": per_call_us(spanned),
        "sample_us": per_call_us(lambda i: counters.sample("cost.sample", i, 0.0)),
        "empty_loop_us": per_call_us(lambda i: None),
        "span_obs_on_us": obs_on_us(),
        "span_profiler_open_us": profiler_open_us(),
        "cpus": os.cpu_count(),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
