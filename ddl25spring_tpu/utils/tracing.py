"""Tracing / profiling hooks.

The reference's only observability is manual ``perf_counter`` segments in the
FL servers (``hfl_complete.py:274-307``) and whole-run ``$SECONDS`` in the
launchers (``run-b1.sh:6,16-17``) — kept here as
:class:`ddl25spring_tpu.utils.metrics.Timer`.  This module adds the TPU-side
instruments those hooks cannot see:

- :func:`trace` — a ``jax.profiler`` trace context producing a TensorBoard/
  Perfetto-loadable profile of XLA execution (MXU utilization, HBM traffic,
  collective time — the real versions of the reference's wall-clock guesses);
  named host-side regions inside it are :func:`ddl25spring_tpu.obs.spans.span`;
- :class:`StepTimer` — steady-state steps/sec with correct async-dispatch
  handling (blocks on the result, discards warmup/compile).
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Iterator

import jax


@contextlib.contextmanager
def trace(log_dir: str, *, host_tracer_level: int = 2) -> Iterator[None]:
    """Capture a jax.profiler trace of everything inside the block.

    Only the process that holds the chip can trace it.  Keep the window
    short (a few steps): traces are large and tracing slows the host.
    ``chip_smoke.py``'s ``runtime_probe`` records on every chip run
    whether a trace completes and holds a device plane.
    """
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = host_tracer_level
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class StepTimer:
    """Throughput meter for jitted train loops.

    ``tick(result)`` blocks until ``result`` is ready (so async dispatch
    doesn't fold the next step's work into this step's time) and records the
    interval.  The first ``warmup`` intervals (compile) are discarded.
    """

    def __init__(self, warmup: int = 2):
        self.warmup = warmup
        self.times: list[float] = []
        self._last: float | None = None
        self._seen = 0

    def tick(self, result: Any = None) -> None:
        if result is not None:
            jax.block_until_ready(result)
        now = time.perf_counter()
        if self._last is not None:
            self._seen += 1
            if self._seen > self.warmup:
                self.times.append(now - self._last)
        self._last = now

    def _require_times(self) -> list[float]:
        if not self.times:
            raise ValueError("no timed steps yet (all in warmup?)")
        return self.times

    @property
    def mean_step_s(self) -> float:
        times = self._require_times()
        return sum(times) / len(times)

    def percentile(self, q: float) -> float:
        """q-th percentile (0-100) of the recorded step intervals."""
        times = sorted(self._require_times())
        if len(times) == 1:
            return times[0]
        # linear interpolation between closest ranks (numpy default)
        pos = (len(times) - 1) * q / 100.0
        lo = int(pos)
        hi = min(lo + 1, len(times) - 1)
        return times[lo] + (times[hi] - times[lo]) * (pos - lo)

    @property
    def p50_step_s(self) -> float:
        return self.percentile(50)

    @property
    def p95_step_s(self) -> float:
        return self.percentile(95)

    @property
    def min_step_s(self) -> float:
        return min(self._require_times())

    def steps_per_sec(self) -> float:
        """Steady-state rate from the MEDIAN interval: one GC pause or
        host hiccup in the window must not skew a bench line (the mean
        remains available as ``mean_step_s``)."""
        return 1.0 / self.p50_step_s
