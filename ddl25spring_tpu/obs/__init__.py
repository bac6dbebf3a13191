"""Run telemetry that works everywhere the framework runs.

The framework's perf story previously rested on two instruments: manual
``perf_counter`` segments and the ``jax.profiler`` device tracer, which
only the process that holds the chip can run, for a short window.  This
package is the always-on, low-overhead substrate that needs no XLA
profiler (whether a device trace completes on the chip at hand is probed
by ``chip_smoke.py``'s ``runtime_probe`` on every chip run):

- :mod:`~ddl25spring_tpu.obs.spans` — ``span()``, the one way to mark a
  host-side region.  ALWAYS ON: every span enters a
  ``jax.profiler.TraceAnnotation`` (so any open profiler session sees it,
  in ``/host:CPU`` on the device trace's clock) and writes ``(t_start,
  duration)`` into the ring of its name in ``counters``.  Behind the
  flag: the nested Chrome-trace/Perfetto JSON of ``SpanRecorder``;
- :mod:`~ddl25spring_tpu.obs.logger` — append-only JSONL step metrics with
  a run-metadata header (mesh, layout, git sha, jax version);
- :mod:`~ddl25spring_tpu.obs.counters` — ALWAYS ON: bounded, stamped,
  process-global host rings (``sample`` / ``window`` / ``wrapped`` /
  ``oldest_t``) that hold every span and those of the serving
  scheduler's per-pass counts that a reader cuts a window out of after
  the fact (the benchmark's per-layer metrics).  Behind the flag: values from INSIDE jitted
  programs via ``jax.debug.callback`` (MoE aux/load stats, pipeline tick
  cadence, ZeRO collective bytes);
- ``tools/obs_report.py`` — folds a run directory into a summary table
  (steps/sec p50/p95, MFU, bubble fraction, h2d bandwidth);

Rates, utilizations and idle shares are not measured here: ``benchmark/run.py``
measures them on the chip and the driver records them in ``PERF_LEDGER.jsonl``
(``PERF.md``).

Runtime health (the operable half — the compile-time analytics'
runtime counterpart):

- :mod:`~ddl25spring_tpu.obs.sentinels` — in-step numerics sentinels
  (loss / grad global-norm / non-finite leaves / update ratio computed
  INSIDE the compiled step; policy log/halt/skip on violation; gated by
  ``DDL25_SENTINELS`` with the same HLO-identical-when-disabled pin);
- :mod:`~ddl25spring_tpu.obs.recorder` — crash-surviving flight
  recorder (ring buffer of the last N step records, dumped as
  ``flight.json`` on unhandled exception / SIGTERM / atexit);
- :mod:`~ddl25spring_tpu.obs.watchdog` — stall watchdog (fires when no
  step completes within a deadline; dumps all host thread stacks plus
  the flight record);
- :mod:`~ddl25spring_tpu.obs.timeline` — graft-trace: the unified run
  timeline (typed append-only ``timeline.jsonl`` every subsystem emits
  into: serve request lifecycles with virtual + wall clocks, chaos
  fires, reshape windows, autosave, watchdog, sentinel violations —
  merged with spans + flight into one Perfetto trace by
  ``tools/trace_export.py``).

What is always on costs the host about 2.5 us a span and 0.7 us a sample
(``benchmark/tools/span_cost.py``) and touches no compiled program; the
compiled programs carry ``jax.named_scope`` and kernel names, which are
metadata.  Everything ELSE is gated by one trace-time flag
(:mod:`~ddl25spring_tpu.obs.state`): disabled (the default), instrumented
step functions lower to HLO identical to uninstrumented ones — zero cost,
pinned in ``tests/test_obs.py``.  Enable with ``DDL25_OBS=1`` or
``obs.enable()`` *before* building/tracing the step.
"""

from ddl25spring_tpu.obs import sentinels
from ddl25spring_tpu.obs.counters import (
    CounterSet,
    counters,
    gpipe_bubble_fraction,
)
from ddl25spring_tpu.obs.recorder import FlightRecorder, flight
from ddl25spring_tpu.obs.sentinels import SentinelViolation
from ddl25spring_tpu.obs.watchdog import StallWatchdog, thread_stacks
from ddl25spring_tpu.obs.logger import (
    MetricsLogger,
    iter_jsonl,
    read_jsonl,
    run_metadata,
)
from ddl25spring_tpu.obs.spans import (
    SpanRecorder,
    get_recorder,
    instant,
    set_recorder,
    span,
)
from ddl25spring_tpu.obs.state import enable, enabled, scoped
from ddl25spring_tpu.obs.timeline import Timeline, timeline

# compile-time analytics (obs/xla_analytics.py, obs/compile_report.py) are
# imported lazily by their consumers — they pull in the parallel stack and
# must not tax `import ddl25spring_tpu.obs` on the hot bench path.

__all__ = [
    "CounterSet",
    "FlightRecorder",
    "MetricsLogger",
    "SentinelViolation",
    "SpanRecorder",
    "StallWatchdog",
    "Timeline",
    "timeline",
    "counters",
    "flight",
    "sentinels",
    "thread_stacks",
    "enable",
    "enabled",
    "get_recorder",
    "gpipe_bubble_fraction",
    "instant",
    "iter_jsonl",
    "read_jsonl",
    "run_metadata",
    "scoped",
    "set_recorder",
    "span",
]
