"""On-device counters surfaced through ``jax.debug.callback``.

A profiler trace is a short, separate run; values that live *inside*
jitted step functions — MoE router load-balance
stats, per-tick pipeline progress, ZeRO collective volumes — are surfaced
by a host callback in every run: ``emit()`` inserts a ``jax.debug.callback``
whose host side folds the value into a named accumulator, and ``mark()``
records (index, host arrival time) pairs so tick cadence can be estimated
without any device tracing.

Zero cost when disabled: every inserter checks :func:`state.enabled` at
TRACE time and inserts nothing when telemetry is off — the lowered HLO is
byte-identical to the uninstrumented program (``tests/test_obs.py``).
When enabled, the cost is one small host transfer per emit per device
shard (callbacks fire once per shard under ``shard_map``; the accumulator
sees every shard's value, which is exactly what load-balance stats want).

Static facts that are known at trace time and carry no runtime cost even
when enabled — e.g. bytes moved by ZeRO's all_gather per step — go through
:func:`add_static`.

Always on, whatever the flag says: the host-side RINGS
(:meth:`CounterSet.sample` / :meth:`CounterSet.window`).  Every
:func:`ddl25spring_tpu.obs.spans.span` writes ``(t_start, duration)`` into
the ring of its name, and the serving scheduler writes its per-pass counts
(``serve.active_slots``, ``serve.prefill.prompt_tokens``, ...), stamped on
absolute ``time.perf_counter()``.  A ring keeps a name's newest
:data:`RING_CAP` samples in insertion order, so a reader in the same
process can cut any window out of it after the fact; ``wrapped`` and
``oldest_t`` tell a ring that has lost the window's first samples from a
window in which nothing happened.  Nothing here touches a jitted program.
"""

from __future__ import annotations

import json
import math
import os
import threading
import time
from typing import Any

import numpy as np

from ddl25spring_tpu.obs import state

# samples a ring keeps per name: a 51 s window of 3 ms decode ticks is
# 17,000; 2 x 2^17 doubles are 2 MiB a name, reserved on its first sample
RING_CAP = 1 << 17


class _Ring:
    """The newest ``cap`` ``(t, value)`` pairs of one name, in the order
    they were written."""

    __slots__ = ("t", "v", "n")

    def __init__(self, cap: int) -> None:
        # zeroed lazily by the allocator: a page is touched when a sample
        # lands in it, not here (filled eagerly, a name's first sample cost
        # 1.5 ms, inside whatever wall clock it was taken under)
        self.t = np.zeros(cap)
        self.v = np.zeros(cap)
        self.n = 0  # samples ever written

    def ordered(self) -> tuple[np.ndarray, np.ndarray]:
        """Copies of the kept stamps and values, oldest first."""
        cap = len(self.t)
        kept = min(self.n, cap)
        t, v = self.t[:kept], self.v[:kept]
        i = self.n % cap if self.n > cap else 0  # the oldest kept sample
        return np.roll(t, -i), np.roll(v, -i)  # np.roll copies


class CounterSet:
    """Named host-side accumulators fed from inside (or outside) jit."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._scalars: dict[str, dict[str, float]] = {}
        self._series: dict[str, list[tuple[float, float]]] = {}
        self._static: dict[str, Any] = {}
        self._rings: dict[str, _Ring] = {}
        self._t0 = time.perf_counter()

    # ---- host-side ------------------------------------------------------
    def add(self, name: str, value: float) -> None:
        """Fold ``value`` into the named scalar accumulator (host call)."""
        v = float(value)
        if math.isnan(v):
            return
        with self._lock:
            s = self._scalars.setdefault(
                name,
                {"sum": 0.0, "count": 0.0, "min": math.inf, "max": -math.inf},
            )
            s["sum"] += v
            s["count"] += 1
            s["last"] = v
            s["min"] = min(s["min"], v)
            s["max"] = max(s["max"], v)

    def observe(self, name: str, index: float) -> None:
        """Append ``(index, host wall time)`` to the named series."""
        t = time.perf_counter() - self._t0
        with self._lock:
            self._series.setdefault(name, []).append((float(index), t))

    def sample(self, name: str, value: float, t: float | None = None) -> None:
        """Write ``(t, value)`` into the ring of ``name``; ``t`` is on
        absolute ``time.perf_counter()`` (now, when not given).  Always
        on; not part of :meth:`snapshot`."""
        if t is None:
            t = time.perf_counter()
        with self._lock:
            ring = self._rings.get(name)
            if ring is None:
                ring = self._rings[name] = _Ring(RING_CAP)
            i = ring.n % len(ring.t)
            ring.t[i] = t
            ring.v[i] = value
            ring.n += 1

    def window(self, name: str, t0: float, t1: float) -> list[tuple[float, float]]:
        """The kept samples of ``name`` stamped inside ``[t0, t1]``, in
        the order they were written (a span is written when it closes,
        under the time it opened).  Empty for a name never sampled; where
        the ring :meth:`wrapped`, ask :meth:`oldest_t` whether it still
        reaches back to ``t0``."""
        with self._lock:
            ring = self._rings.get(name)
            if ring is None:
                return []
            t, v = ring.ordered()
        inside = (t >= t0) & (t <= t1)
        return list(zip(t[inside].tolist(), v[inside].tolist()))

    def wrapped(self, name: str) -> bool:
        """Whether ``name``'s ring has dropped samples: more were written
        than it keeps.  One that has not holds the whole series."""
        with self._lock:
            ring = self._rings.get(name)
            return ring is not None and ring.n > len(ring.t)

    def oldest_t(self, name: str) -> float | None:
        """The stamp of the oldest sample ``name``'s ring still holds;
        ``None`` when it holds none.  Of a ring that :meth:`wrapped`, a
        window that opens before it has lost samples."""
        with self._lock:
            ring = self._rings.get(name)
            if ring is None or ring.n == 0:
                return None
            cap = len(ring.t)
            return float(ring.t[ring.n % cap if ring.n > cap else 0])

    def add_static(self, name: str, value: Any) -> None:
        """Record a trace-time fact (idempotent per name: last write wins —
        rebuilding a step function re-records the same value)."""
        with self._lock:
            self._static[name] = value

    # ---- inside-jit inserters ------------------------------------------
    def emit(self, name: str, value, force: bool = False) -> None:
        """Accumulate a traced scalar into ``name`` on the host.

        Call from INSIDE a jitted function.  Trace-time no-op when
        telemetry is disabled (nothing enters the HLO) unless ``force`` —
        the builders pass it so an explicit ``instrument=True`` (or a
        build-time-enabled flag) wins over the global flag's state at
        trace time.
        """
        if not (force or state.enabled()):
            return
        import jax

        jax.debug.callback(lambda v, _n=name: self.add(_n, v), value)

    def mark(self, name: str, index, force: bool = False) -> None:
        """Record the host arrival time of a traced marker (e.g. the tick
        counter of a pipeline scan) into the named series.  Trace-time
        no-op when disabled unless ``force`` (see :meth:`emit`)."""
        if not (force or state.enabled()):
            return
        import jax

        jax.debug.callback(lambda i, _n=name: self.observe(_n, i), index)

    # ---- export ---------------------------------------------------------
    def snapshot(self) -> dict[str, Any]:
        with self._lock:
            scalars = {
                n: dict(
                    s,
                    mean=(s["sum"] / s["count"]) if s["count"] else None,
                )
                for n, s in self._scalars.items()
            }
            return {
                "scalars": scalars,
                "series": {n: list(v) for n, v in self._series.items()},
                "static": dict(self._static),
            }

    def save(self, run_dir: str, filename: str = "counters.json") -> str:
        os.makedirs(run_dir, exist_ok=True)
        path = os.path.join(run_dir, filename)
        with open(path, "w") as f:
            json.dump(self.snapshot(), f, indent=1)
        return path

    def reset(self) -> None:
        with self._lock:
            self._scalars.clear()
            self._series.clear()
            self._static.clear()
            self._rings.clear()
            self._t0 = time.perf_counter()


counters = CounterSet()


def gpipe_bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    """The GPipe schedule's idle fraction ``(S-1)/(M+S-1)`` (the schedule
    runs ``M+S-1`` ticks of which ``S-1`` are fill/drain per stage) —
    the analytic anchor the measured tick cadence is compared against."""
    s, m = int(num_stages), int(num_microbatches)
    if s <= 1:
        return 0.0
    return (s - 1) / (m + s - 1)
