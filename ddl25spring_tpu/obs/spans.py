"""The one way to mark a host-side region: :func:`span`.

ALWAYS, whatever ``DDL25_OBS`` says, a span

- enters a ``jax.profiler.TraceAnnotation`` carrying its stats: while any
  profiler session is open it lands in the trace's ``/host:CPU`` plane on
  the device trace's own clock, and it costs one ``TraceMe`` check
  otherwise;
- writes ``(t_start, duration)``, on absolute ``time.perf_counter()``,
  into the ring of its name in the process-global
  :data:`ddl25spring_tpu.obs.counters.counters`, which a reader in the
  same process windows after the fact.

ONLY when telemetry is enabled (:mod:`~ddl25spring_tpu.obs.state`) it is
also recorded by the :class:`SpanRecorder`, which writes nested wall-clock
spans in the Chrome Trace Event format — loadable in ``chrome://tracing``
/ https://ui.perfetto.dev without any XLA profiler involvement.
:func:`instant` is gated the same way.

Format: the JSON Object Format — ``{"traceEvents": [...], ...}`` — with
``"X"`` (complete) duration events carrying ``name``/``cat``/``ph``/
``ts``/``dur``/``pid``/``tid``/``args`` and ``"M"`` metadata events naming
the process/threads.  Timestamps are microseconds on a per-recorder
``perf_counter`` origin; the wall-clock anchor rides in ``otherData``.

Thread-safe: spans may open/close concurrently from loader worker threads
and the main loop; event appends are lock-protected and nesting is
per-thread (Chrome's stack-building uses ``tid``).
"""

from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Iterator

from jax.profiler import TraceAnnotation

from ddl25spring_tpu.obs import state
from ddl25spring_tpu.obs.counters import counters


class SpanRecorder:
    """Collects nested host spans; serializes as Chrome trace JSON."""

    def __init__(self, process_name: str = "ddl25spring_tpu"):
        self._lock = threading.Lock()
        self._events: list[dict[str, Any]] = []
        self._t0 = time.perf_counter()
        self._t0_unix = time.time()
        self.process_name = process_name
        self._named_tids: set[int] = set()
        self._emit_meta(
            {
                "name": "process_name",
                "ph": "M",
                "pid": os.getpid(),
                "tid": 0,
                "args": {"name": process_name},
            }
        )

    def _emit_meta(self, ev: dict[str, Any]) -> None:
        with self._lock:
            self._events.append(ev)

    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def _ensure_thread_named(self, tid: int) -> None:
        if tid in self._named_tids:
            return
        self._named_tids.add(tid)
        self._events.append(
            {
                "name": "thread_name",
                "ph": "M",
                "pid": os.getpid(),
                "tid": tid,
                "args": {"name": threading.current_thread().name},
            }
        )

    @contextmanager
    def span(self, name: str, cat: str = "host", **args: Any) -> Iterator[Any]:
        """Record the block as one complete ("X") event; also annotate the
        real profiler timeline when one is active.  Yields the annotation:
        ``set_metadata(**more)`` adds what is known only inside the block
        to both (``args`` is the event's own dict until it closes)."""
        tid = threading.get_ident()
        ts = self._now_us()
        with TraceAnnotation(name, **args) as ann:
            try:
                yield _Late(ann, args)
            finally:
                dur = self._now_us() - ts
                with self._lock:
                    self._ensure_thread_named(tid)
                    self._events.append(
                        {
                            "name": name,
                            "cat": cat,
                            "ph": "X",
                            "ts": ts,
                            "dur": dur,
                            "pid": os.getpid(),
                            "tid": tid,
                            **({"args": args} if args else {}),
                        }
                    )

    def instant(self, name: str, cat: str = "host", **args: Any) -> None:
        """A zero-duration marker ("i" instant event, thread scope)."""
        tid = threading.get_ident()
        with self._lock:
            self._ensure_thread_named(tid)
            self._events.append(
                {
                    "name": name,
                    "cat": cat,
                    "ph": "i",
                    "s": "t",
                    "ts": self._now_us(),
                    "pid": os.getpid(),
                    "tid": tid,
                    **({"args": args} if args else {}),
                }
            )

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def to_chrome_trace(self) -> dict[str, Any]:
        with self._lock:
            events = list(self._events)
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "process_name": self.process_name,
                "time_origin_unix_s": self._t0_unix,
            },
        }

    def save(self, path: str) -> str:
        """Write the trace JSON; returns the path (load it in Perfetto)."""
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        return path


class _Late:
    """Stats added to an open span: into the profiler's annotation, and
    into the dict the Chrome event is written from."""

    __slots__ = ("_ann", "_args")

    def __init__(self, ann, args: dict[str, Any]) -> None:
        self._ann, self._args = ann, args

    def set_metadata(self, **stats: Any) -> None:
        self._ann.set_metadata(**stats)
        self._args.update(stats)


_default = SpanRecorder()


def get_recorder() -> SpanRecorder:
    return _default


def set_recorder(rec: SpanRecorder) -> SpanRecorder:
    """Install a fresh recorder (e.g. one per run dir); returns the old."""
    global _default
    prev, _default = _default, rec
    return prev


class _Span:
    """What :func:`span` returns: annotation + ring sample always, the
    default recorder's Chrome event when telemetry is enabled."""

    __slots__ = ("name", "cat", "stats", "_inner", "_open", "_t0")

    def __init__(self, name: str, cat: str, stats: dict[str, Any]) -> None:
        self.name, self.cat, self.stats = name, cat, stats

    def __enter__(self) -> "_Span":
        if state.enabled():
            self._inner = _default.span(self.name, cat=self.cat, **self.stats)
        else:
            self._inner = TraceAnnotation(self.name, **self.stats)
        self._open = self._inner.__enter__()
        self._t0 = time.perf_counter()
        return self

    def add(self, **stats: Any) -> None:
        """Stats known only inside the block (what a pass read back from
        the device): they join the ones the span was opened with."""
        self._open.set_metadata(**stats)

    def __exit__(self, *exc) -> None:
        counters.sample(self.name, time.perf_counter() - self._t0, self._t0)
        self._inner.__exit__(*exc)


def span(name: str, cat: str = "host", **stats: Any):
    """Mark the block as the region ``name``.  ``stats`` (counts of the
    boundary: plain numbers and strings) ride into the profiler trace and
    the Chrome event; call sites need no guard."""
    return _Span(name, cat, stats)


def watched() -> bool:
    """Whether anything will show a span's stats: a profiler session is
    open, or telemetry is enabled.  For the caller whose stat costs
    something to count."""
    return state.enabled() or TraceAnnotation.is_enabled()


def instant(name: str, **args: Any) -> None:
    if state.enabled():
        _default.instant(name, **args)
