"""The program's own process-global rings, windowed for the readers.

``ddl25spring_tpu.obs.counters`` keeps, per name, the newest samples of
every ``obs.spans.span`` (``(t_start, duration)``) and of the serving
scheduler's counts, stamped on absolute ``time.perf_counter()``: the clock
of ``record["t_open_host"]`` / ``record["t_close_host"]``.  The readers run
in the program's process, after the run, and cut the window out here.
"""

from __future__ import annotations


def series(record: dict, name: str) -> list[tuple[float, float]] | None:
    """The ``(t, value)`` samples of ring ``name`` stamped inside the
    record's window, in the order they were written; ``[]`` for a name
    never sampled.  ``None`` where a number would be wrong: the ring
    wrapped and no longer reaches back to the window's opening, or the
    program has no rings (a commit from before them).  A ring that never
    wrapped holds its whole series, wherever that began."""
    try:
        from ddl25spring_tpu.obs.counters import counters
    except ImportError:
        return None
    if not hasattr(counters, "window"):
        return None
    if counters.wrapped(name) and counters.oldest_t(name) > record["t_open_host"]:
        return None
    return counters.window(name, record["t_open_host"], record["t_close_host"])


def total(record: dict, name: str) -> float | None:
    """Sum of the window's samples of ``name``; ``None`` as above, and
    for a window without a sample."""
    samples = series(record, name)
    if not samples:
        return None
    return sum(v for _, v in samples)
