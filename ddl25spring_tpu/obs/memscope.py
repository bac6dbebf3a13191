"""graft-mem: the runtime memory & resource observatory (PR 17).

The stack pins peak HBM *at compile time* (PR-2 ``memory_analysis()``
budgets, PR-3 donation floors) and narrates *events* at runtime (PR-16
timeline) — but nothing watched runtime memory itself.  A serving fleet
lives and dies by KV page-pool occupancy, fragmentation, and slow leaks
that only show up over thousands of requests; this module closes the
loop by MEASURING what the accounting promised:

- :func:`live_array_summary` / :func:`live_total_bytes` — walk
  ``jax.live_arrays()`` and aggregate by sharding class (count, bytes,
  top-N largest with shape/dtype/sharding).  The flight recorder folds
  the summary into every dump, so an OOM-shaped death is diagnosable
  from ``flight.json`` alone.
- :func:`host_rss_bytes` — resident set size from ``/proc/self/statm``
  (None off Linux): the host-side leak axis (a growing Python list
  never shows in ``live_arrays``).
- :class:`MemScope` — the per-loop sampler: capped reservoirs
  (:class:`Series`) of live bytes / RSS on a step or tick cadence,
  exact high-water marks, timeline ``mem_sample`` mirrors, and a
  windowed monotone-growth detector (:class:`GrowthDetector`) that
  fires a flight ``kind="mem"`` violation naming the growing resource.
- :func:`pool_snapshot` / :func:`pool_leak_check` — KV page-pool
  introspection (occupancy, cache-held vs table-held split, refcount
  histogram, free-run fragmentation) and the drain-time leak detector:
  an idle pool must hold EXACTLY its cache-held pages; any residue is
  attributed (table row -> rid when possible) and fails
  ``tools/mem_report.py --check``.
- :func:`mem_record` / :func:`write_run_mem` — the ``record:"mem"``
  envelope (keyed strategy/mesh/host like the perf rows) appended to
  ``runs/perf_ledger.jsonl`` and written to ``<run_dir>/mem.json`` for
  ``obs_report``'s Memory section and the ``mem_report`` gates.

**Budget-vs-measured semantics** (the gate ``mem_report --check``
enforces): ``budget_bytes`` is the accounted persistent footprint — for
serve, the exact static bill of params + page pools
(:meth:`ServeEngine.mem_budget_bytes`); for training, the live-bytes
baseline captured right after build (params + opt state + data
resident).  The runtime high-water ``live_bytes_peak`` must sit within
``budget_bytes * (1 + tolerance)``; where a registered strategy
additionally declares a compile-time ``memory.max_peak_hbm_bytes``
budget (:func:`describe_budget_bytes`), that rides the record for the
trend report.  Everything here is host-side observation: with
``DDL25_MEMSCOPE=0`` (or obs off) no sample is taken and compiled
programs are byte-identical — pinned in ``tests/test_memscope.py``.
"""

from __future__ import annotations

import contextlib
import json
import os
import random
import time
from collections import deque
from typing import Any, Callable, Iterable

from ddl25spring_tpu.obs import state
from ddl25spring_tpu.utils.config import env_flag, env_float

MEM_BASENAME = "mem.json"
SERIES_CAP = 512

#: default budget band: measured high-water live bytes may exceed the
#: accounted budget by this fraction before the gate fails (runtime
#: live arrays include jax-internal constants/donation scratch the
#: static bill does not enumerate)
DEFAULT_TOLERANCE = 0.5

#: sampler gate — ``DDL25_MEMSCOPE=0`` turns every sampler into a no-op
#: even when obs is on (the HLO/bitwise pins toggle this, not DDL25_OBS)
_flag_enabled = env_flag("DDL25_MEMSCOPE", True)


def enabled() -> bool:
    """True when memory sampling is on: obs enabled AND the
    ``DDL25_MEMSCOPE`` flag not zeroed."""
    return _flag_enabled and state.enabled()


def set_flag(on: bool) -> None:
    global _flag_enabled
    _flag_enabled = bool(on)


@contextlib.contextmanager
def scoped(on: bool):
    """Temporarily force the memscope flag (tests; composes with
    ``obs.state.scoped``)."""
    global _flag_enabled
    prev = _flag_enabled
    _flag_enabled = bool(on)
    try:
        yield
    finally:
        _flag_enabled = prev


def tolerance() -> float:
    """The budget band width (``DDL25_MEM_TOL`` overrides)."""
    return env_float("DDL25_MEM_TOL", DEFAULT_TOLERANCE)


# ------------------------------------------------------------- host side


def host_rss_bytes() -> int | None:
    """Resident set size of this process from ``/proc/self/statm``
    (field 2, in pages) — None where procfs is unavailable."""
    try:
        with open("/proc/self/statm") as f:
            fields = f.read().split()
        return int(fields[1]) * (os.sysconf("SC_PAGE_SIZE") or 4096)
    except (OSError, IndexError, ValueError):
        return None


# ----------------------------------------------------------- device side


def _array_nbytes(a) -> int:
    try:
        return int(a.size) * int(a.dtype.itemsize)
    except Exception:  # noqa: BLE001 — a half-deleted array must not kill
        return 0


def _sharding_key(a) -> str:
    """Aggregation key: sharding class + device platform + device count
    — 'SingleDeviceSharding/cpu x1', 'NamedSharding/tpu x8', ...  The
    strategy-level grouping the summary buckets live bytes by."""
    try:
        sh = a.sharding
        n = len(sh.device_set)
        platform = next(iter(sh.device_set)).platform
        return f"{type(sh).__name__}/{platform} x{n}"
    except Exception:  # noqa: BLE001
        return "unknown"


def live_arrays() -> list:
    """Non-deleted ``jax.live_arrays()``, empty when jax is unusable
    (a crash dump must never raise from here)."""
    try:
        import jax

        return [
            a for a in jax.live_arrays()
            if not getattr(a, "is_deleted", lambda: False)()
        ]
    except Exception:  # noqa: BLE001
        return []


def live_total_bytes() -> int:
    """Total committed bytes across every live jax array — the fast
    per-sample aggregate (no per-array dict building)."""
    return sum(_array_nbytes(a) for a in live_arrays())


def live_array_summary(top: int = 10) -> dict[str, Any]:
    """The full live-array picture: count, total bytes, per-sharding
    buckets, and the ``top`` largest arrays with shape/dtype/sharding —
    what the flight recorder folds into every dump (satellite: an
    OOM-shaped death names its offenders from ``flight.json`` alone)."""
    arrs = live_arrays()
    by_sharding: dict[str, dict[str, int]] = {}
    sized = []
    total = 0
    for a in arrs:
        nb = _array_nbytes(a)
        total += nb
        key = _sharding_key(a)
        b = by_sharding.setdefault(key, {"count": 0, "bytes": 0})
        b["count"] += 1
        b["bytes"] += nb
        sized.append((nb, a))
    sized.sort(key=lambda t: -t[0])
    largest = []
    for nb, a in sized[:top]:
        try:
            largest.append({
                "shape": list(a.shape),
                "dtype": str(a.dtype),
                "bytes": nb,
                "sharding": _sharding_key(a),
            })
        except Exception:  # noqa: BLE001
            largest.append({"bytes": nb, "error": "unreadable"})
    return {
        "count": len(arrs),
        "total_bytes": total,
        "by_sharding": by_sharding,
        "largest": largest,
    }


# ------------------------------------------------------- bounded series


class Series:
    """Algorithm-R reservoir + exact count/max/min/total over the full
    stream — the same bounded-host-series contract as the serve
    engine's ``Reservoir`` (kept local: obs/ must not import serve/).
    Below ``cap`` it is exactly an insertion-ordered list."""

    __slots__ = ("cap", "count", "max", "min", "total", "_xs", "_rng",
                 "_seed")

    def __init__(self, cap: int = SERIES_CAP, seed: int = 0):
        self.cap = int(cap)
        self._seed = int(seed)
        self._xs: list = []
        self._rng = random.Random(self._seed)
        self.count = 0
        self.max: float | None = None
        self.min: float | None = None
        self.total = 0.0

    def append(self, x) -> None:
        self.count += 1
        if isinstance(x, (int, float)) and not isinstance(x, bool):
            self.total += x
            if self.max is None or x > self.max:
                self.max = x
            if self.min is None or x < self.min:
                self.min = x
        if len(self._xs) < self.cap:
            self._xs.append(x)
        else:
            j = self._rng.randrange(self.count)
            if j < self.cap:
                self._xs[j] = x

    def clear(self) -> None:
        self._xs.clear()
        self._rng = random.Random(self._seed)
        self.count = 0
        self.max = None
        self.min = None
        self.total = 0.0

    def __iter__(self):
        return iter(self._xs)

    def __len__(self) -> int:
        return len(self._xs)

    def summary(self) -> dict[str, Any]:
        return {
            "count": self.count,
            "sampled": len(self._xs),
            "cap": self.cap,
            "max": self.max,
            "min": self.min,
            "mean": (
                round(self.total / self.count, 3) if self.count else None
            ),
        }


# ------------------------------------------------- monotone-growth leak


class GrowthDetector:
    """Windowed monotone-growth detector for host-side resources.

    A watched series that rises on EVERY observation across a full
    window of ``window`` samples, by at least ``min_growth_bytes``
    total, is a leak-shaped signal — fired ONCE per source (latched),
    as a dict naming the offender.  A series that plateaus or dips
    anywhere inside the window stays quiet (the near-miss negative the
    tests pin), as does growth below the byte floor (allocator noise)."""

    def __init__(self, window: int = 8,
                 min_growth_bytes: int = 1 << 20):
        if window < 2:
            raise ValueError(f"window={window} must be >= 2")
        self.window = int(window)
        self.min_growth_bytes = int(min_growth_bytes)
        self._hist: dict[str, deque] = {}
        self.fired: dict[str, dict[str, Any]] = {}

    def observe(self, source: str, value: float,
                step: int | None = None) -> dict[str, Any] | None:
        """Feed one sample; returns the violation dict the first time
        ``source`` completes a strictly-increasing window, else None."""
        h = self._hist.setdefault(source, deque(maxlen=self.window))
        h.append(float(value))
        if source in self.fired or len(h) < self.window:
            return None
        xs = list(h)
        monotone = all(b > a for a, b in zip(xs, xs[1:]))
        growth = xs[-1] - xs[0]
        if not monotone or growth < self.min_growth_bytes:
            return None
        v = {
            "kind": "mem",
            "source": source,
            "growth_bytes": int(growth),
            "window": self.window,
            "first_bytes": int(xs[0]),
            "last_bytes": int(xs[-1]),
            **({"step": int(step)} if step is not None else {}),
        }
        self.fired[source] = v
        return v


# ------------------------------------------------------------ the scope


class MemScope:
    """One loop's memory sampler: bounded series of live bytes / host
    RSS, exact high-water marks, watched host resources through a
    :class:`GrowthDetector`, and timeline ``mem_sample`` mirrors.

    Construction is always cheap; :meth:`sample` is a no-op unless
    :func:`enabled` — so wiring a scope through a loop costs nothing
    when memory observation is off (the disabled-identical pin).
    ``every`` thins the cadence (sample 1 tick in N)."""

    def __init__(self, label: str = "train", *, every: int = 1,
                 cap: int = SERIES_CAP, window: int = 8,
                 min_growth_bytes: int = 1 << 20):
        self.label = label
        self.every = max(1, int(every))
        self.live_bytes = Series(cap)
        self.rss_bytes = Series(cap)
        self.live_bytes_peak = 0
        self.rss_bytes_peak = 0
        self.live_bytes_baseline: int | None = None
        self.detector = GrowthDetector(
            window=window, min_growth_bytes=min_growth_bytes
        )
        self.violations: list[dict[str, Any]] = []
        self._watches: dict[str, Callable[[], float]] = {}
        self._n = 0

    # -- configuration ------------------------------------------------

    def watch(self, name: str, fn: Callable[[], float]) -> None:
        """Register a host resource (callable -> byte count) for the
        monotone-growth detector; ``host_rss`` is always watched."""
        self._watches[name] = fn

    def set_baseline(self) -> int | None:
        """Capture the persistent live-bytes floor (call once, after
        build / warmup): the budget anchor the training gate bands."""
        if not enabled():
            return None
        self.live_bytes_baseline = live_total_bytes()
        return self.live_bytes_baseline

    def reset(self) -> None:
        """Forget everything (the serve engine's warmup reset)."""
        self.live_bytes.clear()
        self.rss_bytes.clear()
        self.live_bytes_peak = 0
        self.rss_bytes_peak = 0
        self.live_bytes_baseline = None
        self.detector = GrowthDetector(
            window=self.detector.window,
            min_growth_bytes=self.detector.min_growth_bytes,
        )
        self.violations = []
        self._n = 0

    # -- sampling -----------------------------------------------------

    def sample(self, step: int | None = None, *,
               vt: float | None = None, engine: str | None = None,
               replica: int | None = None,
               **extra: Any) -> dict[str, Any] | None:
        """Take one sample (thinned to 1-in-``every``): live bytes +
        RSS into the series and peaks, watched resources through the
        growth detector (violations -> flight ``kind="mem"``), and a
        timeline ``mem_sample`` event carrying ``extra`` (pool
        occupancy, queue depth, tokens/sec — the counter-track
        payload).  Returns the sample dict, or None when off-cadence
        or disabled."""
        if not enabled():
            return None
        self._n += 1
        if (self._n - 1) % self.every:
            return None
        live = live_total_bytes()
        rss = host_rss_bytes()
        if self.live_bytes_baseline is None:
            # the first sample IS the training baseline: it sees the
            # steady-state placement (e.g. DP replication materializes
            # on the first dispatch), which a post-build probe
            # undercounts by the replication factor
            self.live_bytes_baseline = live
        self.live_bytes.append(live)
        self.live_bytes_peak = max(self.live_bytes_peak, live)
        if rss is not None:
            self.rss_bytes.append(rss)
            self.rss_bytes_peak = max(self.rss_bytes_peak, rss)
        for name, fn in [
            ("host_rss", lambda: rss if rss is not None else 0.0),
            *self._watches.items(),
        ]:
            try:
                value = float(fn())
            except Exception:  # noqa: BLE001 — a probe must not kill
                continue
            v = self.detector.observe(name, value, step)
            if v is not None:
                v["scope"] = self.label
                self.violations.append(v)
                from ddl25spring_tpu.obs.recorder import flight

                flight.record(**v)
        sample = {
            "live_bytes": live,
            **({"rss_bytes": rss} if rss is not None else {}),
            **({"step": step} if step is not None else {}),
            **extra,
        }
        from ddl25spring_tpu.obs.timeline import timeline

        timeline.emit(
            "mem_sample", vt=vt, engine=engine or self.label,
            replica=replica, **sample,
        )
        return sample

    # -- folding ------------------------------------------------------

    def cell(self) -> dict[str, Any]:
        """The scope's summary cell (rides ``telemetry.mem`` and the
        mem record)."""
        return {
            "label": self.label,
            "samples": self.live_bytes.count,
            "every": self.every,
            "live_bytes_peak": self.live_bytes_peak,
            "rss_bytes_peak": self.rss_bytes_peak,
            "live_bytes_baseline": self.live_bytes_baseline,
            "live_bytes": self.live_bytes.summary(),
            "rss_bytes": self.rss_bytes.summary(),
            "growth_violations": list(self.violations),
        }


# -------------------------------------------------- KV page-pool optics


def _free_runs(free: Iterable[bool]) -> list[int]:
    runs: list[int] = []
    n = 0
    for f in free:
        if f:
            n += 1
        elif n:
            runs.append(n)
            n = 0
    if n:
        runs.append(n)
    return runs


def pool_snapshot(pool: dict[str, Any],
                  cache_held: int = 0) -> dict[str, Any]:
    """Host-side KV pool telemetry from the device ``free`` /
    ``refcount`` masks (tiny transfers — ``n_pages`` bools/int32s):
    occupancy, the cache-held vs table-held split, a refcount
    histogram, and the free-run fragmentation metric.

    ``fragmentation`` is ``1 - largest_free_run / free_pages`` (0 = one
    contiguous free region, -> 1 = free pages shattered into single
    slots).  The pool allocates page-at-a-time, so fragmentation never
    blocks an allocation here — the metric exists because real engines
    with multi-page contiguous needs die on exactly this curve."""
    import numpy as np

    free = np.asarray(pool["free"]).astype(bool)
    ref = np.asarray(pool["refcount"]).astype(int)
    n_pages = int(free.shape[0])
    used = int((~free).sum())
    free_n = n_pages - used
    runs = _free_runs(free.tolist())
    vals, counts = np.unique(ref[ref > 0], return_counts=True)
    return {
        "n_pages": n_pages,
        "used_pages": used,
        "free_pages": free_n,
        "occupancy": round(used / n_pages, 4) if n_pages else 0.0,
        "cache_held_pages": int(cache_held),
        "table_held_pages": max(used - int(cache_held), 0),
        "refcount_hist": {
            str(int(v)): int(c) for v, c in zip(vals, counts)
        },
        "free_runs": {
            "count": len(runs),
            "max": max(runs) if runs else 0,
            "mean": round(sum(runs) / len(runs), 2) if runs else 0.0,
        },
        "fragmentation": (
            round(1.0 - max(runs) / free_n, 4) if free_n else 0.0
        ),
    }


def pool_leak_check(
    pool: dict[str, Any],
    *,
    cache_held_pages: int = 0,
    slot_rids: list | None = None,
) -> dict[str, Any]:
    """The drain-time leak detector: an idle pool must hold EXACTLY its
    cache-held pages.  Any residue is enumerated page by page and
    attributed — a page still seated in a page-table row is named by
    that row's last rid (``slot_rids``); a page referenced by nothing
    we can see is an orphan (a lost external reference).  ``ok=False``
    fails ``mem_report --check``."""
    import numpy as np

    free = np.asarray(pool["free"]).astype(bool)
    ref = np.asarray(pool["refcount"]).astype(int)
    table = np.asarray(pool["page_table"]).astype(int)
    used = int((~free).sum())
    residue = used - int(cache_held_pages)
    out: dict[str, Any] = {
        "ok": residue <= 0,
        "used_pages": used,
        "cache_held_pages": int(cache_held_pages),
        "leaked_pages": max(residue, 0),
        "leaks": [],
    }
    if residue <= 0:
        return out
    # page -> the table row(s) still holding it; at drain every row
    # should be -1, so any hit is the leak's name
    holders: dict[int, list[int]] = {}
    for slot in range(table.shape[0]):
        for page in table[slot]:
            if page >= 0:
                holders.setdefault(int(page), []).append(slot)
    leaks = []
    for page in np.nonzero(~free)[0]:
        page = int(page)
        rows = holders.get(page)
        if rows is not None:
            for slot in rows:
                rid = (
                    slot_rids[slot]
                    if slot_rids is not None and slot < len(slot_rids)
                    else None
                )
                leaks.append({
                    "page": page,
                    "refcount": int(ref[page]),
                    "held_by": "page_table",
                    "slot": slot,
                    **({"rid": rid} if rid is not None else {}),
                })
        else:
            leaks.append({
                "page": page,
                "refcount": int(ref[page]),
                "held_by": "orphan_refcount",
            })
    # cache-held pages legitimately sit outside any table; keep only
    # the residue count of orphans beyond what the cache accounts for
    orphans = [x for x in leaks if x["held_by"] == "orphan_refcount"]
    tabled = [x for x in leaks if x["held_by"] == "page_table"]
    excess_orphans = orphans[
        : max(len(orphans) - int(cache_held_pages), 0)
    ]
    out["leaks"] = tabled + excess_orphans
    return out


# --------------------------------------------------------- the envelope


def describe_budget_bytes(strategy: str) -> int | None:
    """The compile-time peak-HBM budget a registered strategy declares
    (``describe()['expected']['memory']['max_peak_hbm_bytes']``) —
    None for workloads outside the registry (the bench resnet, serve
    models): those gate on the static accounting instead."""
    try:
        from ddl25spring_tpu.obs import xla_analytics as xa

        if strategy not in getattr(xa, "STRATEGIES", {}):
            return None
        d = xa.describe_strategy(strategy)
        b = (d.get("expected") or {}).get("memory", {}).get(
            "max_peak_hbm_bytes"
        )
        return int(b) if b is not None else None
    except Exception:  # noqa: BLE001 — budget lookup is best-effort
        return None


def budget_cell(
    measured_peak_bytes: int,
    budget_bytes: int | None,
    *,
    tol: float | None = None,
    source: str = "static_accounting",
) -> dict[str, Any]:
    """The budget-vs-measured verdict: ``within_band`` iff the runtime
    high-water sits at or under ``budget_bytes * (1 + tol)``."""
    tol = tolerance() if tol is None else tol
    if not budget_bytes:
        return {"available": False, "source": source}
    ratio = measured_peak_bytes / budget_bytes
    return {
        "available": True,
        "source": source,
        "budget_bytes": int(budget_bytes),
        "measured_peak_bytes": int(measured_peak_bytes),
        "ratio": round(ratio, 4),
        "tolerance": tol,
        "within_band": ratio <= 1.0 + tol,
    }


def mem_record(
    *,
    strategy: str,
    mesh: dict[str, int] | None,
    scope_cell: dict[str, Any],
    budget: dict[str, Any],
    pool: dict[str, Any] | None = None,
    leaks: list[dict[str, Any]] | None = None,
    reshape_steps: list[dict[str, Any]] | None = None,
    extra: dict[str, Any] | None = None,
) -> dict[str, Any]:
    """One ``record:"mem"`` ledger row / ``mem.json`` document, under
    the identity envelope (strategy/mesh/host/git_sha) that
    ``mem_report`` groups trends by."""
    import jax

    from ddl25spring_tpu.obs.logger import git_sha, host_fingerprint

    return {
        "record": "mem",
        "schema": 1,
        "ts": time.time(),
        "strategy": strategy,
        "mesh": dict(mesh or {}),
        "host": host_fingerprint(),
        "git_sha": git_sha(),
        "jax_version": jax.__version__,
        "memscope": scope_cell,
        "budget": budget,
        **({"pool": pool} if pool is not None else {}),
        "leaks": list(leaks or []),
        "leaked_pages": sum(
            x.get("leaked_pages", 0) for x in (leaks or [])
        ),
        "growth_violations": len(
            scope_cell.get("growth_violations") or []
        ),
        **({"reshape_steps": reshape_steps}
           if reshape_steps is not None else {}),
        **(extra or {}),
    }


def mem_cell(record: dict[str, Any]) -> dict[str, Any]:
    """The ``telemetry.mem`` BENCH cell — the contract keys the CI
    smoke asserts (peaks, budget verdict, leak + growth counters),
    folded from one :func:`mem_record`."""
    scope = record.get("memscope") or {}
    cell: dict[str, Any] = {
        "enabled": True,
        "samples": scope.get("samples"),
        "live_bytes_peak": scope.get("live_bytes_peak"),
        "rss_bytes_peak": scope.get("rss_bytes_peak"),
        "budget": record.get("budget"),
        "leaked_pages": record.get("leaked_pages", 0),
        "growth_violations": record.get("growth_violations", 0),
    }
    pool = record.get("pool")
    if pool is not None:
        cell["pool"] = {
            k: pool.get(k)
            for k in ("n_pages", "used_pages", "occupancy",
                      "cache_held_pages", "table_held_pages",
                      "fragmentation")
        }
    steps = record.get("reshape_steps")
    if steps:
        cell["reshape_steps"] = len(steps)
        cell["reshape_step_down_bytes"] = sum(
            s.get("step_down_bytes", 0) for s in steps
        )
    return cell


def write_run_mem(record: dict[str, Any], run_dir: str) -> str:
    """``<run_dir>/mem.json``, atomically (temp + rename, the
    write_run_perf pattern) — what ``obs_report``'s Memory section and
    ``mem_report --run`` read."""
    os.makedirs(run_dir, exist_ok=True)
    path = os.path.join(run_dir, MEM_BASENAME)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(record, f, indent=1, default=str)
    os.replace(tmp, path)
    return path
