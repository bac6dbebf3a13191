"""Structured metrics logger: append-only JSONL with a run-metadata header.

One line per record, flushed as written, so a killed run keeps everything
logged up to the kill — the property the ad-hoc ``print`` lines in
``BENCH_*.json`` provenance never had.  The first line is a ``header``
record carrying the run's identity (mesh shape, layout, git sha, jax
version, device kind); every later line is a ``step`` (or custom) record:

    {"record": "header", "run_id": ..., "mesh": {"data": 2, "stage": 2},
     "layout": "dppp", "git_sha": "...", "jax_version": "...", ...}
    {"record": "step", "step": 0, "wall_s": 0.0312, "samples": 1024,
     "loss": 2.31, ...}

``tools/obs_report.py`` folds a directory of these into a summary table.
"""

from __future__ import annotations

import json
import os
import subprocess
import time
from typing import Any, Iterator

# the cross-run trend ledger the `mem`, `goodput` and `serve` rows share
DEFAULT_LEDGER = os.path.join("runs", "perf_ledger.jsonl")


def git_sha(cwd: str | None = None) -> str | None:
    """Best-effort HEAD sha (None outside a repo / without git)."""
    try:
        r = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=cwd or os.path.dirname(os.path.abspath(__file__)),
            capture_output=True,
            text=True,
            timeout=10,
        )
        sha = r.stdout.strip()
        return sha if r.returncode == 0 and sha else None
    except Exception:
        return None


def run_metadata(
    mesh: Any = None, layout: str | None = None, **extra: Any
) -> dict[str, Any]:
    """The header payload: everything needed to interpret the run later.

    ``mesh`` may be a ``jax.sharding.Mesh`` (its ``shape`` mapping is
    recorded) or a plain dict.  ``extra`` lands verbatim (batch size,
    flops_per_step, scan_steps, ...).
    """
    import jax

    shape = None
    if mesh is not None:
        shape = dict(getattr(mesh, "shape", None) or mesh)
    dev = jax.devices()[0]  # an unreachable backend raises: no header
    device = {
        "platform": dev.platform,
        "kind": getattr(dev, "device_kind", ""),
        "count": len(jax.devices()),
    }
    return {
        "record": "header",
        "time_unix_s": time.time(),
        "mesh": shape,
        "layout": layout,
        "git_sha": git_sha(),
        "jax_version": jax.__version__,
        "device": device,
        **extra,
    }


class MetricsLogger:
    """Append-only JSONL writer for one run directory.

    ``MetricsLogger(run_dir, meta=run_metadata(...))`` writes the header
    immediately; ``log(step=..., wall_s=..., ...)`` appends one ``step``
    record per call.  Values that are jax/numpy scalars are coerced to
    Python floats/ints so the lines stay plain JSON.

    Passing ``meta`` marks a FRESH run: any previous ``metrics.jsonl`` in
    the directory is truncated, so re-running into a fixed run dir (e.g.
    ``bench.py --smoke``'s default) never pools two runs' step records
    into one summary.  ``meta=None`` reopens in append mode — the
    crash-resume path, where the earlier records are the point.
    """

    def __init__(
        self,
        run_dir: str,
        meta: dict[str, Any] | None = None,
        filename: str = "metrics.jsonl",
    ):
        self.run_dir = run_dir
        os.makedirs(run_dir, exist_ok=True)
        self.path = os.path.join(run_dir, filename)
        # long-lived handle, closed in close()/__exit__ — not a with-block
        self._f = open(  # noqa: SIM115
            self.path, "w" if meta is not None else "a"
        )
        self._n = 0
        if meta is not None:
            self._write(dict(meta, record=meta.get("record", "header")))

    @staticmethod
    def _coerce(v: Any) -> Any:
        if v is None or isinstance(v, (bool, int, float, str)):
            return v
        if isinstance(v, dict):
            return {k: MetricsLogger._coerce(x) for k, x in v.items()}
        if isinstance(v, (list, tuple)):
            return [MetricsLogger._coerce(x) for x in v]
        try:  # jax / numpy scalar
            return float(v)
        except Exception:
            return repr(v)

    def _write(self, rec: dict[str, Any]) -> None:
        self._f.write(json.dumps(self._coerce(rec)) + "\n")
        self._f.flush()
        self._n += 1

    def log(self, record: str = "step", **fields: Any) -> None:
        self._write({"record": record, **fields})

    @property
    def lines_written(self) -> int:
        return self._n

    def close(self) -> None:
        if not self._f.closed:
            self._f.close()

    def __enter__(self) -> "MetricsLogger":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def read_jsonl(path: str) -> list[dict[str, Any]]:
    """Load every record of a JSONL file (skipping blank lines)."""
    return list(iter_jsonl(path))


def iter_jsonl(path: str) -> Iterator[dict[str, Any]]:
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                yield json.loads(line)


def host_fingerprint() -> str:
    """Stable-ish identity of the measuring machine+backend — part of
    the ledger key, so one host's trend never gates another's."""
    import platform as _platform

    import jax

    try:
        d = jax.devices()[0]
        kind = getattr(d, "device_kind", None) or d.platform
    except Exception:  # noqa: BLE001 — no backend, still fingerprintable
        kind = "no-backend"
    return f"{_platform.node()}/{os.cpu_count()}cpu/{kind}"


def append_ledger(
    record: dict[str, Any], path: str | None = None
) -> str:
    """Append one record to the JSONL ledger (created on first use)."""
    path = path or DEFAULT_LEDGER
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    with open(path, "a") as f:
        f.write(json.dumps(record, default=str) + "\n")
    return path
