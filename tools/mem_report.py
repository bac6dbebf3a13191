"""Render and gate the graft-mem runtime memory record (PR 17).

    python tools/mem_report.py --run runs/serve_smoke            # render
    python tools/mem_report.py --run runs/serve_smoke --check    # CI gate
    python tools/mem_report.py --run runs/serve_elastic --check \
        --require-step-down                      # + elastic memory proof
    python tools/mem_report.py --ledger runs/perf_ledger.jsonl --check

Two sources, same record schema (``ddl25spring_tpu/obs/memscope.py``):

- ``--run RUN_DIR`` reads the run's ``mem.json`` — the single
  ``record: "mem"`` document the serve/train driver wrote at exit:
  measured live-bytes / host-RSS peaks, the budget-vs-measured verdict,
  the KV-pool occupancy/fragmentation snapshot, and the drain-time leak
  check.  ``--check`` fails when the budget band is breached, any KV
  page leaked (each leak names its page + holder — page-table slot with
  the seated request's rid, or an orphan refcount), or the windowed
  monotone-growth detector fired during the run.
  ``--require-step-down`` additionally demands at least one elastic
  reshape step-down whose live bytes went DOWN — the proof a retired
  replica's pools were actually freed, not leaked into the retired
  roster.

- ``--ledger PATH`` trends ``record: "mem"`` rows: within each
  (strategy, mesh, host) key the LATEST record's live/RSS peaks must
  sit within the ``--tolerance`` band over the median of up to
  ``--window`` priors.
  Single-record keys pass with a "no baseline yet" note; different
  hosts never gate each other.

Exit codes: 0 ok, 1 check failed, 2 no data.  Pure stdlib — no jax
import, so the gate runs anywhere the JSON does.
"""

from __future__ import annotations

import json
import statistics
import sys
from pathlib import Path

MEM_BASENAME = "mem.json"            # restated from obs/memscope.py
DEFAULT_LEDGER = "runs/perf_ledger.jsonl"
DEFAULT_TOLERANCE = 0.5
DEFAULT_WINDOW = 5


def read_ledger(path: str) -> list[dict]:
    """Parseable ``record: "mem"`` rows in append order (torn lines
    skipped)."""
    out: list[dict] = []
    p = Path(path)
    if not p.exists():
        return out
    for line in p.read_text().splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(rec, dict) and rec.get("record") == "mem":
            out.append(rec)
    return out


def ledger_key(rec: dict) -> tuple[str, str, str]:
    mesh = rec.get("mesh")
    mesh_s = (
        ",".join(f"{k}={v}" for k, v in mesh.items())
        if isinstance(mesh, dict) else str(mesh)
    )
    return (str(rec.get("strategy")), mesh_s, str(rec.get("host")))


def _mib(v) -> str:
    if not isinstance(v, (int, float)):
        return "n/a"
    return f"{v / (1 << 20):.1f} MiB"


def check_record(rec: dict, require_step_down: bool = False) -> list[str]:
    """The --run gate: [] means the record passes."""
    fails: list[str] = []
    b = rec.get("budget") or {}
    if b.get("available") and b.get("within_band") is False:
        fails.append(
            f"budget band breached: measured {_mib(b.get('measured_peak_bytes'))} "
            f"is {b.get('ratio')}x the accounted "
            f"{_mib(b.get('budget_bytes'))} budget "
            f"({b.get('source')}; tolerance {b.get('tolerance')})"
        )
    leaked = rec.get("leaked_pages", 0)
    if leaked:
        names = []
        for chk in rec.get("leaks") or []:
            for leak in (chk.get("leaks") or [])[:8]:
                if leak.get("held_by") == "page_table":
                    who = f"slot {leak.get('slot')}"
                    if leak.get("rid") is not None:
                        who += f" (rid {leak['rid']})"
                else:
                    who = "orphan refcount"
                names.append(
                    f"page {leak.get('page')} held by {who} "
                    f"(refcount {leak.get('refcount')})"
                )
        fails.append(
            f"{leaked} KV page(s) leaked at drain: "
            + ("; ".join(names) if names else "no attribution recorded")
        )
    growth = rec.get("growth_violations", 0)
    if growth:
        srcs = [
            f"{v.get('source')} grew {_mib(v.get('growth_bytes'))} over "
            f"{v.get('window')} consecutive samples"
            for v in (rec.get("memscope") or {}).get(
                "growth_violations", [])[:4]
        ]
        fails.append(
            f"{growth} monotone-growth violation(s): "
            + ("; ".join(srcs) if srcs else "see memscope cell")
        )
    if require_step_down:
        steps = rec.get("reshape_steps") or []
        downs = [
            s for s in steps
            if isinstance(s.get("step_down_bytes"), (int, float))
            and s["step_down_bytes"] > 0
        ]
        if not downs:
            fails.append(
                "--require-step-down: no elastic reshape step-down with "
                f"live bytes going DOWN recorded ({len(steps)} reshape "
                "step(s) present) — a retired replica's pools were "
                "never freed"
            )
        bad_leaks = [s for s in steps if s.get("leak_ok") is False]
        if bad_leaks:
            fails.append(
                f"{len(bad_leaks)} reshape step-down(s) retired a "
                "replica with a leaking pool"
            )
    return fails


def check_group(
    recs: list[dict],
    tolerance: float = DEFAULT_TOLERANCE,
    window: int = DEFAULT_WINDOW,
) -> list[str]:
    """Trend verdicts for one ledger key: latest live/RSS peak within
    the band over the median of up to ``window`` priors."""
    if len(recs) < 2:
        return []
    latest = recs[-1]
    base = recs[:-1][-window:]
    fails: list[str] = []
    for field in ("live_bytes_peak", "rss_bytes_peak"):
        b = statistics.median([
            (r.get("memscope") or {}).get(field) for r in base
            if isinstance((r.get("memscope") or {}).get(field),
                          (int, float))
        ] or [0])
        lv = (latest.get("memscope") or {}).get(field)
        if b and isinstance(lv, (int, float)):
            if lv > b * (1.0 + tolerance):
                fails.append(
                    f"{field} {_mib(lv)} exceeds the "
                    f"{(1 + tolerance):.2f}x band over the baseline "
                    f"{_mib(b)} (median of {len(base)} prior record(s))"
                )
    return fails


def format_record(rec: dict) -> str:
    lines = [
        f"strategy {rec.get('strategy')}  mesh {rec.get('mesh')}  "
        f"host {rec.get('host')}  sha "
        f"{(rec.get('git_sha') or '?')[:7]}"
    ]
    scope = rec.get("memscope") or {}
    lines.append(
        f"  live bytes peak {_mib(scope.get('live_bytes_peak'))}  "
        f"host RSS peak {_mib(scope.get('rss_bytes_peak'))}  "
        f"samples {scope.get('samples')} "
        f"(every {scope.get('every')} tick(s))"
    )
    if scope.get("live_bytes_baseline") is not None:
        lines.append(
            f"  live-bytes baseline (post-build) "
            f"{_mib(scope['live_bytes_baseline'])}"
        )
    b = rec.get("budget") or {}
    if b.get("available"):
        verdict = "WITHIN BAND" if b.get("within_band") else "BREACHED"
        lines.append(
            f"  budget ({b.get('source')}): accounted "
            f"{_mib(b.get('budget_bytes'))}, measured/budget "
            f"{b.get('ratio')}, tolerance {b.get('tolerance')} -> "
            f"{verdict}"
        )
    else:
        lines.append(
            f"  budget: unavailable ({b.get('source', '?')})"
        )
    pool = rec.get("pool")
    if pool:
        fr = pool.get("free_runs") or {}
        lines.append(
            f"  kv pool: {pool.get('used_pages')}/{pool.get('n_pages')} "
            f"pages used (occupancy {pool.get('occupancy')}) — "
            f"cache-held {pool.get('cache_held_pages')}, table-held "
            f"{pool.get('table_held_pages')}"
        )
        lines.append(
            f"  free runs: {fr.get('count')} run(s), max "
            f"{fr.get('max')}, fragmentation {pool.get('fragmentation')}"
        )
    lines.append(
        f"  leaked pages {rec.get('leaked_pages', 0)}  "
        f"growth violations {rec.get('growth_violations', 0)}"
    )
    steps = rec.get("reshape_steps")
    if steps:
        for s in steps:
            lines.append(
                f"  reshape step-down [{s.get('scope')}:"
                f"{s.get('reason')}]: {_mib(s.get('live_bytes_before'))}"
                f" -> {_mib(s.get('live_bytes_after'))} "
                f"(freed {_mib(s.get('step_down_bytes'))}"
                + (
                    f", leak check "
                    f"{'ok' if s.get('leak_ok') else 'FAILED'}"
                    if "leak_ok" in s else ""
                )
                + ")"
            )
    return "\n".join(lines)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--run", default=None, metavar="RUN_DIR",
                    help=f"run directory holding {MEM_BASENAME} "
                         "(written by bench.py when graft-mem is on)")
    ap.add_argument("--ledger", default=None, metavar="JSONL",
                    help="trend record:\"mem\" rows in this ledger "
                         f"instead (default {DEFAULT_LEDGER} when "
                         "--run is absent)")
    ap.add_argument("--window", type=int, default=DEFAULT_WINDOW,
                    help="prior records per key the trend baseline "
                         "medians over")
    ap.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE,
                    help="fractional trend band on live/RSS peaks "
                         "(0.5 = may grow 50%%)")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero on budget breach / leaked "
                         "pages / growth violations (--run) or a "
                         "trend regression (--ledger) — the CI gate")
    ap.add_argument("--require-step-down", action="store_true",
                    help="with --run --check: also fail unless at "
                         "least one elastic reshape step-down freed "
                         "live bytes (and none leaked)")
    ap.add_argument("--format", choices=("table", "json"),
                    default="table")
    args = ap.parse_args(argv)

    if args.run is not None:
        path = Path(args.run) / MEM_BASENAME
        if not path.exists():
            print(f"no {MEM_BASENAME} at {args.run} (graft-mem off? "
                  "check DDL25_OBS / DDL25_MEMSCOPE)", file=sys.stderr)
            return 2
        try:
            rec = json.loads(path.read_text())
        except (json.JSONDecodeError, OSError) as e:
            print(f"unreadable {path}: {e}", file=sys.stderr)
            return 2
        fails = check_record(rec, args.require_step_down)
        if args.format == "json":
            print(json.dumps({
                "record": "mem_report", "run": args.run, "mem": rec,
                "check": {"ok": not fails, "fails": fails},
            }, indent=1, default=str))
        else:
            print(f"mem record: {path}\n")
            print(format_record(rec))
        if args.check:
            for fail in fails:
                print(f"CHECK FAIL: {fail}", file=sys.stderr)
            if fails:
                return 1
            print(f"\nmem check OK for {args.run}: budget within band, "
                  "zero leaked pages, zero growth violations"
                  + (", elastic step-down present"
                     if args.require_step_down else ""),
                  file=sys.stderr)
        return 0

    ledger = args.ledger or DEFAULT_LEDGER
    records = read_ledger(ledger)
    if not records:
        print(f"no mem records in {ledger} (run bench.py with obs on "
              "to populate it)", file=sys.stderr)
        return 2 if args.check else 0
    groups: dict[tuple, list[dict]] = {}
    for rec in records:
        groups.setdefault(ledger_key(rec), []).append(rec)
    verdicts = {
        key: {
            "fails": check_group(recs, args.tolerance, args.window),
            "note": ("no baseline yet (single record)"
                     if len(recs) < 2 else None),
        }
        for key, recs in groups.items()
    }
    bad = sum(len(v["fails"]) for v in verdicts.values())
    if args.format == "json":
        print(json.dumps({
            "record": "mem_report", "ledger": ledger,
            "tolerance": args.tolerance, "window": args.window,
            "groups": [
                {"strategy": k[0], "mesh": k[1], "host": k[2],
                 "records": len(v), "fails": verdicts[k]["fails"],
                 "note": verdicts[k]["note"]}
                for k, v in groups.items()
            ],
            "check": {"ok": bad == 0, "fails": bad},
        }, indent=1, default=str))
    else:
        print(f"mem ledger: {ledger}  ({len(records)} record(s), "
              f"{len(groups)} key(s))\n")
        print("\n\n".join(
            format_record(recs[-1]) for recs in groups.values()
        ))
    if args.check:
        for key, v in verdicts.items():
            label = f"{key[0]} mesh({key[1]})"
            if v["note"]:
                print(f"CHECK NOTE {label}: {v['note']}",
                      file=sys.stderr)
            for fail in v["fails"]:
                print(f"CHECK FAIL {label}: {fail}", file=sys.stderr)
        if bad:
            return 1
        print(f"\nmem trend check OK: {len(groups)} key(s) within the "
              f"{args.tolerance:.2f} band", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
