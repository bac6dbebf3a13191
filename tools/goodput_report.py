"""Render goodput/badput decompositions and gate goodput regressions.

    python tools/goodput_report.py                     # ledger trend tables
    python tools/goodput_report.py --run runs/bench_smoke   # one run's doc
    python tools/goodput_report.py --check             # the CI trend gate
    python tools/goodput_report.py --check --slo-floor 0.9  # serve SLO gate
    python tools/goodput_report.py --check-elastic \\
        runs/elastic/goodput.json runs/relaunch/goodput.json
    python tools/goodput_report.py --format json       # machine-readable

The ledger (``runs/perf_ledger.jsonl``) holds one ``record:"goodput"``
row per run lineage, written by ``bench.py`` (training: the merged
all-attempts decomposition) and the serve driver (SLO attainment,
availability, goodput tokens/sec/chip) — semantics in
``ddl25spring_tpu/obs/goodput.py``.  Per-run ``goodput.json`` files
carry the full decomposition including the badput windows
``tools/trace_export.py`` renders.

Gates (all CI-facing — keys with a single record pass with a "no
baseline yet" note, different hosts never gate each other):

- ``--check``: within each (strategy, mesh, host, scope) key, the
  latest row's ``fraction_useful`` must not fall more than
  ``--tolerance`` (fractional) below the median of up to ``--window``
  prior rows; serve rows apply the same band to ``slo_attainment``.
  Any row whose own ``sum_check`` failed (buckets over-attributed past
  the pinned tolerance) fails unconditionally — a decomposition that
  does not add up gates no trend.
- ``--slo-floor F``: the latest serve-scope row's ``slo_attainment``
  must be >= F (absolute; a single fresh record already gates — the
  serve-smoke SLO gate).
- ``--check-elastic ELASTIC RELAUNCH``: two run-dir ``goodput.json``
  paths measured on the SAME fault spec; the elastic run's
  ``fraction_useful`` must be STRICTLY higher than the relaunch run's
  — the PR-14 recovery A/B re-expressed in the production metric (an
  in-process reshape pays seconds where a relaunch pays process
  restart + restore + replayed steps).

Pure stdlib — no jax import, so the gate runs anywhere the JSON does.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
from datetime import datetime, timezone
from pathlib import Path

DEFAULT_LEDGER = "runs/perf_ledger.jsonl"
DEFAULT_TOLERANCE = 0.35
DEFAULT_WINDOW = 5

# restated from ddl25spring_tpu/obs/goodput.py (stdlib tools never
# import the package: its __init__ pulls jax)
GOODPUT_BASENAME = "goodput.json"
BUCKETS = (
    "useful_step",
    "warmup_compile",
    "checkpoint_save",
    "replayed_steps",
    "stall",
    "recovery",
    "reshape_window",
    "other",
)


def read_ledger(path: str, kind: str = "goodput") -> list[dict]:
    """Parseable ``record: kind`` rows in append order (torn trailing
    lines skipped, same contract as every ledger reader)."""
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
        if isinstance(rec, dict) and rec.get("record") == kind:
            out.append(rec)
    return out


def ledger_key(rec: dict) -> tuple[str, str, str, str]:
    """(strategy, mesh, host, scope): the trend identity.  The lineage
    id is IDENTITY on the row, never part of the key — every lineage
    is unique, so keying on it would orphan every trend group."""
    key = rec.get("key") if isinstance(rec.get("key"), dict) else {}
    mesh = key.get("mesh")
    mesh_s = (
        ",".join(f"{k}={v}" for k, v in sorted(mesh.items()))
        if isinstance(mesh, dict) else str(mesh)
    )
    return (
        str(key.get("strategy")), mesh_s, str(rec.get("host")),
        str(key.get("scope")),
    )


def group_records(records: list[dict]) -> dict[tuple, list[dict]]:
    groups: dict[tuple, list[dict]] = {}
    for rec in records:
        groups.setdefault(ledger_key(rec), []).append(rec)
    return groups


def _median(xs: list[float]) -> float | None:
    return statistics.median(xs) if xs else None


def _band_fail(latest, base: list[dict], field: str,
               tolerance: float) -> list[str]:
    b = _median([
        r[field] for r in base
        if isinstance(r.get(field), (int, float))
    ])
    v = latest.get(field)
    if b and isinstance(v, (int, float)) and v < b * (1.0 - tolerance):
        return [
            f"{field} {v:.4f} fell below the {(1 - tolerance):.2f}x "
            f"band under the baseline {b:.4f} (median of {len(base)} "
            "prior record(s))"
        ]
    return []


def check_group(recs: list[dict], tolerance: float = DEFAULT_TOLERANCE,
                window: int = DEFAULT_WINDOW) -> list[str]:
    """Regression verdicts for one key: [] = within band (or no
    baseline).  A latest row whose own decomposition failed its sum
    contract fails regardless of history."""
    fails: list[str] = []
    latest = recs[-1]
    sc = latest.get("sum_check")
    if isinstance(sc, dict) and sc.get("ok") is False:
        fails.append(
            f"decomposition sum_check failed: attributed "
            f"{sc.get('attributed_s')}s vs total "
            f"{sc.get('total_wall_s')}s exceeds the pinned "
            f"{sc.get('tolerance')} tolerance"
        )
    if len(recs) < 2:
        return fails
    base = recs[:-1][-window:]
    fails += _band_fail(latest, base, "fraction_useful", tolerance)
    if latest.get("key", {}).get("scope") == "serve":
        fails += _band_fail(latest, base, "slo_attainment", tolerance)
    return fails


def check_slo_floor(recs: list[dict], floor: float) -> list[str]:
    """Absolute SLO-attainment floor on the latest serve-scope row —
    needs no baseline (the serve-smoke gate).  Rows whose attainment
    is None (nothing completed to evaluate) FAIL: an engine that
    finished zero requests did not attain its SLO."""
    latest = recs[-1]
    if latest.get("key", {}).get("scope") != "serve":
        return []
    att = latest.get("slo_attainment")
    if att is None:
        return [
            "slo_attainment is null (no completed requests were "
            f"evaluated) — below the --slo-floor {floor:.3f}"
        ]
    if att < floor:
        return [
            f"slo_attainment {att:.4f} fell under the --slo-floor "
            f"{floor:.3f}"
        ]
    return []


def load_run_doc(path: str) -> dict:
    """A goodput doc from a run dir or a direct goodput.json path."""
    if os.path.isdir(path):
        path = os.path.join(path, GOODPUT_BASENAME)
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or doc.get("record") != "goodput":
        raise ValueError(f"{path} is not a goodput doc")
    return doc


def check_elastic(elastic_path: str, relaunch_path: str) -> list[str]:
    """The elastic-vs-relaunch recovery A/B in goodput terms: on the
    same fault spec, the in-process reshape must waste strictly less
    of the lineage's wall than the kill->relaunch->restore->replay
    round-trip.  STRICT inequality — equal goodput means the reshape
    path bought nothing."""
    fails: list[str] = []
    e = load_run_doc(elastic_path)
    r = load_run_doc(relaunch_path)
    for name, doc in (("elastic", e), ("relaunch", r)):
        sc = doc.get("sum_check")
        if isinstance(sc, dict) and sc.get("ok") is False:
            fails.append(
                f"{name} decomposition sum_check failed "
                f"(attributed {sc.get('attributed_s')}s vs total "
                f"{sc.get('total_wall_s')}s)"
            )
    fe, fr = e.get("fraction_useful"), r.get("fraction_useful")
    if not isinstance(fe, (int, float)) or not isinstance(
        fr, (int, float)
    ):
        fails.append(
            f"fraction_useful missing (elastic={fe!r}, relaunch={fr!r})"
        )
    elif fe <= fr:
        fails.append(
            f"elastic goodput {fe:.4f} is not strictly above the "
            f"relaunch goodput {fr:.4f} on the same fault spec "
            f"(elastic wasted {1 - fe:.4f}, relaunch {1 - fr:.4f})"
        )
    return fails


def _fmt(v, nd=3, scale=1.0, suffix=""):
    if not isinstance(v, (int, float)):
        return "n/a"
    return f"{v * scale:.{nd}f}{suffix}"


def format_run(doc: dict) -> str:
    """One run's decomposition table (the --run view)."""
    total = doc.get("total_wall_s")
    lines = [
        f"goodput [{doc.get('scope')}]  lineage {doc.get('lineage_id')}"
        f"  attempts {doc.get('attempts')}  chips {doc.get('chips')}",
        f"  total wall {_fmt(total, 2, 1.0, ' s')}  fraction_useful "
        f"{_fmt(doc.get('fraction_useful'), 4)}",
    ]
    seconds = doc.get("seconds") or {}
    if seconds:
        lines.append(f"  {'bucket':<18}{'seconds':>12}{'share':>9}")
        lines.append("  " + "-" * 37)
        for b in BUCKETS:
            s = seconds.get(b)
            if not isinstance(s, (int, float)):
                continue
            share = s / total if total else None
            lines.append(
                f"  {b:<18}{_fmt(s, 3):>12}{_fmt(share, 3):>9}"
            )
    sc = doc.get("sum_check") or {}
    lines.append(
        f"  sum_check: attributed {_fmt(sc.get('attributed_s'), 3)} s "
        f"vs total {_fmt(sc.get('total_wall_s'), 3)} s -> "
        f"{'ok' if sc.get('ok') else 'FAIL'}"
    )
    if doc.get("slo_attainment") is not None or doc.get(
        "scope"
    ) == "serve":
        lines.append(
            f"  serve: slo_attainment "
            f"{_fmt(doc.get('slo_attainment'), 4)}  availability "
            f"{_fmt(doc.get('availability'), 4)}  goodput tok/s/chip "
            f"{_fmt(doc.get('goodput_tokens_per_sec_per_chip'), 1)}"
        )
    if doc.get("replayed_steps_count"):
        lines.append(
            f"  replayed steps: {doc['replayed_steps_count']}"
        )
    return "\n".join(lines)


def format_group(key: tuple, recs: list[dict], last: int) -> str:
    strategy, mesh_s, host, scope = key
    lines = [
        f"strategy {strategy}  mesh({mesh_s})  scope {scope}  host {host}"
    ]
    cols = (
        f"  {'when (utc)':<20}{'lineage':<14}{'att':>4}{'wall':>10}"
        f"{'useful':>9}{'replay':>8}{'slo':>8}{'avail':>8}"
    )
    lines.append(cols)
    lines.append("  " + "-" * (len(cols) - 2))
    for rec in recs[-last:]:
        ts = rec.get("ts")
        when = (
            datetime.fromtimestamp(ts, tz=timezone.utc)
            .strftime("%Y-%m-%d %H:%M:%S")
            if isinstance(ts, (int, float)) else "?"
        )
        lines.append(
            f"  {when:<20}{str(rec.get('lineage_id'))[:12]:<14}"
            f"{rec.get('attempts') or 1:>4}"
            f"{_fmt(rec.get('total_wall_s'), 1, 1.0, ' s'):>10}"
            f"{_fmt(rec.get('fraction_useful'), 3):>9}"
            f"{rec.get('replayed_steps_count') or 0:>8}"
            f"{_fmt(rec.get('slo_attainment'), 3):>8}"
            f"{_fmt(rec.get('availability'), 3):>8}"
        )
    return "\n".join(lines)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ledger", default=DEFAULT_LEDGER, metavar="JSONL")
    ap.add_argument("--run", default=None, metavar="DIR",
                    help="render one run's goodput.json decomposition "
                         "(a run dir or a direct path) instead of the "
                         "ledger trend tables")
    ap.add_argument("--strategy", default=None,
                    help="comma-separated strategy filter")
    ap.add_argument("--last", type=int, default=8,
                    help="rows per key in the trend table")
    ap.add_argument("--window", type=int, default=DEFAULT_WINDOW,
                    help="prior records per key the baseline medians over")
    ap.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE,
                    help="fractional regression band on fraction_useful "
                         "/ slo_attainment (0.35 = may fall 35%%)")
    ap.add_argument("--format", choices=("table", "json"), default="table",
                    help="json: one structured document with the grouped "
                         "rows AND every check verdict (CI parses "
                         "instead of grepping)")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero when any key's latest row "
                         "regresses past the band or fails its own "
                         "sum contract (the CI goodput gate)")
    ap.add_argument("--slo-floor", type=float, default=None, metavar="F",
                    help="with --check: the latest serve-scope row's "
                         "slo_attainment must be >= F (absolute floor, "
                         "no baseline needed — the serve-smoke gate)")
    ap.add_argument("--check-elastic", nargs=2, default=None,
                    metavar=("ELASTIC", "RELAUNCH"),
                    help="two goodput.json paths (run dirs or files) "
                         "from the SAME fault spec: elastic "
                         "fraction_useful must be STRICTLY above the "
                         "relaunch one (the PR-14 recovery A/B in "
                         "goodput terms); exits non-zero otherwise")
    args = ap.parse_args(argv)

    # --check-elastic is a self-contained two-artifact gate
    if args.check_elastic is not None:
        try:
            fails = check_elastic(*args.check_elastic)
        except (OSError, ValueError) as e:
            print(f"CHECK FAIL elastic-vs-relaunch: {e}", file=sys.stderr)
            return 2
        for f in fails:
            print(f"CHECK FAIL elastic-vs-relaunch: {f}", file=sys.stderr)
        if fails:
            return 1
        e_doc = load_run_doc(args.check_elastic[0])
        r_doc = load_run_doc(args.check_elastic[1])
        print(
            "elastic-vs-relaunch goodput OK: elastic "
            f"{e_doc.get('fraction_useful'):.4f} > relaunch "
            f"{r_doc.get('fraction_useful'):.4f}",
            file=sys.stderr,
        )
        if args.format == "json":
            print(json.dumps({
                "record": "goodput_elastic_check",
                "elastic": e_doc.get("fraction_useful"),
                "relaunch": r_doc.get("fraction_useful"),
                "ok": True,
            }, indent=1))
        return 0

    if args.run is not None:
        try:
            doc = load_run_doc(args.run)
        except (OSError, ValueError) as e:
            print(f"no goodput doc at {args.run}: {e}", file=sys.stderr)
            return 2
        if args.format == "json":
            print(json.dumps(doc, indent=1, default=str))
        else:
            print(format_run(doc))
        if args.check:
            fails = check_group([_doc_as_row(doc)], args.tolerance)
            if args.slo_floor is not None:
                fails += check_slo_floor(
                    [_doc_as_row(doc)], args.slo_floor
                )
            for f in fails:
                print(f"CHECK FAIL {args.run}: {f}", file=sys.stderr)
            return 1 if fails else 0
        return 0

    records = read_ledger(args.ledger)
    if not records:
        print(f"no goodput records in {args.ledger} (run bench.py with "
              "--obs-dir, or the serve bench, to populate it)",
              file=sys.stderr)
        return 2 if args.check else 0
    if args.strategy:
        wanted = {s.strip() for s in args.strategy.split(",") if s.strip()}
        records = [
            r for r in records
            if (r.get("key") or {}).get("strategy") in wanted
        ]

    groups = group_records(records)
    verdicts: dict[tuple, dict] = {}
    for key, recs in groups.items():
        fails = check_group(recs, args.tolerance, args.window)
        if args.slo_floor is not None:
            fails += check_slo_floor(recs, args.slo_floor)
        note = (
            "no baseline yet (single record)"
            if len(recs) < 2 and not fails else None
        )
        verdicts[key] = {"fails": fails, "note": note}
    bad = sum(len(v["fails"]) for v in verdicts.values())

    if args.format == "json":
        doc = {
            "record": "goodput_report",
            "ledger": args.ledger,
            "tolerance": args.tolerance,
            "window": args.window,
            "slo_floor": args.slo_floor,
            "groups": [
                {
                    "strategy": key[0],
                    "mesh": key[1],
                    "host": key[2],
                    "scope": key[3],
                    "records": recs[-args.last:],
                    "fails": verdicts[key]["fails"],
                    "note": verdicts[key]["note"],
                }
                for key, recs in groups.items()
            ],
            "check": {"ok": bad == 0, "fails": bad},
        }
        print(json.dumps(doc, indent=1, default=str))
    else:
        print(f"goodput ledger: {args.ledger}  ({len(records)} "
              f"record(s), {len(groups)} key(s))\n")
        print("\n\n".join(
            format_group(k, v, args.last) for k, v in groups.items()
        ))

    if args.check:
        for key, v in verdicts.items():
            label = f"{key[0]} mesh({key[1]}) scope {key[3]}"
            if v["note"]:
                print(f"CHECK NOTE {label}: {v['note']}", file=sys.stderr)
            for fail in v["fails"]:
                print(f"CHECK FAIL {label}: {fail}", file=sys.stderr)
        if bad:
            return 1
        floor = (
            f", slo floor {args.slo_floor:.2f}"
            if args.slo_floor is not None else ""
        )
        print(f"\ngoodput check OK: {len(groups)} key(s) within the "
              f"{args.tolerance:.2f} tolerance band{floor}",
              file=sys.stderr)
    return 0


def _doc_as_row(doc: dict) -> dict:
    """Adapt a run's goodput.json doc to the ledger-row shape the
    check helpers read (key.scope + the summary fields)."""
    return {
        **doc,
        "key": {
            "strategy": doc.get("strategy"),
            "scope": doc.get("scope"),
        },
    }


if __name__ == "__main__":
    sys.exit(main())
