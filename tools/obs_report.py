"""Summarize a telemetry run directory written by ``ddl25spring_tpu.obs``.

    python tools/obs_report.py <run_dir>          # aligned table
    python tools/obs_report.py <run_dir> --json   # machine-readable

The run directory comes from any obs-instrumented driver — e.g.
``python bench.py --smoke`` (CPU) or ``python bench.py --obs-dir DIR``
(TPU).  Besides the perf table, the report renders a "health" section
from ``flight.json`` and a "recovery" section from the flight meta +
the autosave ``ckpt/manifest.json`` (last durable step, resume count,
steps replayed, saves the poisoned-checkpoint gate refused) — so a
post-mortem answers "what survived" as well as "what died".
Everything reported derives from host-side artifacts
(``metrics.jsonl``, ``counters.json``, ``trace.json``); no
``jax.profiler`` capture is involved anywhere on this path, so it needs
no chip and no trace.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from ddl25spring_tpu.obs.report import format_report, summarize_run  # noqa: E402


EXIT_CODES = """\
exit codes:
  0  report printed; with --check-health, the run is healthy
  2  no telemetry at run_dir (missing metrics.jsonl / artifacts)
  3  --check-health: sentinel violation(s), stall, or flight error
  4  --check-health: memory violation — mem.json records leaked KV
     pages, windowed monotone live-bytes growth, or a budget-band
     breach (graft-mem; see tools/mem_report.py for the full gate)
  5  --check-health: goodput/SLO violation — goodput.json's bucket
     decomposition breaks its sum-to-wall contract, or (with
     --slo-floor) a serve-scope record's SLO attainment sits below
     the floor (graft-goodput; see tools/goodput_report.py for the
     cross-run trend gate)
"""


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        epilog=EXIT_CODES,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("run_dir", help="directory holding metrics.jsonl (+ "
                                    "counters.json / trace.json)")
    ap.add_argument("--json", action="store_true",
                    help="print the raw summary dict as JSON")
    ap.add_argument("--check-health", action="store_true",
                    help="exit non-zero when the run's flight.json "
                         "records sentinel violations or a stall (the "
                         "CI health gate)")
    ap.add_argument("--slo-floor", type=float, default=None,
                    metavar="FRACTION",
                    help="with --check-health: also fail (exit 5) when "
                         "a serve-scope goodput.json reports SLO "
                         "attainment below this fraction (0..1)")
    args = ap.parse_args(argv)

    try:
        summary = summarize_run(args.run_dir)
    except FileNotFoundError as e:
        print(f"no telemetry at {args.run_dir}: {e}", file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps(summary, indent=1, default=str))
    else:
        print(format_report(summary))
    if args.check_health:
        h = summary.get("health") or {}
        problems = []
        # an elastic in-run reshape (ft/elastic.py, flight kind=
        # "reshape") is RECOVERY, not damage: the gate names it so the
        # log is explicit, and never fails on it
        reshapes = (summary.get("recovery") or {}).get("reshapes")
        if reshapes:
            print(
                f"note: {reshapes} elastic reshape(s) recorded for "
                f"{args.run_dir} — recovery events, not violations",
                file=sys.stderr,
            )
        if h.get("violations"):
            problems.append(f"{h['violations']} sentinel violation(s)")
        if h.get("stall"):
            problems.append(
                f"stall (watchdog {h['stall'].get('watchdog')})"
            )
        if h.get("error"):
            problems.append(h["error"])
        if problems:
            print(
                f"health check FAILED for {args.run_dir}: "
                + "; ".join(problems),
                file=sys.stderr,
            )
            return 3
        mem = summary.get("mem") or {}
        mem_problems = []
        if not mem.get("error"):
            if mem.get("leaked_pages"):
                mem_problems.append(
                    f"{mem['leaked_pages']} leaked KV page(s)"
                )
            if mem.get("growth_violations"):
                mem_problems.append(
                    f"{mem['growth_violations']} live-bytes growth "
                    f"violation(s)"
                )
            b = mem.get("budget") or {}
            if b.get("available") and b.get("within_band") is False:
                mem_problems.append(
                    f"budget band breach (measured/budget "
                    f"{b.get('ratio')}, tol {b.get('tolerance')})"
                )
        if mem_problems:
            print(
                f"memory check FAILED for {args.run_dir}: "
                + "; ".join(mem_problems),
                file=sys.stderr,
            )
            return 4
        gp = summary.get("goodput") or {}
        gp_problems = []
        if gp and not gp.get("error"):
            sc = gp.get("sum_check") or {}
            if sc.get("ok") is False:
                gp_problems.append(
                    f"decomposition breaks the sum-to-wall contract "
                    f"(attributed {sc.get('attributed_s')} s vs wall "
                    f"{sc.get('total_wall_s')} s, tol "
                    f"{sc.get('tolerance')})"
                )
            att = gp.get("slo_attainment")
            if (args.slo_floor is not None
                    and gp.get("scope") == "serve"
                    and (not isinstance(att, (int, float))
                         or att < args.slo_floor)):
                gp_problems.append(
                    f"SLO attainment {att} below floor "
                    f"{args.slo_floor}"
                )
        if gp_problems:
            print(
                f"goodput check FAILED for {args.run_dir}: "
                + "; ".join(gp_problems),
                file=sys.stderr,
            )
            return 5
        print(f"health check ok for {args.run_dir}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
