"""Render a serving run and gate cross-run serving regressions.

    python tools/serve_report.py runs/serve_smoke        # run report
    python tools/serve_report.py --ledger-only           # trend tables
    python tools/serve_report.py runs/serve_smoke --check   # the CI gate
    python tools/serve_report.py --check --check-ab      # + A/B verdict

The run directory holds the ``serve.json`` a ``bench.py --serve``
run dropped there (``ddl25spring_tpu/serve/driver.py``): the report
renders its throughput/admission table and ASCII latency histograms
(TTFT and per-decode-tick wall time).  The ledger
(``runs/perf_ledger.jsonl``) additionally holds one ``record: "serve"``
trend row per run, keyed (workload key, host) with git sha as the
variable under test; the ``mem`` and ``goodput`` rows live in the same
file under their own record kinds.

``--check`` exits non-zero when, within any
(key, host) group, the LATEST row regresses past the tolerance band
against the median of up to ``--window`` priors — tokens/sec/chip
falling by more than ``--tolerance`` (fractional, default 0.5 — CPU CI
wall clocks are noisy) or p95 TTFT growing by more than it.  On
shared-prefix runs (``profile=shared`` in the key) ``prefix_hit_rate``
is a gated key too: deterministic on the seeded trace, so it gates at
the same band.  Groups with a single row pass with a "no baseline yet"
note, and rows from different hosts never gate each other.
``--check-ab`` adds the continuous-batching acceptance verdict: the
latest row's A/B cell must show continuous strictly ahead of static in
tokens delivered at the fixed budget (the deterministic virtual-clock
comparison the driver records).  ``--check-prefix-ab`` adds the radix
prefix cache's (PR 11): the latest row's cached-vs-cold cell must show
``prefill_tokens_saved > 0``, a strictly higher cached virtual-clock
tokens/sec/chip, tokens delivered strictly ahead at the fixed budget,
and bitwise-matching token streams.  ``--check-spec-ab`` adds the
speculative-decoding verdict (PR 13): the latest row's spec-on-vs-off
cell must show real accepted draft tokens, a strictly higher
speculative virtual-clock tokens/sec/chip at equal admission budget,
tokens delivered strictly ahead at the fixed budget, and
bitwise-matching token streams over >= 1 compared request (greedy
speculation IS the target's own output — an empty comparison would
pass the bitwise gate vacuously, so it fails instead).  On spec runs
(``spec`` in the key) ``acceptance_rate`` joins the banded trend keys:
deterministic on the seeded trace, it collapses when the drafter or
the acceptance walk regresses, long before the noisy wall clocks
notice.  ``--check-reshape`` adds the elastic-reshape verdict (PR 14):
the latest row's reshape cell must show >= 1 driven scale event, ZERO
dropped (accepted-then-lost) requests across the replica handoff, and
reshape-window p95 TTFT within ``--reshape-ttft-factor`` (default 3x)
of steady state over a non-empty window.

Pure stdlib — no jax import, so the gate runs anywhere the JSON does.
"""

from __future__ import annotations

import json
import statistics
import sys
from datetime import datetime, timezone
from pathlib import Path

DEFAULT_LEDGER = "runs/perf_ledger.jsonl"
DEFAULT_TOLERANCE = 0.5
DEFAULT_WINDOW = 5
# restated from ddl25spring_tpu.obs.report so the gate never imports
# the package (or numpy/jax behind it)
SERVE_BASENAME = "serve.json"


def read_serve_json(run_dir: str) -> dict:
    p = Path(run_dir) / SERVE_BASENAME
    with open(p) as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or doc.get("record") != "serve":
        raise ValueError(f"{p} is not a serve record")
    return doc


def read_ledger(path: str) -> list[dict]:
    """Parseable ``record: "serve"`` rows in append order.  A torn line
    (a writer killed mid-write) is skipped, never fatal."""
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
        if isinstance(rec, dict) and rec.get("record") == "serve":
            out.append(rec)
    return out


def ledger_key(rec: dict) -> tuple[str, str]:
    """(workload key, host): the trend identity.  git sha is the
    variable under test, so it stays OUT of the key."""
    key = rec.get("key")
    key_s = (
        ",".join(f"{k}={key[k]}" for k in sorted(key))
        if isinstance(key, dict) else str(key)
    )
    return (key_s, str(rec.get("host")))


def group_records(records: list[dict]) -> dict[tuple, list[dict]]:
    groups: dict[tuple, list[dict]] = {}
    for rec in records:
        groups.setdefault(ledger_key(rec), []).append(rec)
    return groups


def _median(xs: list[float]) -> float | None:
    return statistics.median(xs) if xs else None


def _fmt(v, nd=3, scale=1.0, suffix=""):
    if not isinstance(v, (int, float)):
        return "n/a"
    return f"{v * scale:.{nd}f}{suffix}"


def check_group(
    recs: list[dict],
    tolerance: float = DEFAULT_TOLERANCE,
    window: int = DEFAULT_WINDOW,
) -> list[str]:
    """Regression verdicts for one (key, host) group: [] = latest within
    band (or no baseline yet).  Baseline = median of up to ``window``
    priors — one noisy historical run must not move the gate."""
    if len(recs) < 2:
        return []
    latest = recs[-1]
    base = recs[:-1][-window:]
    fails: list[str] = []
    b_tps = _median([
        r["tokens_per_sec_per_chip"] for r in base
        if isinstance(r.get("tokens_per_sec_per_chip"), (int, float))
    ])
    l_tps = latest.get("tokens_per_sec_per_chip")
    if b_tps and isinstance(l_tps, (int, float)):
        if l_tps < b_tps * (1.0 - tolerance):
            fails.append(
                f"tokens_per_sec_per_chip {l_tps:.2f} fell below the "
                f"{(1 - tolerance):.2f}x band under the baseline "
                f"{b_tps:.2f} (median of {len(base)} prior run(s))"
            )
    b_ttft = _median([
        r["ttft_s_p95"] for r in base
        if isinstance(r.get("ttft_s_p95"), (int, float))
    ])
    l_ttft = latest.get("ttft_s_p95")
    if b_ttft and isinstance(l_ttft, (int, float)):
        if l_ttft > b_ttft * (1.0 + tolerance):
            fails.append(
                f"ttft_s_p95 {l_ttft * 1e3:.2f} ms exceeds the "
                f"{(1 + tolerance):.2f}x band over the baseline "
                f"{b_ttft * 1e3:.2f} ms"
            )
    if _is_shared_prefix(latest):
        # prefix_hit_rate is DETERMINISTIC on the seeded shared-prefix
        # trace, so it gates like a perf key: a radix-tree or eviction
        # regression shows up as a hit-rate collapse long before the
        # noisy wall clocks notice
        b_hit = _median([
            r["prefix_hit_rate"] for r in base
            if isinstance(r.get("prefix_hit_rate"), (int, float))
        ])
        l_hit = latest.get("prefix_hit_rate")
        if b_hit and isinstance(l_hit, (int, float)):
            if l_hit < b_hit * (1.0 - tolerance):
                fails.append(
                    f"prefix_hit_rate {l_hit:.3f} fell below the "
                    f"{(1 - tolerance):.2f}x band under the baseline "
                    f"{b_hit:.3f} on a shared-prefix run"
                )
    if _is_spec(latest):
        # acceptance_rate is equally deterministic on a seeded trace
        # (greedy drafter vs greedy target): a collapse means the
        # drafter construction or the acceptance walk regressed
        b_acc = _median([
            r["acceptance_rate"] for r in base
            if isinstance(r.get("acceptance_rate"), (int, float))
        ])
        l_acc = latest.get("acceptance_rate")
        if b_acc and isinstance(l_acc, (int, float)):
            if l_acc < b_acc * (1.0 - tolerance):
                fails.append(
                    f"acceptance_rate {l_acc:.3f} fell below the "
                    f"{(1 - tolerance):.2f}x band under the baseline "
                    f"{b_acc:.3f} on a speculative run"
                )
    return fails


def _is_shared_prefix(rec: dict) -> bool:
    key = rec.get("key")
    return isinstance(key, dict) and key.get("profile") == "shared"


def _is_spec(rec: dict) -> bool:
    key = rec.get("key")
    return isinstance(key, dict) and bool(key.get("spec"))


def _ramp_or_top(rec: dict, name: str):
    """A gated counter: top-level on a ledger row, under ``ramp`` in a
    serve.json run doc — accept either, so the direct-doc fallback
    (custom --ledger paths) judges the same keys."""
    v = rec.get(name)
    if v is None:
        v = (rec.get("ramp") or {}).get(name)
    return v


def check_ab(recs: list[dict]) -> list[str]:
    """The continuous-batching acceptance verdict on the latest row:
    the A/B cell must exist and show continuous STRICTLY ahead."""
    if not recs:
        return []
    ab = recs[-1].get("ab")
    if not isinstance(ab, dict):
        return ["latest record carries no A/B cell (run without "
                "--no-serve-ab to record one)"]
    adv = ab.get("advantage_tokens")
    if not isinstance(adv, (int, float)) or adv <= 0:
        return [
            f"continuous batching did not beat static at the fixed "
            f"budget: continuous {ab.get('continuous_tokens_at_budget')} "
            f"vs static {ab.get('static_tokens_at_budget')} tokens "
            f"(budget {ab.get('budget_s')} s)"
        ]
    return []


def check_prefix_ab(recs: list[dict]) -> list[str]:
    """The radix-prefix-cache acceptance verdict on the latest row
    (PR 11): the cached-vs-cold cell must exist and show real skipped
    prefill work, a strict virtual-clock win at equal admission budget,
    and bitwise-matching token streams."""
    if not recs:
        return []
    latest = recs[-1]
    pab = latest.get("prefix_ab")
    if not isinstance(pab, dict):
        return ["latest record carries no prefix A/B cell (run with "
                "DDL25_SERVE_PREFIX=1 and without --no-serve-prefix-ab "
                "to record one)"]
    # a ledger row carries the flattened cell; a serve.json doc carries
    # the driver's full output with cached/cold sub-dicts — accept both
    cached_arm = pab.get("cached") or {}
    cold_arm = pab.get("cold") or {}
    pab = {
        **pab,
        "cached_tokens_per_sec_per_chip": pab.get(
            "cached_tokens_per_sec_per_chip",
            cached_arm.get("tokens_per_sec_per_chip"),
        ),
        "cold_tokens_per_sec_per_chip": pab.get(
            "cold_tokens_per_sec_per_chip",
            cold_arm.get("tokens_per_sec_per_chip"),
        ),
        "prefill_tokens_saved": pab.get(
            "prefill_tokens_saved", cached_arm.get("prefill_tokens_saved")
        ),
    }
    fails: list[str] = []
    saved = pab.get("prefill_tokens_saved")
    if not isinstance(saved, (int, float)) or saved <= 0:
        fails.append(
            f"prefix cache skipped no prefill work "
            f"(prefill_tokens_saved={saved}); on a shared-prefix trace "
            "the radix cache must hit"
        )
    cached_tps = pab.get("cached_tokens_per_sec_per_chip")
    cold_tps = pab.get("cold_tokens_per_sec_per_chip")
    if not (isinstance(cached_tps, (int, float))
            and isinstance(cold_tps, (int, float))
            and cached_tps > cold_tps):
        fails.append(
            f"cached engine not strictly faster on the virtual clock: "
            f"cached {cached_tps} vs cold {cold_tps} tokens/sec/chip "
            "at equal admission budget"
        )
    adv = pab.get("advantage_tokens")
    if not isinstance(adv, (int, float)) or adv <= 0:
        fails.append(
            f"cached engine not ahead at the fixed budget: cached "
            f"{pab.get('cached_tokens_at_budget')} vs cold "
            f"{pab.get('cold_tokens_at_budget')} tokens (budget "
            f"{pab.get('budget_s')} s)"
        )
    cmp_n = pab.get("compared_requests")
    if pab.get("tokens_match") is not True or not (
        isinstance(cmp_n, int) and cmp_n > 0
    ):
        # tokens_match is all() over the requests BOTH arms completed —
        # vacuously True over an empty intersection, so zero compared
        # requests is itself a gate failure, not a pass
        fails.append(
            "prefix-cached decode did not reproduce the cold path "
            f"token-for-token (tokens_match={pab.get('tokens_match')} "
            f"over {cmp_n} compared request(s); the comparison must "
            "cover at least one request)"
        )
    if _is_shared_prefix(latest):
        hit = _ramp_or_top(latest, "prefix_hit_rate")
        if not isinstance(hit, (int, float)) or hit <= 0:
            fails.append(
                f"prefix_hit_rate={hit} on a shared-prefix run (gated "
                "key: the seeded trace repeats its system prompts, so "
                "a zero hit rate is a cache defect, not workload noise)"
            )
    return fails


DEFAULT_RESHAPE_TTFT_FACTOR = 3.0


def check_reshape(
    recs: list[dict],
    ttft_factor: float = DEFAULT_RESHAPE_TTFT_FACTOR,
) -> list[str]:
    """The elastic-reshape acceptance verdict on the latest row
    (PR 14): the reshape cell must exist and show (a) at least one
    reshape event actually driven, (b) ZERO dropped requests across the
    handoff — a request accepted is a request served, the page-pool
    handoff's whole contract — and (c) p95 TTFT inside the reshape
    windows bounded at ``ttft_factor`` x the steady-state p95, over a
    non-empty window (an event nobody was waiting through proves
    nothing, the same vacuity hole the compared_requests guards
    close)."""
    if not recs:
        return []
    rsh = recs[-1].get("reshape")
    if not isinstance(rsh, dict):
        return ["latest record carries no reshape cell (arm elastic "
                "chaos — DDL25_CHAOS=traffic_spike@k / device_loss@k / "
                "capacity_change@k:N — on a bench.py --serve run to "
                "record one)"]
    fails: list[str] = []
    events = rsh.get("events") or []
    if not events:
        fails.append(
            "reshape cell carries no events: the armed chaos never "
            "drove a scale-up/down (wrong step index for the trace?)"
        )
    dropped = rsh.get("dropped_requests")
    if dropped != 0:
        fails.append(
            f"dropped_requests={dropped}: an admitted request was lost "
            f"across the handoff (admitted {rsh.get('admitted')} vs "
            f"completed {rsh.get('completed')}) — the drain/re-admit "
            "discipline must never lose accepted work"
        )
    steady = rsh.get("ttft_s_p95_steady")
    window = rsh.get("ttft_s_p95_reshape")
    n_window = rsh.get("reshape_window_requests")
    if not isinstance(n_window, int) or n_window < 1:
        fails.append(
            f"reshape_window_requests={n_window}: no request's first "
            "token landed inside a reshape window, so the TTFT bound "
            "is vacuous — fire the event while traffic is live"
        )
    elif not (isinstance(steady, (int, float))
              and isinstance(window, (int, float))):
        fails.append(
            f"reshape TTFT percentiles undefined (steady={steady}, "
            f"reshape={window}) with {n_window} window request(s)"
        )
    elif window > ttft_factor * steady:
        fails.append(
            f"p95 TTFT through the reshape window {window * 1e3:.2f} ms "
            f"exceeds {ttft_factor:.1f}x the steady-state p95 "
            f"{steady * 1e3:.2f} ms (over {n_window} window request(s))"
        )
    return fails


def check_spec_ab(recs: list[dict]) -> list[str]:
    """The speculative-decoding acceptance verdict on the latest row
    (PR 13): the spec-on-vs-off cell must exist and show real accepted
    draft work, a strict virtual-clock win at equal admission budget,
    and bitwise-matching token streams over at least one compared
    request (greedy speculation must BE the target's own output — an
    empty intersection would pass ``all()`` vacuously, the same hole
    the PR-11 ``compared_requests`` guard closed for the prefix gate).
    """
    if not recs:
        return []
    latest = recs[-1]
    sab = latest.get("spec_ab")
    if not isinstance(sab, dict):
        return ["latest record carries no spec A/B cell (run with "
                "DDL25_SERVE_SPEC=1 and without --no-serve-spec-ab "
                "to record one)"]
    # a ledger row carries the flattened cell; a serve.json doc carries
    # the driver's full output with spec/nospec sub-dicts — accept both
    spec_arm = sab.get("spec") or {}
    nospec_arm = sab.get("nospec") or {}
    sab = {
        **sab,
        "spec_tokens_per_sec_per_chip": sab.get(
            "spec_tokens_per_sec_per_chip",
            spec_arm.get("tokens_per_sec_per_chip"),
        ),
        "nospec_tokens_per_sec_per_chip": sab.get(
            "nospec_tokens_per_sec_per_chip",
            nospec_arm.get("tokens_per_sec_per_chip"),
        ),
        "draft_tokens_accepted": sab.get(
            "draft_tokens_accepted",
            spec_arm.get("draft_tokens_accepted"),
        ),
        "acceptance_rate": sab.get(
            "acceptance_rate", spec_arm.get("acceptance_rate")
        ),
    }
    fails: list[str] = []
    accepted = sab.get("draft_tokens_accepted")
    if not isinstance(accepted, (int, float)) or accepted <= 0:
        fails.append(
            f"the drafter contributed no accepted tokens "
            f"(draft_tokens_accepted={accepted}, acceptance_rate="
            f"{sab.get('acceptance_rate')}); speculation that never "
            "accepts only ever costs"
        )
    spec_tps = sab.get("spec_tokens_per_sec_per_chip")
    nospec_tps = sab.get("nospec_tokens_per_sec_per_chip")
    if not (isinstance(spec_tps, (int, float))
            and isinstance(nospec_tps, (int, float))
            and spec_tps > nospec_tps):
        fails.append(
            f"speculative engine not strictly faster on the virtual "
            f"clock: spec {spec_tps} vs non-spec {nospec_tps} "
            "tokens/sec/chip at equal admission budget"
        )
    adv = sab.get("advantage_tokens")
    if not isinstance(adv, (int, float)) or adv <= 0:
        fails.append(
            f"speculative engine not ahead at the fixed budget: spec "
            f"{sab.get('spec_tokens_at_budget')} vs non-spec "
            f"{sab.get('nospec_tokens_at_budget')} tokens (budget "
            f"{sab.get('budget_s')} s)"
        )
    cmp_n = sab.get("compared_requests")
    if sab.get("tokens_match") is not True or not (
        isinstance(cmp_n, int) and cmp_n > 0
    ):
        fails.append(
            "speculative decode did not reproduce the sequential "
            f"engine token-for-token (tokens_match="
            f"{sab.get('tokens_match')} over {cmp_n} compared "
            "request(s); the comparison must cover at least one "
            "request)"
        )
    return fails


def check_tp(recs: list[dict]) -> list[str]:
    """The TP-sharded serving acceptance verdict on the latest row
    (PR 18): the sharded-vs-dense cell must exist, the sharded arm's
    static per-chip residency must come in STRICTLY below the dense
    arm's (the whole point of dividing the KV head dim and the
    params), and the token streams must match bitwise over at least
    one compared request — vacuity-guarded exactly like the spec gate
    (an empty intersection passes ``all()`` for free)."""
    if not recs:
        return []
    latest = recs[-1]
    tab = latest.get("tp_ab")
    if not isinstance(tab, dict):
        return ["latest record carries no TP A/B cell (run with "
                "--serve-tp N / DDL25_SERVE_TP > 1 and without "
                "--no-serve-tp-ab to record one)"]
    # a ledger row carries the flattened cell; a serve.json doc carries
    # the driver's full output with sharded/dense sub-dicts — both work
    tp_arm = tab.get("sharded") or {}
    dense_arm = tab.get("dense") or {}
    tab = {
        **tab,
        "tp_mem_budget_bytes_per_chip": tab.get(
            "tp_mem_budget_bytes_per_chip",
            tp_arm.get("mem_budget_bytes_per_chip"),
        ),
        "dense_mem_budget_bytes_per_chip": tab.get(
            "dense_mem_budget_bytes_per_chip",
            dense_arm.get("mem_budget_bytes_per_chip"),
        ),
    }
    fails: list[str] = []
    shard = tab.get("tp_mem_budget_bytes_per_chip")
    dense = tab.get("dense_mem_budget_bytes_per_chip")
    if not (isinstance(shard, (int, float))
            and isinstance(dense, (int, float)) and shard < dense):
        fails.append(
            f"tp={tab.get('tp')} did not shrink the static per-chip "
            f"residency: sharded {shard} vs dense {dense} bytes "
            "(mem_budget_bytes must divide for the sharded engine to "
            "serve bigger models at all)"
        )
    if tab.get("budget_shrunk") is not True:
        fails.append(
            f"the driver's budget_shrunk verdict is "
            f"{tab.get('budget_shrunk')!r}, expected True"
        )
    cmp_n = tab.get("compared_requests")
    if tab.get("tokens_match") is not True or not (
        isinstance(cmp_n, int) and cmp_n > 0
    ):
        fails.append(
            "the sharded engine did not reproduce the dense oracle "
            f"token-for-token (tokens_match={tab.get('tokens_match')} "
            f"over {cmp_n} compared request(s); the comparison must "
            "cover at least one request)"
        )
    return fails


def histogram(xs: list[float], *, bins: int = 10, width: int = 40,
              scale: float = 1e3, unit: str = "ms") -> list[str]:
    """ASCII histogram lines (log-ish readable, linear bins)."""
    xs = [x for x in xs if isinstance(x, (int, float))]
    if not xs:
        return ["  (no samples)"]
    lo, hi = min(xs), max(xs)
    span = (hi - lo) or max(abs(hi), 1e-9)
    counts = [0] * bins
    for x in xs:
        i = min(int((x - lo) / span * bins), bins - 1)
        counts[i] += 1
    peak = max(counts)
    out = []
    for i, c in enumerate(counts):
        a = lo + span * i / bins
        b = lo + span * (i + 1) / bins
        bar = "#" * max(1 if c else 0, round(c / peak * width))
        out.append(
            f"  {a * scale:9.3f}-{b * scale:9.3f} {unit} "
            f"|{bar:<{width}}| {c}"
        )
    return out


def format_run(doc: dict) -> str:
    ramp = doc.get("ramp", {})
    key = doc.get("key", {})
    lines = [
        "serving run "
        + " ".join(f"{k}={key[k]}" for k in sorted(key))
        + f"  sha {(doc.get('git_sha') or '?')[:7]}",
        "",
        f"  requests {doc.get('requests')}  admitted {ramp.get('admitted')}"
        f"  rejected {ramp.get('rejected')} {ramp.get('rejected_by_reason')}"
        f"  completed {ramp.get('completed')}",
        f"  generated tokens {ramp.get('generated_tokens')}"
        f"  tokens/sec/chip "
        f"{_fmt(ramp.get('tokens_per_sec_per_chip'), 2)}"
        f"  (chips {ramp.get('n_chips')}, wall "
        f"{_fmt(ramp.get('wall_s'), 2)} s)",
        f"  TTFT p50 {_fmt(ramp.get('ttft_s_p50'), 2, 1e3, ' ms')}"
        f"  p95 {_fmt(ramp.get('ttft_s_p95'), 2, 1e3, ' ms')}"
        f"  |  per-token p50 "
        f"{_fmt(ramp.get('tok_latency_s_p50'), 2, 1e3, ' ms')}"
        f"  p95 {_fmt(ramp.get('tok_latency_s_p95'), 2, 1e3, ' ms')}",
        f"  queue depth max {ramp.get('queue_depth_max')}"
        f"  page pool peak {ramp.get('page_pool_peak_pages')}"
        f"/{ramp.get('page_pool_pages')} pages "
        f"({_fmt(ramp.get('page_pool_peak_occupancy'), 1, 100, '%')})"
        f"  pool-ok failures {ramp.get('pool_ok_failures')}",
    ]
    # PR 16: the per-request TTFT decomposition — "p95 regressed"
    # becomes "p95 regressed because queue-wait doubled"
    dec = ramp.get("ttft_decomp") or {}
    if dec.get("requests"):
        lines.append(
            f"  TTFT decomposition ({dec.get('clock')} clock, "
            f"{dec['requests']} req): queue-wait p50 "
            f"{_fmt(dec.get('queue_wait_s_p50'), 2, 1e3, ' ms')}"
            f" p95 {_fmt(dec.get('queue_wait_s_p95'), 2, 1e3, ' ms')}"
            f"  |  prefill p50 "
            f"{_fmt(dec.get('prefill_s_p50'), 2, 1e3, ' ms')}"
            f" p95 {_fmt(dec.get('prefill_s_p95'), 2, 1e3, ' ms')}"
            f"  |  first-decode p50 "
            f"{_fmt(dec.get('first_decode_s_p50'), 2, 1e3, ' ms')}"
            f" p95 {_fmt(dec.get('first_decode_s_p95'), 2, 1e3, ' ms')}"
        )
    prefix = ramp.get("prefix") or {}
    if prefix.get("enabled"):
        lines.append(
            f"  prefix cache: hit rate "
            f"{_fmt(ramp.get('prefix_hit_rate'), 1, 100, '%')} "
            f"({prefix.get('hits')}/{prefix.get('lookups')} admitted)  "
            f"prefill saved {ramp.get('prefill_tokens_saved')} tokens / "
            f"{_fmt(ramp.get('prefill_flops_saved'), 2, 1e-6, ' MFLOP')}"
            f"  cached pages {prefix.get('cached_pages')}  evictions "
            f"{prefix.get('evictions')}"
        )
    spec = ramp.get("spec") or {}
    if spec.get("enabled"):
        lines.append(
            f"  speculative decode: k={spec.get('k')} drafter "
            f"{spec.get('draft_layers')}L/{spec.get('draft_dim')}d "
            f"(flop ratio {_fmt(spec.get('flop_ratio'), 2)})  "
            f"acceptance "
            f"{_fmt(ramp.get('acceptance_rate'), 1, 100, '%')} "
            f"({ramp.get('draft_tokens_accepted')} accepted / "
            f"{ramp.get('draft_tokens_rejected')} rejected)  "
            f"rounds {spec.get('rounds')}  draft steps "
            f"{spec.get('draft_steps')}  accepts by prefix "
            f"{spec.get('accept_counts')}"
        )
    ab = doc.get("ab")
    if ab:
        lines += [
            "",
            "  continuous-vs-static A/B (virtual clock, tick "
            f"{_fmt(ab.get('tick_s'), 4)} s, budget "
            f"{_fmt(ab.get('budget_s'), 3)} s):",
            f"    continuous {ab.get('continuous_tokens_at_budget')} "
            f"tokens  static {ab.get('static_tokens_at_budget')} tokens  "
            f"advantage {ab.get('advantage_tokens')} "
            f"({_fmt(ab.get('advantage_frac'), 1, 100, '%')})",
        ]
    pab = doc.get("prefix_ab")
    if pab:
        cached = pab.get("cached") or {}
        cold = pab.get("cold") or {}
        lines += [
            "",
            "  cached-vs-cold prefix A/B (virtual clock, budget "
            f"{_fmt(pab.get('budget_s'), 3)} s, equal admission "
            "budget):",
            f"    cached {pab.get('cached_tokens_at_budget')} tokens  "
            f"cold {pab.get('cold_tokens_at_budget')} tokens  advantage "
            f"{pab.get('advantage_tokens')} "
            f"({_fmt(pab.get('advantage_frac'), 1, 100, '%')})",
            f"    tokens/sec/chip cached "
            f"{_fmt(cached.get('tokens_per_sec_per_chip'), 2)}"
            f" vs cold "
            f"{_fmt(cold.get('tokens_per_sec_per_chip'), 2)}"
            f"  hit rate {_fmt(cached.get('prefix_hit_rate'), 1, 100, '%')}"
            f"  saved {cached.get('prefill_tokens_saved')} tokens  "
            f"tokens match {pab.get('tokens_match')}",
        ]
    sab = doc.get("spec_ab")
    if sab:
        spec_arm = sab.get("spec") or {}
        nospec_arm = sab.get("nospec") or {}
        lines += [
            "",
            "  spec-on-vs-off A/B (virtual clock, budget "
            f"{_fmt(sab.get('budget_s'), 3)} s, equal admission "
            "budget; verify = 1 tick, drafter at its FLOP ratio):",
            f"    spec {sab.get('spec_tokens_at_budget')} tokens  "
            f"non-spec {sab.get('nospec_tokens_at_budget')} tokens  "
            f"advantage {sab.get('advantage_tokens')} "
            f"({_fmt(sab.get('advantage_frac'), 1, 100, '%')})",
            f"    tokens/sec/chip spec "
            f"{_fmt(spec_arm.get('tokens_per_sec_per_chip'), 2)}"
            f" vs non-spec "
            f"{_fmt(nospec_arm.get('tokens_per_sec_per_chip'), 2)}"
            f"  acceptance "
            f"{_fmt(spec_arm.get('acceptance_rate'), 1, 100, '%')}"
            f"  tokens match {sab.get('tokens_match')}",
        ]
    rsh = doc.get("reshape")
    if rsh:
        evs = rsh.get("events") or []
        lines += [
            "",
            f"  elastic reshape ({len(evs)} event(s), replicas "
            f"{rsh.get('replicas_start')} -> {rsh.get('replicas_end')}, "
            f"dropped {rsh.get('dropped_requests')}):",
        ]
        for ev in evs:
            lines.append(
                f"    {ev.get('reason')}: {ev.get('old')} -> "
                f"{ev.get('new')} at t={_fmt(ev.get('t'), 3)} s"
                f" (drained by {_fmt(ev.get('t_end'), 3)} s,"
                f" requeued {ev.get('requeued') or 0})"
            )
        lines.append(
            f"    TTFT p95 reshape window "
            f"{_fmt(rsh.get('ttft_s_p95_reshape'), 1, 1e3, ' ms')} "
            f"({rsh.get('reshape_window_requests')} req) vs steady "
            f"{_fmt(rsh.get('ttft_s_p95_steady'), 1, 1e3, ' ms')} "
            f"({rsh.get('steady_requests')} req)"
        )
    if doc.get("ttft_s"):
        lines += ["", "  TTFT histogram:"] + histogram(doc["ttft_s"])
    if doc.get("tick_wall_s"):
        lines += (
            ["", "  decode-tick wall histogram:"]
            + histogram(doc["tick_wall_s"])
        )
    return "\n".join(lines)


def format_group(key: tuple, recs: list[dict], last: int) -> str:
    key_s, host = key
    lines = [f"serve {key_s}  host {host}"]
    cols = (
        f"  {'when (utc)':<20}{'sha':<9}{'tok/s/chip':>11}"
        f"{'ttft p50':>11}{'ttft p95':>11}{'tok p95':>11}"
        f"{'adm':>5}{'rej':>5}{'pool%':>7}{'ab adv':>8}"
        f"{'hit%':>7}{'saved':>7}{'pfx adv':>8}"
        f"{'acc%':>7}{'dacc':>6}{'spec adv':>9}"
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
        sha = (rec.get("git_sha") or "?")[:7]
        ab = rec.get("ab") or {}
        pab = rec.get("prefix_ab") or {}
        sab = rec.get("spec_ab") or {}
        lines.append(
            f"  {when:<20}{sha:<9}"
            f"{_fmt(rec.get('tokens_per_sec_per_chip'), 2):>11}"
            f"{_fmt(rec.get('ttft_s_p50'), 1, 1e3, 'ms'):>11}"
            f"{_fmt(rec.get('ttft_s_p95'), 1, 1e3, 'ms'):>11}"
            f"{_fmt(rec.get('tok_latency_s_p95'), 1, 1e3, 'ms'):>11}"
            f"{rec.get('admitted', '?'):>5}"
            f"{rec.get('rejected', '?'):>5}"
            f"{_fmt(rec.get('page_pool_peak_occupancy'), 0, 100, '%'):>7}"
            f"{_fmt(ab.get('advantage_tokens'), 0):>8}"
            f"{_fmt(rec.get('prefix_hit_rate'), 0, 100, '%'):>7}"
            f"{_fmt(rec.get('prefill_tokens_saved'), 0):>7}"
            f"{_fmt(pab.get('advantage_tokens'), 0):>8}"
            f"{_fmt(rec.get('acceptance_rate'), 0, 100, '%'):>7}"
            f"{_fmt(rec.get('draft_tokens_accepted'), 0):>6}"
            f"{_fmt(sab.get('advantage_tokens'), 0):>9}"
        )
    return "\n".join(lines)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("run_dir", nargs="?", default=None,
                    help="obs dir holding serve.json (omit with "
                         "--ledger-only for the trend tables alone)")
    ap.add_argument("--ledger", default=DEFAULT_LEDGER, metavar="JSONL")
    ap.add_argument("--ledger-only", action="store_true",
                    help="skip the run report; render/check the ledger")
    ap.add_argument("--last", type=int, default=8,
                    help="rows per key in the trend table")
    ap.add_argument("--window", type=int, default=DEFAULT_WINDOW,
                    help="prior rows per key the baseline medians over")
    ap.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE,
                    help="fractional regression band (0.5 = tokens/sec "
                         "may drop 50%%, p95 TTFT may grow 50%%); CPU CI "
                         "wall clocks want wide bands")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero when any (key, host) group's "
                         "latest row regresses past the band (the CI "
                         "serving gate)")
    ap.add_argument("--check-ab", action="store_true",
                    help="also fail when the latest row's "
                         "continuous-vs-static A/B does not show "
                         "continuous strictly ahead (implies --check)")
    ap.add_argument("--check-prefix-ab", action="store_true",
                    help="also fail when the latest row's cached-vs-"
                         "cold prefix A/B does not show skipped prefill "
                         "work, a strict virtual-clock win, and "
                         "matching token streams (implies --check)")
    ap.add_argument("--check-spec-ab", action="store_true",
                    help="also fail when the latest row's speculative "
                         "spec-on-vs-off A/B does not show accepted "
                         "draft tokens, a strict virtual-clock win, and "
                         "matching token streams over >= 1 compared "
                         "request (implies --check)")
    ap.add_argument("--check-tp", action="store_true",
                    help="also fail when the latest row's TP "
                         "sharded-vs-dense A/B does not show a strictly "
                         "smaller per-chip static residency and "
                         "matching token streams over >= 1 compared "
                         "request (implies --check)")
    ap.add_argument("--check-reshape", action="store_true",
                    help="also fail when the latest row's elastic "
                         "reshape cell does not show >= 1 driven event, "
                         "ZERO dropped (accepted-then-lost) requests "
                         "across the replica handoff, and reshape-"
                         "window p95 TTFT within --reshape-ttft-factor "
                         "of steady state (implies --check)")
    ap.add_argument("--reshape-ttft-factor", type=float,
                    default=DEFAULT_RESHAPE_TTFT_FACTOR,
                    help="allowed p95 TTFT inflation through a reshape "
                         "window vs steady state (default 3.0)")
    args = ap.parse_args(argv)
    if (args.check_ab or args.check_prefix_ab or args.check_spec_ab
            or args.check_tp or args.check_reshape):
        args.check = True  # a verdict nobody reads is not a gate

    if args.run_dir is None and not args.ledger_only:
        ap.error("pass a run_dir, or --ledger-only")

    doc = None
    if args.run_dir is not None:
        try:
            doc = read_serve_json(args.run_dir)
        except (OSError, ValueError, json.JSONDecodeError) as e:
            print(f"no serving record at {args.run_dir}: {e}",
                  file=sys.stderr)
            return 2
        print(format_run(doc))
        print()

    records = read_ledger(args.ledger)
    if not records:
        print(f"no serve records in {args.ledger} (run "
              "bench.py --serve to populate it)", file=sys.stderr)
        return 2 if args.check else 0

    groups = group_records(records)
    # with a run_dir the A/B acceptance verdict gates THAT run's
    # (key, host) group ONLY; other groups' rows may legitimately have
    # been recorded with --no-serve-ab or hold a documented tie (an
    # unloaded engine serves both policies identically), and a stale
    # unrelated key must not wedge the gate forever.  Ledger-only mode
    # has no run to scope to and stays strict across every group.
    ab_scope = ledger_key(doc) if doc is not None else None
    verdicts: dict[tuple, dict] = {}
    for key, recs in groups.items():
        fails: list[str] = []
        note = None
        if ab_scope is None or key == ab_scope:
            # the A/B verdicts need no baseline: a single row gates
            if args.check_ab:
                fails += check_ab(recs)
            if args.check_prefix_ab:
                fails += check_prefix_ab(recs)
            if args.check_spec_ab:
                fails += check_spec_ab(recs)
            if args.check_tp:
                fails += check_tp(recs)
            if args.check_reshape:
                fails += check_reshape(recs, args.reshape_ttft_factor)
        if len(recs) < 2:
            if not fails:
                note = "no baseline yet (single record)"
        else:
            fails += check_group(recs, args.tolerance, args.window)
        verdicts[key] = {"fails": fails, "note": note}
    if ((args.check_ab or args.check_prefix_ab or args.check_spec_ab
            or args.check_tp or args.check_reshape)
            and ab_scope is not None and ab_scope not in groups):
        # the run under test never landed in this ledger (custom
        # --ledger path): judge its serve.json directly
        fails = check_ab([doc]) if args.check_ab else []
        if args.check_prefix_ab:
            fails += check_prefix_ab([doc])
        if args.check_spec_ab:
            fails += check_spec_ab([doc])
        if args.check_tp:
            fails += check_tp([doc])
        if args.check_reshape:
            fails += check_reshape([doc], args.reshape_ttft_factor)
        verdicts[ab_scope] = {"fails": fails, "note": None}
    bad = sum(len(v["fails"]) for v in verdicts.values())

    print(f"serve ledger: {args.ledger}  ({len(records)} record(s), "
          f"{len(groups)} key(s))\n")
    print("\n\n".join(
        format_group(k, v, args.last) for k, v in groups.items()
    ))

    if args.check:
        for key, v in verdicts.items():
            label = f"serve({key[0][:60]})"
            if v["note"]:
                print(f"CHECK NOTE {label}: {v['note']}", file=sys.stderr)
            for fail in v["fails"]:
                print(f"CHECK FAIL {label}: {fail}", file=sys.stderr)
        if bad:
            return 1
        ab_note = ", A/B advantage verified" if args.check_ab else ""
        if args.check_prefix_ab:
            ab_note += ", prefix A/B advantage verified"
        if args.check_spec_ab:
            ab_note += ", spec A/B advantage verified"
        if args.check_tp:
            ab_note += ", tp shrink + token equality verified"
        if args.check_reshape:
            ab_note += ", reshape handoff verified"
        print(f"\nserve check OK: {len(groups)} key(s) within the "
              f"{args.tolerance:.2f} tolerance band{ab_note}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
