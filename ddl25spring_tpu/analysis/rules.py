"""The hazard rule pack: rule ids, severities, and the Finding record.

Every rule is a pure function from an :class:`~ddl25spring_tpu.analysis.
engine.HloLintContext` (the structured facts ``obs.xla_analytics``
extracts from one compiled program: collective op sites, per-computation
def tables, the input-output alias table, entry parameters, and the
strategy's declared signature) to zero or more :class:`Finding` records.
Rules never raise on weird HLO — a fact they cannot establish is a
finding they do not emit (the engine's job is judgment on evidence, not
speculation).

The initial pack covers the failure classes the PR-2/PR-3 analytics can
*measure* but not *judge*:

========  ========  ====================================================
rule      severity  hazard
========  ========  ====================================================
H001      warn      sync collective above a byte threshold with no async
                    start/done pair — compute/comms overlap left on the
                    table
H002      warn      inverse-collective pairs: an all-gather feeding a
                    reduce-scatter, or a gather whose result is
                    immediately dynamic-sliced — redundant resharding
H003      warn      collective inside a while loop with unknown trip
                    count (comms bill unaccountable), or whose operand
                    is loop-invariant (hoistable out of the loop)
H004      warn      f32 collective fed by a narrow->wide ``convert`` —
                    2x the wire bytes the payload needs
H005      error     donation miss: a donatable params/opt-state input
                    buffer above the byte threshold absent from the
                    input-output alias table
H006      error     host round-trip (callback custom-call / infeed /
                    outfeed) inside the compiled step while DDL25_OBS
                    is off — instrumentation leaked into the hot path
H007      error     collective-permute whose source-target pairs repeat
                    a TARGET (two sources into one receive buffer — the
                    deadlock-shaped mismatched cycle; duplicate sources
                    are legal multicast), or a collective grouping over
                    mesh axes the strategy's ``describe()`` signature
                    never declared (axis leak)
H008      warn      zero/near-zero-slack overlap window: an async
                    start/done pair with (provably) nothing schedulable
                    between start and done, or an overlap-declared
                    strategy's collective with no dataflow-independent
                    work — the overlap is cosmetic
                    (:mod:`ddl25spring_tpu.analysis.sched`)
H009      error     mismatched or reordered collective sequence across
                    participants: duplicate device in one replica
                    group, one channel_id shared by sites with
                    different groups, participants beyond the compiled
                    device range, conditional branches issuing
                    divergent collective sequences, crossed async
                    windows over unequal overlapping groups — the
                    static deadlock shapes H007's shape-local check
                    cannot see
H011      error     implicit reshard: a non-scalar collective kind in
                    the compiled HLO that the strategy's ``describe()``
                    signature neither declares nor forbids — XLA's
                    partitioner inserted traffic the author never
                    declared (:mod:`ddl25spring_tpu.analysis.
                    shard_flow`)
H012      error/    rule-coverage defect in a partition-rule table
          warn      (:mod:`ddl25spring_tpu.parallel.rules`): a param
                    leaf no rule matches (error), a leaf matched by
                    two rules (warn: order silently load-bearing), or
                    a rule shadowed so it can never fire (warn)
H013      error     cross-program layout mismatch: a ZeRO-family
                    step's saved param/opt-state sharding off
                    ``ft/reshard``'s ``[n, k]``/``[L, n, k]``
                    checkpoint contract, or serve prefill/decode
                    disagreeing on the paged-KV pool split.  The
                    per-program half runs in the pack; the
                    program-pair half emits from :func:`ddl25spring_
                    tpu.analysis.shard_flow.check_layout_contracts`
                    (``graft_lint --shard-flow``)
========  ========  ====================================================

Source-level (AST) rules S101-S103 live in
:mod:`ddl25spring_tpu.analysis.source_lint`; both families share the
:class:`Finding` record and the waiver workflow
(:mod:`ddl25spring_tpu.analysis.waivers`).
"""

from __future__ import annotations

from dataclasses import asdict, dataclass
from typing import Any, Callable, Iterable

from ddl25spring_tpu.utils.metrics import fmt_bytes as _fmt_bytes

SEVERITIES = ("info", "warn", "error")


def severity_rank(sev: str | None) -> int:
    """info < warn < error; unknown severities sort below info."""
    try:
        return SEVERITIES.index(sev)
    except ValueError:
        return -1


def worst_severity(sevs: Iterable[str]) -> str | None:
    """The highest-ranked severity in ``sevs`` (None when empty)."""
    best: str | None = None
    for s in sevs:
        if best is None or severity_rank(s) > severity_rank(best):
            best = s
    return best


@dataclass
class Finding:
    """One hazard the analyzer established, HLO- or source-level.

    ``op`` anchors the finding: the HLO op name (``all-reduce.3``), the
    entry-parameter arg path (``params['w1']``), or the Python symbol
    (``make_dp_train_step.step``).  ``bytes`` is the payload the hazard
    taxes, when byte-denominated.  ``source`` is a ``file:line`` when
    the HLO metadata or the AST carries one.  ``fix_hint`` is the one
    sentence a reader needs to start fixing.  Waiver resolution
    (:mod:`ddl25spring_tpu.analysis.waivers`) sets ``waived`` +
    ``waived_reason`` instead of dropping the record — a waived finding
    stays visible in reports and stops gating CI.
    """

    rule: str
    severity: str
    message: str
    strategy: str | None = None
    op: str | None = None
    bytes: int | None = None
    fix_hint: str = ""
    source: str | None = None
    waived: bool = False
    waived_reason: str | None = None

    def to_dict(self) -> dict[str, Any]:
        return asdict(self)

    def key(self) -> str:
        """Stable-ish identity used in waiver bookkeeping and dedup."""
        return f"{self.rule}:{self.strategy or '-'}:{self.op or self.source or '-'}"


# ------------------------------------------------------------ rule registry

# rule id -> (function, default params).  Functions take (ctx) and read
# their thresholds from ctx.thresholds (engine merges DEFAULT_THRESHOLDS
# with caller overrides).
HLO_RULES: dict[str, Callable] = {}

DEFAULT_THRESHOLDS = {
    # H001: a sync collective below this payload isn't worth async-ifying
    "h001_sync_bytes": 1024 * 1024,
    # H005: donatable input buffers above this must alias
    "h005_donation_bytes": 64 * 1024,
    # payloads at or below this are scalar bookkeeping (loss pmeans),
    # exempt from H001/H007-axis checks — mirrors check_signature's
    # `scalar_bytes`
    "scalar_bytes": 64,
    # H008: an overlap window whose compute time covers less than this
    # percentage of the transfer's wire time (reference-chip model) is
    # cosmetic — the window exists but hides nothing
    "h008_min_slack_pct": 1,
}


def hlo_rule(rule_id: str):
    def deco(fn):
        HLO_RULES[rule_id] = fn
        fn.rule_id = rule_id
        return fn

    return deco


# ----------------------------------------------------------------- helpers

# ops that only move/reinterpret bytes: walking through them preserves
# "what data is on the wire" for the producer-chain rules
_PASS_THROUGH = {"reshape", "bitcast", "copy", "transpose"}

_INVERSE = {
    "all-gather": "reduce-scatter",
    "reduce-scatter": "all-gather",
}


def resolve_producer(ctx, comp: str, name: str, depth: int = 12):
    """Walk a value back through pure data movement to the op that made
    its bytes.  Follows :data:`_PASS_THROUGH` single-operand ops, dives
    through ``fusion`` ops to the fused computation's ROOT (the fused
    value's real producer), and climbs back OUT of a fused computation
    when the chain reaches its ``parameter(k)`` (to the caller's k-th
    operand, via ``ctx.fusion_callers``).  Returns the producing def
    dict (with ``"computation"`` added) or None when the chain leaves
    the parsed program (entry parameters, constants, multi-operand
    math)."""
    for _ in range(depth):
        d = ctx.defs.get(comp, {}).get(name)
        if d is None:
            return None
        opcode = d["opcode"]
        if opcode == "fusion":
            called = ctx.called_computation(d)
            root = ctx.root_of(called) if called else None
            if root is None:
                return dict(d, computation=comp)
            comp, name = called, root
            continue
        if opcode == "parameter":
            caller = ctx.fusion_callers.get(comp)
            idx = ctx.param_index(d)
            if caller and idx is not None and idx < len(caller[1]["operands"]):
                comp, name = caller[0], caller[1]["operands"][idx]
                continue
            return dict(d, computation=comp)
        if opcode in _PASS_THROUGH and d["operands"]:
            name = d["operands"][0]
            continue
        return dict(d, computation=comp)
    return None


def _result_dtype(type_str: str) -> str | None:
    import re

    m = re.search(r"\b([a-z]\w*)\[", type_str)
    return m.group(1) if m else None


# -------------------------------------------------------------- HLO rules


@hlo_rule("H001")
def rule_sync_collective_no_overlap(ctx) -> list[Finding]:
    """Big collective issued synchronously: no ``-start``/``-done`` pair
    means XLA serializes it against compute instead of overlapping."""
    thr = ctx.thresholds["h001_sync_bytes"]
    out = []
    for op in ctx.ops:
        # judge the per-execution WIRE traffic, not the result shape — a
        # reduce-scatter's result is payload/n while (n-1) payloads
        # cross the wire, and it is the wire time that wants overlap
        moved = max(op["result_bytes"], op.get("wire_bytes") or 0)
        if op.get("async") or moved < thr:
            continue
        out.append(Finding(
            rule="H001", severity="warn", strategy=ctx.strategy,
            op=op.get("name"), bytes=moved,
            source=op.get("source"),
            message=(
                f"sync {op['kind']} moving ~{_fmt_bytes(moved)} on the "
                "wire with no async start/done pair — the transfer "
                "serializes against compute"
            ),
            fix_hint=(
                "let XLA async-ify it (--xla_tpu_enable_async_collective_"
                "fusion) or restructure so the collective overlaps the "
                "next layer's compute (cf. the zero3-prefetch double "
                "buffer)"
            ),
        ))
    return out


@hlo_rule("H002")
def rule_inverse_collective_pair(ctx) -> list[Finding]:
    """All-gather feeding reduce-scatter (or vice versa) moves the same
    bytes twice; all-gather feeding dynamic-slice gathers everything to
    keep a slice.  Both are resharding that a sharding tweak removes."""
    out = []
    for op in ctx.ops:
        inv = _INVERSE.get(op["kind"])
        if inv is None:
            continue
        for operand in op.get("operands") or ():
            prod = resolve_producer(ctx, op["computation"], operand)
            if prod and prod["opcode"] == inv:
                out.append(Finding(
                    rule="H002", severity="warn", strategy=ctx.strategy,
                    op=op.get("name"), bytes=op["result_bytes"],
                    source=op.get("source"),
                    message=(
                        f"{inv} output feeds straight into this "
                        f"{op['kind']} — the bytes cross the wire twice "
                        "to end up resharded"
                    ),
                    fix_hint=(
                        "produce the value in the target sharding (or "
                        "fuse the pair into one collective-permute / "
                        "all-to-all)"
                    ),
                ))
    # gather-then-slice: every dynamic-slice whose data operand resolves
    # to an all-gather
    for comp, defs in ctx.defs.items():
        if not ctx.reachable(comp):
            continue
        for name, d in defs.items():
            if d["opcode"] != "dynamic-slice" or not d["operands"]:
                continue
            prod = resolve_producer(ctx, comp, d["operands"][0])
            if prod and prod["opcode"] == "all-gather":
                out.append(Finding(
                    rule="H002", severity="warn", strategy=ctx.strategy,
                    op=name,
                    message=(
                        "all-gather result is immediately dynamic-sliced "
                        "— gathered the full buffer to keep a shard"
                    ),
                    fix_hint=(
                        "gather only the needed shard (collective-permute"
                        " or a smaller all-gather group)"
                    ),
                ))
    return out


@hlo_rule("H003")
def rule_collective_in_opaque_or_hoistable_loop(ctx) -> list[Finding]:
    """A collective inside a while XLA cannot bound makes the comms bill
    unaccountable (and unpinnable); one whose operand never changes
    across iterations is paying the loop trip count for nothing."""
    out = []
    for op in ctx.ops:
        if not op["trip_known"]:
            out.append(Finding(
                rule="H003", severity="warn", strategy=ctx.strategy,
                op=op.get("name"), bytes=op["result_bytes"],
                source=op.get("source"),
                message=(
                    f"{op['kind']} inside a while loop with unknown trip "
                    "count — per-step collective bytes cannot be "
                    "accounted or pinned"
                ),
                fix_hint=(
                    "bound the loop (lax.scan / fori_loop with a static "
                    "trip count) so XLA annotates known_trip_count"
                ),
            ))
            continue
        invariant = ctx.invariant_gtes.get(op["computation"])
        if not invariant:
            continue
        for operand in op.get("operands") or ():
            prod = resolve_producer(ctx, op["computation"], operand)
            if (
                prod
                and prod["opcode"] == "get-tuple-element"
                and ctx.is_param_gte(prod["computation"], prod)
                and ctx.gte_index(prod) in invariant
            ):
                out.append(Finding(
                    rule="H003", severity="warn", strategy=ctx.strategy,
                    op=op.get("name"), bytes=op["result_bytes"],
                    source=op.get("source"),
                    message=(
                        f"{op['kind']} executes {op['count']}x inside a "
                        "loop but its operand is loop-invariant — the "
                        "same bytes cross the wire every iteration"
                    ),
                    fix_hint="hoist the collective above the loop",
                ))
    return out


@hlo_rule("H004")
def rule_upcast_before_collective(ctx) -> list[Finding]:
    """Converting bf16 (or other narrow dtype) up to f32 right before a
    collective doubles the wire bytes for no numeric gain the reduce
    itself needs."""
    from ddl25spring_tpu.obs.xla_analytics import _DTYPE_BYTES

    out = []
    for op in ctx.ops:
        res_dt = _result_dtype(ctx.op_type(op))
        res_w = _DTYPE_BYTES.get(res_dt or "")
        if not res_w:
            continue
        for operand in op.get("operands") or ():
            prod = resolve_producer(ctx, op["computation"], operand)
            if not prod or prod["opcode"] != "convert":
                continue
            # the convert line carries its operand's type inline:
            # %c = f32[..] convert(bf16[..] %x)
            src_dt = _result_dtype(
                prod["line"].split("convert(", 1)[-1]
            )
            src_w = _DTYPE_BYTES.get(src_dt or "")
            if src_w and src_w < res_w:
                out.append(Finding(
                    rule="H004", severity="warn", strategy=ctx.strategy,
                    op=op.get("name"), bytes=op["result_bytes"],
                    source=op.get("source"),
                    message=(
                        f"{op['kind']} carries {res_dt} on the wire but "
                        f"its payload was just converted up from "
                        f"{src_dt} — {res_w // src_w}x the bytes the "
                        "data holds"
                    ),
                    fix_hint=(
                        f"run the collective in {src_dt} and convert "
                        "after (or reduce in mixed precision via "
                        "lax.psum dtype control)"
                    ),
                ))
    return out


@hlo_rule("H005")
def rule_donation_miss(ctx) -> list[Finding]:
    """A big params/opt-state input absent from the alias table double-
    resides in HBM for the whole step — the exact regression PR 3's
    universal donation removed."""
    report = ctx.report or {}
    donation = report.get("donation") or {}
    donatable = donation.get("donatable_leaves")
    if not donatable:
        return []  # not a train step (or unknown layout): no claim
    aliased = set(
        donation["aliased_params"]
        if "aliased_params" in donation
        else (a["param_number"] for a in ctx.aliases)
    )
    thr = ctx.thresholds["h005_donation_bytes"]
    out = []
    for p in ctx.entry_params:
        if p["number"] >= donatable or p["number"] in aliased:
            continue
        if p["bytes"] < thr:
            continue
        out.append(Finding(
            rule="H005", severity="error", strategy=ctx.strategy,
            op=p.get("arg") or p["name"], bytes=p["bytes"],
            message=(
                f"donatable input #{p['number']} "
                f"({p.get('arg') or p['name']}, {_fmt_bytes(p['bytes'])}) "
                "is not in the input-output alias table — it double-"
                "resides in HBM for the whole step"
            ),
            fix_hint=(
                "compile the step with donate_argnums=(0, 1) (the "
                "builders' default; check the caller didn't pass "
                "donate=False) and keep the output structure aliasable"
            ),
        ))
    return out


@hlo_rule("H006")
def rule_host_roundtrip_in_step(ctx) -> list[Finding]:
    """Host callbacks / infeed / outfeed inside the compiled step when
    observability is OFF: each one stalls the step on a host sync that
    nobody asked for."""
    if ctx.obs_enabled:
        return []  # instrumentation was requested; the cost is the deal
    import re

    out = []
    for comp, defs in ctx.defs.items():
        if not ctx.reachable(comp):
            continue
        for name, d in defs.items():
            opcode = d["opcode"]
            hazard = None
            if opcode in ("infeed", "outfeed"):
                hazard = opcode
            elif opcode == "custom-call":
                m = re.search(r'custom_call_target="([^"]+)"', d["line"])
                target = m.group(1) if m else ""
                if "callback" in target or "host" in target.lower():
                    hazard = f"custom-call {target}"
            if hazard is None:
                continue
            out.append(Finding(
                rule="H006", severity="error", strategy=ctx.strategy,
                op=name,
                message=(
                    f"host round-trip ({hazard}) compiled into the step "
                    "while DDL25_OBS is off — every execution stalls on "
                    "the host"
                ),
                fix_hint=(
                    "gate the jax.debug.callback / io_callback behind "
                    "obs.enabled() at trace time (see parallel/dp.py's "
                    "instrument flag)"
                ),
            ))
    return out


@hlo_rule("H007")
def rule_permute_cycle_and_axis_leak(ctx) -> list[Finding]:
    """Deadlock-shaped permutes and collectives leaking onto mesh axes
    the strategy never declared."""
    out = []
    for op in ctx.ops:
        pairs = op.get("pairs")
        if op["kind"] == "collective-permute" and pairs:
            # duplicate SOURCES are legal (one-to-many multicast);
            # duplicate TARGETS are undefined in XLA — two devices
            # writing one receive buffer, the mismatched-cycle shape
            # that deadlocks/corrupts the ring on hardware
            targets = [t for _, t in pairs]
            if len(targets) != len(set(targets)):
                out.append(Finding(
                    rule="H007", severity="error", strategy=ctx.strategy,
                    op=op.get("name"), bytes=op["result_bytes"],
                    source=op.get("source"),
                    message=(
                        "collective-permute repeats a target device in "
                        f"its source-target pairs ({pairs}) — two "
                        "sources write one receive buffer, a mismatched "
                        "cycle that deadlocks the ring on hardware"
                    ),
                    fix_hint=(
                        "make the receive side a function: each device "
                        "at most once as target (sources may multicast)"
                    ),
                ))
    declared = ctx.declared_axes
    if declared:
        scalar = ctx.thresholds["scalar_bytes"]
        for op in ctx.ops:
            if op["result_bytes"] <= scalar or not op.get("axes"):
                continue
            leak = set(op["axes"]) - declared
            if leak:
                out.append(Finding(
                    rule="H007", severity="error", strategy=ctx.strategy,
                    op=op.get("name"), bytes=op["result_bytes"],
                    source=op.get("source"),
                    message=(
                        f"{op['kind']} groups over mesh axes "
                        f"{sorted(leak)} that the strategy's describe() "
                        "signature never declares — an axis leak "
                        "(cross-replica traffic the accounting misses)"
                    ),
                    fix_hint=(
                        "either the sharding is wrong (fix the specs) or "
                        "the signature is stale (declare the axis in "
                        "describe())"
                    ),
                ))
    return out


@hlo_rule("H008")
def rule_zero_slack_overlap_window(ctx) -> list[Finding]:
    """An overlap claim with nothing inside the window: an async
    start/done pair issued back-to-back, or an overlap-declared
    strategy's collective whose dataflow window holds no independent
    work.  The transfer serializes exactly as if it were sync — the
    overlap is cosmetic (the shape H001's has-a-pair test passes
    trivially)."""
    sched = getattr(ctx, "sched", None)
    if not sched:
        return []
    thr = ctx.thresholds["h001_sync_bytes"]
    min_pct = ctx.thresholds.get("h008_min_slack_pct", 1)
    out = []
    for rec in sched.get("slack") or []:
        if rec["window"] not in ("pair", "dataflow"):
            continue  # a sync schedule window is H001's department
        moved = max(rec["result_bytes"], rec.get("wire_bytes") or 0)
        if moved < thr:
            continue
        t_wire = rec.get("t_wire_s") or 0.0
        t_slack = rec.get("t_slack_s") or 0.0
        if t_wire > 0 and t_slack >= t_wire * (min_pct / 100.0):
            continue
        how = (
            "the start/done pair closes immediately"
            if rec["window"] == "pair"
            else "no dataflow-independent work exists to fill it"
        )
        out.append(Finding(
            rule="H008", severity="warn", strategy=ctx.strategy,
            op=rec.get("op"), bytes=moved,
            message=(
                f"{rec['kind']} claims overlap but its window is "
                f"empty ({how}): slack covers "
                f"{0.0 if t_wire <= 0 else 100.0 * t_slack / t_wire:.2f}%"
                f" of the transfer on {sched.get('ref_chip', '?')} — "
                "the overlap is cosmetic"
            ),
            fix_hint=(
                "move independent compute into the window (issue the "
                "collective earlier / consume its result later), or "
                "drop the async/overlap claim so H001 judges it as the "
                "sync transfer it is"
            ),
        ))
    return out


@hlo_rule("H009")
def rule_participant_stream_mismatch(ctx) -> list[Finding]:
    """Mismatched or reordered collective sequences across participants
    — the static deadlock proof.  The evidence comes from the
    per-participant stream expansion in :mod:`ddl25spring_tpu.analysis.
    sched` (``check_schedule_safety``); each hazard record is one
    provable rendezvous that can never complete."""
    sched = getattr(ctx, "sched", None)
    if not sched:
        return []
    out = []
    for hz in sched.get("hazards") or []:
        out.append(Finding(
            rule="H009", severity="error", strategy=ctx.strategy,
            op=hz.get("op"),
            message=f"[{hz['check']}] {hz['message']}",
            fix_hint=(
                "make every participant issue the same collective "
                "sequence with the same groups (check the sharding "
                "specs and any device-varying control flow feeding "
                "this op)"
            ),
        ))
    return out


@hlo_rule("H011")
def rule_implicit_reshard(ctx) -> list[Finding]:
    """A collective kind present in the compiled program but absent
    from the strategy's declared signature — neither pinned with bounds
    nor listed forbidden.  The signature gate cannot see it (it only
    judges what the author wrote down); this rule closes that hole, so
    a partitioner-inserted reshard can never ride along unaccounted.
    One finding per undeclared kind (the example site named), scalar
    bookkeeping exempt."""
    from ddl25spring_tpu.obs.xla_analytics import _COLLECTIVE_KINDS

    expected = (ctx.report or {}).get("expected")
    if not expected:
        return []  # no declared signature: no claim to hold the HLO to
    declared = {k for k in expected if k in _COLLECTIVE_KINDS}
    declared |= set(expected.get("forbidden") or ())
    scalar = int(
        expected.get("scalar_bytes", ctx.thresholds.get("scalar_bytes", 0))
    )
    per_kind: dict[str, list[dict]] = {}
    for op in ctx.ops:
        if op["kind"] in declared or op["result_bytes"] <= scalar:
            continue
        per_kind.setdefault(op["kind"], []).append(op)
    out = []
    for kind in sorted(per_kind):
        ops = per_kind[kind]
        total = sum(o["result_bytes"] * o["count"] for o in ops)
        out.append(Finding(
            rule="H011", severity="error", strategy=ctx.strategy,
            op=ops[0].get("name"), bytes=total,
            source=ops[0].get("source"),
            message=(
                f"implicit reshard: {len(ops)} {kind} site(s) moving "
                f"{_fmt_bytes(total)} total that the describe() "
                "signature neither declares nor forbids — XLA inserted "
                "traffic the author never declared"
            ),
            fix_hint=(
                "either the sharding flow is wrong (fix the specs so "
                "the reshard disappears) or the signature is incomplete "
                f"(declare {kind} with bounds/axes, or forbid it, in "
                "describe())"
            ),
        ))
    return out


@hlo_rule("H012")
def rule_partition_coverage(ctx) -> list[Finding]:
    """The coverage proof for rule-table strategies: every param leaf
    matched exactly once, every rule reachable.  Judged from the
    serialized table + leaf paths the describe() meta carries — the
    evidence survives JSON round-trips, so the proof re-runs on any
    stored report."""
    meta = ((ctx.report or {}).get("meta")) or {}
    table = meta.get("rule_table")
    if not table:
        return []  # not a rule-table strategy: no table to prove
    from ddl25spring_tpu.analysis.shard_flow import coverage_defects

    paths = meta.get("param_paths") or []
    out = []
    for d in coverage_defects(table, paths):
        severe = d["defect"] in ("unmatched", "bad-table")
        out.append(Finding(
            rule="H012",
            severity="error" if severe else "warn",
            strategy=ctx.strategy,
            op=d.get("path") or d.get("pattern"),
            message=(
                f"rule-coverage defect [{d['defect']}] in table "
                f"{table.get('name', '?')!r}: {d['detail']}"
            ),
            fix_hint=(
                "edit the table until every leaf matches exactly one "
                "rule and every rule fires (parallel/rules.py; "
                "rule_coverage() shows the full match matrix)"
            ),
        ))
    return out


@hlo_rule("H013")
def rule_saved_layout_contract(ctx) -> list[Finding]:
    """The per-program half of the cross-program layout contract: a
    ZeRO-family step's saved state must shard exactly as ``ft/reshard``
    re-lands it (rank-2 ``[n, k]`` on dim 0, rank-3 ``[L, n, k]`` on
    dim 1, row count == the shard axis) — walked off the compiled
    program's own entry-parameter shardings, so the pin can never
    drift from what XLA actually laid out."""
    if not ctx.report:
        return []
    from ddl25spring_tpu.analysis.shard_flow import saved_layout_findings

    report = dict(ctx.report)
    report.setdefault("strategy", ctx.strategy)
    report.setdefault("entry_params", ctx.entry_params)
    return saved_layout_findings(report)


def h013_finding(
    strategy: str | None,
    op: str | None,
    message: str,
    bytes: int | None = None,
) -> Finding:
    """One H013 cross-program layout-mismatch finding — the constructor
    lives here so the rule pack owns every severity/message, while the
    emission points are the pack's per-program walk above and
    :func:`ddl25spring_tpu.analysis.shard_flow.check_layout_contracts`
    (the only place several compiled programs are in hand)."""
    return Finding(
        rule="H013", severity="error", strategy=strategy, op=op,
        bytes=bytes, message=message,
        fix_hint=(
            "make the layouts agree: fix the sharding specs (or the "
            "save layout in ft/reshard's contract / the serve pool "
            "specs) so every program in the round-trip sees the same "
            "split"
        ),
    )
