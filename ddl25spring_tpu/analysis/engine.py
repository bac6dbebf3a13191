"""The hazard-rule engine: structured HLO facts in, Findings out.

The engine owns no hazard knowledge itself — it builds one
:class:`HloLintContext` from a compiled program (via the parsers
``obs.xla_analytics`` exposes: collective op sites, per-computation def
tables, the input-output alias table, entry parameters) plus the
strategy's analytics report, runs every registered rule from
:mod:`ddl25spring_tpu.analysis.rules` over it, and resolves waivers
(:mod:`ddl25spring_tpu.analysis.waivers`).  Three entry points:

- :func:`lint_hlo_text` — raw optimized-HLO text (what the synthetic
  per-rule tests feed);
- :func:`lint_compiled` — a jax ``Compiled`` (what
  ``xla_analytics.compile_strategy`` calls for every strategy report);
- :func:`lint_strategy` — compile + analyze + lint one registered
  strategy by name (what ``tools/graft_lint.py`` drives).

Findings are never dropped by waivers — they come back marked
``waived`` with the waiver's reason, so reports stay complete while CI
gates only on the unwaived set (:func:`summarize`).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Any

from ddl25spring_tpu.analysis import waivers as waivers_mod
from ddl25spring_tpu.analysis.rules import (
    DEFAULT_THRESHOLDS,
    HLO_RULES,
    Finding,
    worst_severity,
)

_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
# the trailing `, index=N` attribute of a get-tuple-element — long tuple
# types embed `/*index=5*/` position comments that a bare `index=(\d+)`
# would match first, so comments are stripped before searching
_GTE_INDEX_RE = re.compile(r",\s*index=(\d+)")
_COMMENT_RE = re.compile(r"/\*.*?\*/")


def _gte_index_of_line(line: str) -> int | None:
    m = _GTE_INDEX_RE.search(_COMMENT_RE.sub("", line))
    return int(m.group(1)) if m else None


@dataclass
class HloLintContext:
    """Everything a hazard rule may interrogate about one program."""

    ops: list[dict[str, Any]]
    defs: dict[str, dict[str, dict[str, Any]]]
    multipliers: dict[str, int]
    entry_params: list[dict[str, Any]] = field(default_factory=list)
    aliases: list[dict[str, Any]] = field(default_factory=list)
    report: dict[str, Any] | None = None
    strategy: str | None = None
    obs_enabled: bool = False
    thresholds: dict[str, int] = field(default_factory=dict)
    # while-body computation -> tuple indices that pass through the loop
    # unchanged (carry element i is returned as exactly gte(param, i))
    invariant_gtes: dict[str, set[int]] = field(default_factory=dict)
    # multiplier>0 computations plus everything they reference via
    # `calls=` (fusion bodies, reducers) — the multiplier walk follows
    # control-flow callees only, so without the closure every fused
    # dynamic-slice/custom-call would look dead to the def-table rules
    reachable_comps: set[str] = field(default_factory=set)
    # fused computation -> (caller computation, the fusion op's def):
    # lets producer walks map a fused parameter(k) back to the caller's
    # k-th operand (fusion bodies have exactly one call site)
    fusion_callers: dict[str, tuple[str, dict]] = field(
        default_factory=dict
    )
    # whole-program schedule report (analysis/sched.py): per-collective
    # overlap-slack windows + participant-stream safety hazards — what
    # H008/H009 judge.  None when the sched pass failed (its breakage
    # must never cost the other rules)
    sched: dict[str, Any] | None = None

    # -------------------------------------------------- rule conveniences

    def reachable(self, comp: str) -> bool:
        return comp in self.reachable_comps

    def called_computation(self, d: dict[str, Any]) -> str | None:
        m = _CALLS_RE.search(d["line"])
        return m.group(1) if m else None

    def root_of(self, comp: str) -> str | None:
        for name, d in self.defs.get(comp, {}).items():
            if d["root"]:
                return name
        return None

    def gte_index(self, d: dict[str, Any]) -> int | None:
        return _gte_index_of_line(d["line"])

    def param_index(self, d: dict[str, Any]) -> int | None:
        m = re.search(r"parameter\((\d+)\)", d["line"])
        return int(m.group(1)) if m else None

    def is_param_gte(self, comp: str, d: dict[str, Any]) -> bool:
        """Is ``d`` a get-tuple-element reading straight off ``comp``'s
        parameter (the while carry), not some inner op's tuple result?"""
        if d.get("opcode") != "get-tuple-element" or not d["operands"]:
            return False
        pd = self.defs.get(comp, {}).get(d["operands"][0])
        return bool(pd) and pd["opcode"] == "parameter"

    def op_type(self, op: dict[str, Any]) -> str:
        """Result-type string of a collective op-site record."""
        d = self.defs.get(op.get("computation", ""), {}).get(
            op.get("name", "")
        )
        return d["type"] if d else ""

    @property
    def declared_axes(self) -> set[str]:
        """Union of mesh axes the strategy's signature declares traffic
        on (empty = signature declares no axes, axis-leak checks skip)."""
        expected = (self.report or {}).get("expected") or {}
        axes: set[str] = set()
        for want in expected.values():
            if isinstance(want, dict) and "axes" in want:
                axes.update(want["axes"])
        return axes


def _invariant_gtes(
    defs: dict[str, dict[str, dict[str, Any]]],
) -> dict[str, set[int]]:
    """For each computation shaped like a while body (parameter(0) ->
    ROOT tuple), the carry indices returned untouched: ROOT tuple
    operand ``i`` is exactly ``get-tuple-element(param, i)``."""
    out: dict[str, set[int]] = {}
    for comp, dd in defs.items():
        root_name = next((n for n, d in dd.items() if d["root"]), None)
        if root_name is None or dd[root_name]["opcode"] != "tuple":
            continue
        inv: set[int] = set()
        for pos, operand in enumerate(dd[root_name]["operands"]):
            od = dd.get(operand)
            if od is None or od["opcode"] != "get-tuple-element":
                continue
            src = dd.get(od["operands"][0]) if od["operands"] else None
            if src is None or src["opcode"] != "parameter":
                continue  # reads an inner op's tuple, not the carry
            if _gte_index_of_line(od["line"]) == pos:
                inv.add(pos)
        if inv:
            out[comp] = inv
    return out


def build_context(
    hlo_text: str,
    mesh=None,
    report: dict[str, Any] | None = None,
    strategy: str | None = None,
    obs_enabled: bool | None = None,
    thresholds: dict[str, int] | None = None,
) -> HloLintContext:
    from ddl25spring_tpu.obs import xla_analytics as xa

    if obs_enabled is None:
        from ddl25spring_tpu import obs

        obs_enabled = obs.enabled()
    comps, entry = xa._split_computations(hlo_text)
    mult, _known = xa._execution_multipliers(comps, entry)
    defs = xa.parse_op_defs(hlo_text)
    reachable = {c for c, m in mult.items() if m > 0}
    fusion_callers: dict[str, tuple[str, dict]] = {}
    frontier = list(reachable)
    while frontier:
        comp = frontier.pop()
        for d in defs.get(comp, {}).values():
            m = _CALLS_RE.search(d["line"])
            if not m:
                continue
            if d["opcode"] == "fusion":
                fusion_callers.setdefault(m.group(1), (comp, d))
            if m.group(1) not in reachable:
                reachable.add(m.group(1))
                frontier.append(m.group(1))
    ops = (
        report["collectives"]["ops"]
        if report and "collectives" in report
        else xa.parse_hlo_collectives(hlo_text, mesh)
    )
    entry_params = (
        report.get("entry_params")
        if report and report.get("entry_params") is not None
        else xa.parse_entry_parameters(hlo_text)
    )
    merged_thresholds = {**DEFAULT_THRESHOLDS, **(thresholds or {})}
    # the schedule report: reuse the one analyze_compiled already built
    # for this report (one DAG pass per compile), else build it here
    # (synthetic-HLO lints); a sched failure degrades to None so the
    # H001-H007 pass never pays for it
    sched_report = (report or {}).get("sched")
    if sched_report is None:
        try:
            from ddl25spring_tpu.analysis import sched as sched_mod

            sched_report = sched_mod.analyze_schedule(
                hlo_text,
                mesh,
                ops=ops,
                discipline=sched_mod.discipline_of((report or {}).get("meta")),
                scalar_bytes=merged_thresholds["scalar_bytes"],
            )
        except Exception:  # noqa: BLE001 — degrade, keep the lint pass
            sched_report = None
    return HloLintContext(
        ops=ops,
        defs=defs,
        multipliers=mult,
        entry_params=entry_params or [],
        aliases=xa.parse_input_output_aliases(hlo_text),
        report=report,
        strategy=strategy,
        obs_enabled=bool(obs_enabled),
        thresholds=merged_thresholds,
        invariant_gtes=_invariant_gtes(defs),
        reachable_comps=reachable,
        fusion_callers=fusion_callers,
        sched=sched_report,
    )


def run_rules(
    ctx: HloLintContext, rules: dict | None = None
) -> list[Finding]:
    """Every registered rule over one context, rule-id order; a rule
    that crashes on odd HLO yields a single info finding naming itself
    rather than killing the pass."""
    out: list[Finding] = []
    for rule_id in sorted((rules or HLO_RULES)):
        fn = (rules or HLO_RULES)[rule_id]
        try:
            out.extend(fn(ctx))
        except Exception as e:  # noqa: BLE001 — a broken rule is a finding
            out.append(Finding(
                rule=rule_id, severity="info", strategy=ctx.strategy,
                message=f"rule crashed on this program: "
                        f"{type(e).__name__}: {e}",
                fix_hint="fix the rule in analysis/rules.py",
            ))
    return out


def lint_hlo_text(
    hlo_text: str,
    mesh=None,
    report: dict[str, Any] | None = None,
    strategy: str | None = None,
    obs_enabled: bool | None = None,
    thresholds: dict[str, int] | None = None,
    waivers: list | None = None,
) -> list[Finding]:
    """Run the full HLO rule pack over optimized-HLO text."""
    ctx = build_context(
        hlo_text, mesh, report, strategy, obs_enabled, thresholds
    )
    findings = run_rules(ctx)
    return waivers_mod.apply_waivers(
        findings,
        waivers_mod.load_waivers() if waivers is None else waivers,
    )


def lint_compiled(
    compiled: Any,
    report: dict[str, Any] | None = None,
    strategy: str | None = None,
    **kw: Any,
) -> list[Finding]:
    """Lint a jax ``Compiled`` train step (mesh/axes come through the
    ``report`` produced by ``xla_analytics.analyze_compiled``)."""
    return lint_hlo_text(
        compiled.as_text(), report=report, strategy=strategy, **kw
    )


def lint_strategy(
    name: str,
    mesh_sizes: tuple[int, ...] | None = None,
    **overrides: Any,
) -> dict[str, Any]:
    """Compile + analyze + lint one registered strategy.  Returns the
    full ``compile_strategy`` report (findings under ``"findings"``, or
    ``"error"`` when the strategy cannot compile on this jax)."""
    from ddl25spring_tpu.obs import xla_analytics as xa

    return xa.compile_strategy(name, mesh_sizes, lint=True, **overrides)


def summarize(findings: list[Finding | dict]) -> dict[str, Any]:
    """Counts the CI gate and the bench telemetry key off: total /
    unwaived / waived, worst unwaived severity, and per-rule tallies."""
    dicts = [
        f.to_dict() if isinstance(f, Finding) else f for f in findings
    ]
    unwaived = [f for f in dicts if not f.get("waived")]
    by_rule: dict[str, int] = {}
    for f in dicts:
        by_rule[f["rule"]] = by_rule.get(f["rule"], 0) + 1
    return {
        "findings": len(dicts),
        "unwaived": len(unwaived),
        "waived": len(dicts) - len(unwaived),
        "worst": worst_severity(f["severity"] for f in unwaived),
        "by_rule": by_rule,
    }
