"""graft-shard: the static sharding-flow verifier.

PRs 4 and 9 built compile-time judgment for collectives (graft-lint)
and schedules (graft-sched); this module is the third leg — *sharding
flow*.  It reads the layout facts ``obs.xla_analytics`` already parses
out of optimized HLO (entry-parameter ``sharding=`` annotations, the
per-computation def tables, the collective op sites) and proves three
things a rule-table strategy engine (:mod:`ddl25spring_tpu.parallel.
rules`) needs before strategies can safely become data:

- **H011 — implicit reshard**: every non-scalar collective kind in the
  compiled program must appear in the strategy's ``describe()``
  signature (declared with bounds, or explicitly forbidden — the
  signature gate's department).  A kind that is neither is traffic XLA's
  partitioner inserted that the author never declared: the silent
  reshard that turns a layout typo into an un-accounted wire bill
  (found live on ``tp``/``sp`` when this rule first ran — see their
  describes).
- **H012 — rule-coverage defect**: for a strategy whose meta carries a
  partition-rule table, every param leaf must match exactly one rule
  and every rule must fire for at least one leaf.  Unmatched leaf,
  doubly-matched leaf, and shadowed/dead rule are each reported — the
  coverage proof that makes "strategy as data" safe
  (:func:`ddl25spring_tpu.parallel.rules.rule_coverage` supplies the
  evidence; the table round-trips through describe() meta as plain
  JSON, so the proof needs no import of the strategy module).
- **H013 — cross-program layout mismatch**: the layouts that must agree
  ACROSS compiled programs.  Per program: a ZeRO-family train step's
  saved param/opt-state leaves must land exactly on ``ft/reshard``'s
  checkpoint contract (``[n, k]`` row shards partitioned on dim 0,
  stacked ``[L, n, k]`` on dim 1 — :data:`ddl25spring_tpu.ft.reshard.
  SAVED_SHARD_DIMS`), proven by walking entry-parameter shardings; a
  transposed ``[k, n]`` save layout restores garbage after the next
  preemption, silently.  Per pair: the serve prefill/decode programs
  must shard the paged KV pool identically (and on the engine's
  declared head dim) — a divergence means a prefill-written page is
  read back through the wrong device split.

H011/H012 and the per-program half of H013 run inside the ordinary rule
pass (:mod:`ddl25spring_tpu.analysis.rules`), so every registered
strategy's clean pin covers them; the cross-program half needs several
compiled programs in hand and is emitted by
:func:`check_layout_contracts` (``tools/graft_lint.py --shard-flow``).
Waivers ride the shared file; findings are never dropped, only marked.

Grounding: pjit-on-TPUv4 scalable training (arXiv:2204.06514) and
automatic cross-replica weight-update sharding (arXiv:2004.13336) both
treat sharding specs as declarative artifacts worth verifying.
"""

from __future__ import annotations

import re
from typing import Any

from ddl25spring_tpu.analysis import waivers as waivers_mod
from ddl25spring_tpu.analysis.rules import Finding

# ------------------------------------------------------------- summaries


def _pfactor(sh: dict[str, Any], dim: int):
    """Partition factor of ``dim`` in a parsed sharding — tolerant of
    JSON round-trips, which coerce the ``partitions`` dict's int keys
    to strings (the proofs must re-run off stored reports)."""
    parts = sh.get("partitions") or {}
    return parts.get(dim, parts.get(str(dim)))


def sharding_summary(sh: dict[str, Any] | None) -> str:
    """One human token for a parsed ``sharding=`` annotation:
    ``replicated`` / ``dim0/4`` / ``dim1/4`` / ``maximal`` / ``-``."""
    if not sh:
        return "-"
    if sh.get("replicated"):
        return "replicated"
    if sh.get("maximal"):
        return "maximal"
    if sh.get("manual"):
        return "manual"
    dims = sh.get("partitioned_dims") or []
    if not dims:
        return "replicated"
    return ",".join(f"dim{d}/{_pfactor(sh, d)}" for d in dims)


def _type_rank(type_str: str) -> int | None:
    m = re.search(r"\b[a-z]\w*\[([\d,]*)\]", type_str or "")
    if not m:
        return None
    dims = m.group(1)
    return len([d for d in dims.split(",") if d]) if dims else 0


def _norm_arg(arg: str | None) -> str | None:
    """op_name metadata escapes quotes (``pool[\\'k\\']``) — normalize
    everywhere an arg path is rendered, keyed, or matched, so tables,
    JSON artifacts, and waiver globs all see the real ``pool['k']``."""
    return arg.replace("\\'", "'") if arg else arg


# ------------------------------------------------ per-tensor flow graph


def collective_flows(
    hlo_text: str,
    mesh=None,
    report: dict[str, Any] | None = None,
    ctx=None,
) -> list[dict[str, Any]]:
    """The sharding-propagation graph, walked: for every collective op
    site, climb the dataflow back to the entry parameters whose bytes
    feed it (through pass-through ops, fusions — via the engine's
    fusion-caller map — and arbitrary math) and report their declared
    layouts.  A collective whose ancestry stays inside loop bodies the
    walk cannot leave is reported with ``sources=[]`` and
    ``internal=True`` (scan carries; the per-program contracts still
    hold through the carry's entry layout).

    Returns one record per op site: ``{"op", "kind", "computation",
    "sources": [{"arg", "sharding"}], "internal", "truncated"}`` —
    ``truncated`` marks a walk that hit the node budget with frontier
    left, so its source list is a lower bound, not a claim of
    completeness.  Pass a prebuilt ``ctx`` (``engine.build_context``)
    when one is already in hand to skip re-parsing the HLO.
    """
    from ddl25spring_tpu.analysis import engine

    if ctx is None:
        ctx = engine.build_context(hlo_text, mesh, report=report)
    by_name = {p["name"]: p for p in ctx.entry_params}
    # the entry computation: the one defining the entry parameters
    # (derivable from the context — no second _split_computations pass)
    entry = None
    if ctx.entry_params:
        first = ctx.entry_params[0]["name"]
        entry = next(
            (
                comp for comp, defs in ctx.defs.items()
                if defs.get(first, {}).get("opcode") == "parameter"
                and ctx.reachable(comp)
            ),
            None,
        )
    out = []
    for op in ctx.ops:
        seen: set[tuple[str, str]] = set()
        frontier = [
            (op.get("computation"), o) for o in op.get("operands") or []
        ]
        sources: dict[str, dict[str, Any]] = {}
        internal = False
        while frontier and len(seen) < 4096:
            comp, name = frontier.pop()
            if (comp, name) in seen:
                continue
            seen.add((comp, name))
            d = ctx.defs.get(comp, {}).get(name)
            if d is None:
                continue
            if d["opcode"] == "parameter":
                if comp == entry:
                    p = by_name.get(name)
                    if p is not None:
                        key = _norm_arg(p.get("arg")) or p["name"]
                        sources[key] = {
                            "arg": key,
                            "sharding": sharding_summary(p.get("sharding")),
                        }
                    continue
                caller = ctx.fusion_callers.get(comp)
                idx = ctx.param_index(d)
                if (
                    caller
                    and idx is not None
                    and idx < len(caller[1]["operands"])
                ):
                    frontier.append((caller[0], caller[1]["operands"][idx]))
                else:
                    # a while/cond body parameter: the walk cannot map
                    # the carry slot back generically — mark and stop
                    internal = True
                continue
            called = ctx.called_computation(d)
            if d["opcode"] == "fusion" and called:
                root = ctx.root_of(called)
                if root is not None:
                    frontier.append((called, root))
                    continue
            frontier.extend((comp, o) for o in d.get("operands") or [])
        out.append({
            "op": op.get("name"),
            "kind": op["kind"],
            "computation": op.get("computation"),
            "sources": sorted(sources.values(), key=lambda s: s["arg"]),
            "internal": internal,
            "truncated": bool(frontier),
        })
    return out


def flow_summary(report: dict[str, Any]) -> dict[str, Any]:
    """The per-strategy shard-flow block ``graft_lint --shard-flow``
    renders: entry-parameter layout table always; the per-collective
    source walk only when the report kept its HLO text."""
    entry = [
        {
            "arg": _norm_arg(p.get("arg")) or p["name"],
            "bytes": p["bytes"],
            "sharding": sharding_summary(p.get("sharding")),
        }
        for p in report.get("entry_params") or []
    ]
    out: dict[str, Any] = {"entry_params": entry}
    hlo = report.get("hlo_text")
    if hlo:
        out["flows"] = collective_flows(hlo, report=report)
    return out


# --------------------------------------------------- H012 coverage proof


def coverage_defects(
    table_meta: dict[str, Any], paths: list[str]
) -> list[dict[str, Any]]:
    """Judge a serialized rule table (describe() meta shape, see
    :meth:`ddl25spring_tpu.parallel.rules.RuleTable.to_meta`) against
    the param leaf paths it must cover.  Returns one defect record per
    violation: ``{"defect": "unmatched"|"ambiguous"|"shadowed"|
    "bad-table", "path"|"pattern", "detail"}`` — empty list == the
    coverage proof holds (every leaf matched exactly once, every rule
    fires)."""
    from ddl25spring_tpu.parallel.rules import rule_coverage

    try:
        cov = rule_coverage(
            [tuple(r) for r in table_meta.get("rules") or []], paths
        )
    except (ValueError, TypeError, re.error) as e:
        return [{
            "defect": "bad-table",
            "pattern": None,
            "detail": f"table does not parse: {e}",
        }]
    out = []
    for leaf in cov["leaves"]:
        if not leaf["matches"]:
            out.append({
                "defect": "unmatched",
                "path": leaf["path"],
                "detail": "no rule matches this param leaf — it would "
                          "train under no declared layout",
            })
        elif len(leaf["matches"]) > 1:
            pats = [
                cov["rules"][i]["pattern"] for i in leaf["matches"]
            ]
            out.append({
                "defect": "ambiguous",
                "path": leaf["path"],
                "detail": f"matched by {len(pats)} rules {pats} — only "
                          "the first fires; the table's order is "
                          "silently load-bearing",
            })
    for i, r in enumerate(cov["rules"]):
        if r["first_matches"] == 0:
            why = (
                "every leaf it matches is taken by an earlier rule"
                if r["matches"] else "it matches no leaf at all"
            )
            out.append({
                "defect": "shadowed",
                "pattern": r["pattern"],
                "detail": f"rule #{i} ({r['pattern']!r} -> {r['spec']}) "
                          f"can never fire: {why}",
            })
    return out


# ------------------------------------------- H013 cross-program contract


def _zero_family(meta: dict[str, Any]) -> bool:
    atoms = {
        s for _, s in (meta.get("rule_table") or {}).get("rules", [])
    }
    return bool(meta.get("zero_stage")) or bool(atoms & {"rows", "layers"})


def saved_layout_findings(report: dict[str, Any]) -> list[Finding]:
    """The per-program half of H013: a ZeRO-family train step's saved
    state (the donatable params/opt-state entry parameters — exactly
    what ``ft/autosave`` persists) must shard per ``ft/reshard``'s
    checkpoint contract, read off the entry-parameter ``sharding=``
    annotations of the compiled program itself."""
    from ddl25spring_tpu.analysis.rules import h013_finding
    from ddl25spring_tpu.ft.reshard import SAVED_SHARD_DIMS

    meta = report.get("meta") or {}
    if not _zero_family(meta):
        return []
    donatable = (report.get("donation") or {}).get("donatable_leaves")
    mesh_sizes = set((report.get("mesh") or {}).values())
    out = []
    for p in report.get("entry_params") or []:
        if donatable is not None and p["number"] >= donatable:
            continue  # batch/rng: not part of the saved state
        sh = p.get("sharding")
        dims = (sh or {}).get("partitioned_dims") or []
        if not dims:
            continue  # replicated leaf (zero1/2 params): nothing to save sharded
        rank = _type_rank(p.get("type") or "")
        want = SAVED_SHARD_DIMS.get(rank)
        where = _norm_arg(p.get("arg")) or p["name"]
        if want is None or dims != [want]:
            out.append(h013_finding(
                report.get("strategy"),
                op=where,
                bytes=p.get("bytes"),
                message=(
                    f"saved leaf {where} (rank {rank}) is partitioned on "
                    f"dim(s) {dims} but ft/reshard's checkpoint contract "
                    f"shards rank-{rank} state on dim "
                    f"{want if want is not None else '<unsupported>'} "
                    "([n, k] rows / [L, n, k] layers) — a resumed run "
                    "would re-land rows through the wrong split"
                ),
            ))
        elif mesh_sizes and _pfactor(sh, want) not in mesh_sizes:
            out.append(h013_finding(
                report.get("strategy"),
                op=where,
                bytes=p.get("bytes"),
                message=(
                    f"saved leaf {where} splits dim {want} "
                    f"{_pfactor(sh, want)} ways, matching no "
                    f"mesh axis of {report.get('mesh')} — the [n, k] "
                    "row count must be the shard axis size for "
                    "ft/reshard's row refit to be exact"
                ),
            ))
    return out


def _pool_params(report: dict[str, Any]) -> dict[str, dict[str, Any]]:
    return {
        _norm_arg(p["arg"]): p
        for p in report.get("entry_params") or []
        if p.get("arg") and p["arg"].startswith("pool[")
    }


def serve_pair_findings(
    reports: dict[str, dict[str, Any]],
) -> list[Finding]:
    """The cross-program half of H013 for serving: every compiled serve
    program pair (prefill/decode/cached-prefill) must shard each paged
    KV-pool buffer IDENTICALLY, and the k/v pages must split exactly the
    head dim the engine declares (``meta["kv_sharded_dim"]``) — the
    prefill program writes the pages the decode program reads, so a
    layout divergence is silent KV corruption on a real mesh."""
    from ddl25spring_tpu.analysis.rules import h013_finding

    serve = {
        name: r for name, r in reports.items()
        if (r.get("meta") or {}).get("program") and "error" not in r
    }
    pools = {name: _pool_params(r) for name, r in serve.items()}
    out = []
    for name, r in serve.items():
        meta = r.get("meta") or {}
        kv_dim = meta.get("kv_sharded_dim")
        if kv_dim is None:
            continue
        # with TP active the pages must shard EXACTLY the declared head
        # dim — a pool that silently falls back to replicated (dims ==
        # []) is as much a contract break as one split on a wrong dim.
        # (t == 1 legitimately compiles everything replicated.)
        want = [kv_dim] if int(meta.get("tp") or 1) > 1 else []
        for arg in ("pool['k']", "pool['v']"):
            p = pools[name].get(arg)
            if p is None:
                # op_name metadata missing/renamed: nothing to judge
                # here — tier-1 pins the args' presence on this jax
                # (tests/test_shard_flow.py), so a silent skip cannot
                # rot unnoticed
                continue
            dims = (p.get("sharding") or {}).get("partitioned_dims") or []
            if dims != want:
                out.append(h013_finding(
                    name, op=arg,
                    message=(
                        f"{arg} is partitioned on dim(s) {dims} but the "
                        f"engine declares the KV pool shards exactly "
                        f"its head dim ({want or 'none at tp=1'}) — the "
                        "page layout and the admission accounting "
                        "disagree"
                    ),
                ))
    names = sorted(serve)
    for i, a in enumerate(names):
        for b in names[i + 1:]:
            pa, pb = pools[a], pools[b]
            for arg in sorted(set(pa) & set(pb)):
                sa = sharding_summary(pa[arg].get("sharding"))
                sb = sharding_summary(pb[arg].get("sharding"))
                if sa != sb:
                    # the finding carries ONE real strategy name (the
                    # first of the pair) so ordinary waiver globs match
                    # it; the message names both sides of the pair
                    out.append(h013_finding(
                        a, op=arg,
                        message=(
                            f"cross-program layout mismatch on {arg}: "
                            f"{a} compiles it {sa}, {b} compiles it "
                            f"{sb} — pages written by one program are "
                            "read through a different device split by "
                            "the other"
                        ),
                    ))
    return out


def stream_rows_findings(
    reports: dict[str, dict[str, Any]],
) -> list[Finding]:
    """The weight-streaming half of H013 (PR 18): a program that
    declares ``meta["stream_rows_dim"]`` holds its block params as
    ZeRO-3 ``[L, n, k]`` rows, so every ``params['blocks']`` entry
    parameter must be partitioned on exactly that dim — a blocks leaf
    compiled replicated (or split elsewhere) means XLA materialized the
    full stack per chip and the `param_bytes/n` residency claim is
    silently void."""
    from ddl25spring_tpu.analysis.rules import h013_finding

    out = []
    for name, r in reports.items():
        meta = r.get("meta") or {}
        dim = meta.get("stream_rows_dim")
        if dim is None or "error" in r:
            continue
        if int(meta.get("tp") or 1) <= 1:
            continue  # one chip legitimately compiles rows replicated
        for p in r.get("entry_params") or []:
            # op_name metadata escapes quotes — normalize BEFORE the
            # prefix match or the walk silently sees nothing
            arg = _norm_arg(p.get("arg")) or ""
            if not arg.startswith("params['blocks']"):
                continue
            dims = (p.get("sharding") or {}).get("partitioned_dims") or []
            if dims != [dim]:
                where = arg or p["name"]
                out.append(h013_finding(
                    name, op=where, bytes=p.get("bytes"),
                    message=(
                        f"streamed blocks leaf {where} is partitioned "
                        f"on dim(s) {dims} but the engine declares the "
                        f"ZeRO-3 row split on dim {dim} ([L, n, k]) — "
                        "the layer stack is resident per chip and the "
                        "param_bytes/n streaming claim does not hold"
                    ),
                ))
    return out


def check_layout_contracts(
    reports: dict[str, dict[str, Any]],
    waivers: list | None = None,
) -> list[Finding]:
    """All cross-program layout checks over a set of compiled strategy
    reports (the ``graft_lint --shard-flow`` emission point): the
    per-program saved-layout walk is already part of each strategy's
    own rule pass (H013 in the pack), so only the program-PAIR
    contracts emit here.  Waiver-resolved like every finding."""
    findings = serve_pair_findings(reports) + stream_rows_findings(reports)
    return waivers_mod.apply_waivers(
        findings,
        waivers_mod.load_waivers() if waivers is None else waivers,
    )


# ----------------------------------------------------- graft-lint section


def flow_report(
    reports: dict[str, dict[str, Any]],
    waivers: list | None = None,
) -> dict[str, Any]:
    """The ``--shard-flow`` document: per-strategy flow summaries, the
    cross-program findings, and per-rule counts over EVERYTHING the
    shard-flow family produced (H011-H013, including the per-strategy
    findings already resolved in each report) — the machine-diffable
    shape the CI artifact wants."""
    strategies = {
        name: flow_summary(r)
        for name, r in reports.items()
        if "error" not in r
    }
    cross = [f.to_dict() for f in check_layout_contracts(reports, waivers)]
    by_rule: dict[str, int] = {}
    for f in cross:
        by_rule[f["rule"]] = by_rule.get(f["rule"], 0) + 1
    for r in reports.values():
        for f in r.get("findings") or []:
            if f.get("rule") in ("H011", "H012", "H013"):
                by_rule[f["rule"]] = by_rule.get(f["rule"], 0) + 1
    return {
        "strategies": strategies,
        "findings": cross,
        "by_rule": by_rule,
    }
