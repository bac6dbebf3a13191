"""graft-sched: whole-program SPMD schedule verification over HLO.

The PR-8 backward overlap shipped a *scheduling* win the 2-core CI host
cannot measure (RESULTS.md: the whole comms bill is ~1% of step wall,
noise-bound), and the only static judgment so far is H001's single-op
"has a start/done pair" test — which a zero-slack ``start; done``
sequence passes trivially.  This module turns the schedule itself into
compile-time facts, two families:

**Overlap slack.**  From the per-device instruction stream of one HLO
computation, build the instruction-level dependency DAG (operand +
``control-predecessors`` edges), estimate each instruction's static
cost (FLOPs via dot contracting-dim accounting, fusion bodies inlined,
loop bodies multiplied by ``known_trip_count``; bytes via result
shapes), and for every collective derive the **window** of provably
independent work schedulable while its transfer is in flight:

- an ``-start``/``-done`` pair's window is the instructions *between*
  the pair in program order, DAG-verified independent of the pair —
  the literal async window the schedule committed to;
- a sync collective under the **sync issue discipline** gets the
  committed schedule's window: instructions between the op and the
  first use of its result (on a scheduled module this is exactly what
  an in-order device could overlap if the op were async-ified in
  place);
- a sync collective under the **overlap issue discipline** (a strategy
  whose ``describe()`` declares ``overlap``/``prefetch`` — the
  backward-issued bucket collectives and the double-buffered gather,
  whose issue points are fixed by dataflow, not by this backend's
  scheduler) gets the dataflow window: every instruction that is
  neither ancestor nor descendant of the op.  This is the maximal
  window ANY legal schedule can realize — the right bound for a
  strategy whose contract is "issue at readiness", and the only
  faithful one on a CPU backend whose scheduler re-sinks every
  collective to its first use regardless of how the program staged it.

The per-strategy roll-up is ``static_overlap_bound``: an analytical
upper bound on the share of its transfers a schedule can hide under
the strategy's issue discipline.  Each collective can hide at most
``min(t_wire, t_slack)`` seconds of its transfer, with both times taken
from ONE reference chip spec (:data:`REF_CHIP` — a datasheet constant,
so the bound is noise-free and host-independent by construction)::

    bound = sum(count * min(t_wire, t_slack)) / sum(count * t_wire)

A sync strategy on this backend shows ~0 (its committed schedule
leaves nothing in the windows); the overlapped twins show the slack
their restructured backward provably created — the static proof the
noise-bound PR-8 A/B could not give.

**Schedule safety.**  Replica groups expand into per-participant
collective streams, and :func:`check_schedule_safety` proves the
absence of the deadlock shapes a single-module textual check (H007's
duplicate-permute-target rule) cannot see:

- a device repeated inside one replica group (it would rendezvous with
  itself — a mismatched instance on hardware);
- two collective sites sharing a ``channel_id`` with *different*
  participant groups (the channel is the rendezvous identity: the two
  sites' participants wait on each other and neither set completes);
- participants outside the compiled program's device range
  (``num_partitions``/mesh size): the named peer never arrives;
- conditional branches whose collective sequences diverge (kind/group
  order): any device-varying predicate splits the mesh into
  sub-programs that issue mismatched sequences — the MPMD deadlock
  class, statically visible inside one module;
- crossed async windows (``start-A start-B done-A done-B``) over
  overlapping-but-unequal groups — a cross-channel ordering inversion:
  the shared participants hold A's resources while B's disjoint
  participants cannot make progress on B.

Rules H008 (zero-slack window) and H009 (participant-stream mismatch)
surface both families through the existing engine/waiver machinery; see
``analysis/rules.py`` and ``tools/graft_lint.py --sched``.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Any

# deterministic reference spec for the bound's wire/compute times: a
# datasheet constant (never the runtime-calibrated host peak — the
# bound must be bit-identical across machines)
REF_CHIP = "TPU v4"

# instructions that move/relabel bytes without arithmetic: zero FLOPs
_ZERO_FLOP_OPS = frozenset({
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "copy", "reshape", "transpose", "broadcast", "iota", "slice",
    "dynamic-slice", "dynamic-update-slice", "concatenate", "pad",
    "after-all", "partition-id", "replica-id", "rng-bit-generator",
    "convert", "all-reduce", "all-gather", "reduce-scatter",
    "collective-permute", "all-to-all", "collective-broadcast",
    "all-reduce-start", "all-reduce-done", "all-gather-start",
    "all-gather-done", "reduce-scatter-start", "reduce-scatter-done",
    "collective-permute-start", "collective-permute-done",
    "all-to-all-start", "all-to-all-done", "copy-start", "copy-done",
    "send", "send-done", "recv", "recv-done", "optimization-barrier",
})

_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_CTRL_RE = re.compile(r"control-predecessors=\{([^}]*)\}")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_NUM_PARTITIONS_RE = re.compile(r"num_partitions=(\d+)")
_REPLICA_COUNT_RE = re.compile(r"replica_count=(\d+)")
_CHANNEL_RE = re.compile(r"channel_id=(\d+)")
_SHAPE_ELEMS_RE = re.compile(r"\b[a-z]\w*\[([\d,]*)\]")


def _elems(type_str: str) -> int:
    """Total elements across every shape group in an HLO type string."""
    total = 0
    for dims in _SHAPE_ELEMS_RE.findall(type_str):
        total += math.prod(int(d) for d in dims.split(",") if d) if dims else 1
    return total


def _arg_shapes(line: str, opcode: str) -> list[str]:
    """The operand type strings inside ``opcode(...)``'s balanced-paren
    argument list (``f32[8,16]{1,0} %param.1`` -> ``f32[8,16]``)."""
    i = line.find(opcode + "(")
    if i < 0:
        return []
    i += len(opcode)
    depth, end = 0, len(line)
    for j in range(i, len(line)):
        c = line[j]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                end = j
                break
    return re.findall(r"\b([a-z]\w*\[[\d,]*\])", line[i:end])


# --------------------------------------------------------- static costs


def instruction_flops(
    defs: dict[str, dict[str, dict[str, Any]]],
    comp: str,
    d: dict[str, Any],
    comp_cache: dict[str, float],
) -> float:
    """Static FLOP estimate for one instruction.

    ``dot``: ``2 * out_elems * k`` with ``k`` the product of the lhs
    contracting dims (parsed off the op line — exact for every matmul
    jax emits).  ``convolution``: ``2 * sqrt(lhs * rhs * out)`` — the
    symmetric estimate, exact for plain matmul-shaped convs and within
    a batch factor otherwise (the bound only needs relative weight).
    ``fusion``/``call``: the callee's total.  ``while``: body+condition
    times ``known_trip_count``.  ``conditional``: the widest branch.
    Data movement (:data:`_ZERO_FLOP_OPS`): 0.  Everything else: one
    FLOP per result element (the elementwise estimate).
    """
    opcode = d["opcode"]
    if opcode in _ZERO_FLOP_OPS:
        return 0.0
    line = d["line"]
    if opcode == "dot":
        out = _elems(d["type"])
        args = _arg_shapes(line, "dot")
        m = _CONTRACT_RE.search(line)
        if args and m is not None:
            ldims = [
                int(x)
                for x in (re.search(r"\[([\d,]*)\]", args[0]).group(1) or ""
                          ).split(",")
                if x
            ]
            try:
                k = math.prod(
                    ldims[int(i)] for i in m.group(1).split(",") if i
                )
            except (IndexError, ValueError):
                k = 1
            return 2.0 * out * max(k, 1)
        return 2.0 * out
    if opcode == "convolution":
        args = _arg_shapes(line, "convolution")
        out = _elems(d["type"])
        if len(args) >= 2:
            return 2.0 * math.sqrt(
                max(_elems(args[0]), 1) * max(_elems(args[1]), 1) * max(out, 1)
            )
        return 2.0 * out
    if opcode in ("fusion", "call", "custom-call", "map"):
        m = _CALLS_RE.search(line)
        if m:
            return computation_flops(defs, m.group(1), comp_cache)
        return 0.0
    if opcode == "while":
        t = re.search(r'known_trip_count[\\"=:{\s]+n[\\"=:\s]+(\d+)', line)
        trip = int(t.group(1)) if t else 1
        total = 0.0
        for attr in ("body", "condition"):
            m = re.search(attr + r"=%?([\w.\-]+)", line)
            if m:
                total += computation_flops(defs, m.group(1), comp_cache)
        return trip * total
    if opcode == "conditional":
        m = re.search(r"branches=\{([^}]*)\}", line)
        branches = (
            [b.strip().lstrip("%") for b in m.group(1).split(",")]
            if m
            else [
                g.group(1)
                for g in re.finditer(
                    r"(?:true_computation|false_computation)=%?([\w.\-]+)",
                    line,
                )
            ]
        )
        return max(
            (computation_flops(defs, b, comp_cache) for b in branches),
            default=0.0,
        )
    if opcode in ("reduce", "reduce-window", "sort", "scatter", "gather"):
        args = _arg_shapes(line, opcode)
        return float(max((_elems(a) for a in args), default=_elems(d["type"])))
    return float(_elems(d["type"]))


def computation_flops(
    defs: dict[str, dict[str, dict[str, Any]]],
    comp: str,
    comp_cache: dict[str, float] | None = None,
) -> float:
    """Total static FLOPs of one computation (callees inlined)."""
    if comp_cache is None:
        comp_cache = {}
    if comp in comp_cache:
        return comp_cache[comp]
    comp_cache[comp] = 0.0  # cycle guard: recursive HLO cannot recur
    total = 0.0
    for d in defs.get(comp, {}).values():
        total += instruction_flops(defs, comp, d, comp_cache)
    comp_cache[comp] = total
    return total


# ------------------------------------------------------ dependency DAG


@dataclass
class CompDag:
    """One computation's instruction stream as a dependency DAG.

    ``names`` is program order (HLO lists defs before uses, so it is a
    topological order — and on an ``is_scheduled`` module it is the
    device's execution order).  ``anc[i]`` is the bitmask of ancestor
    indices of instruction ``i`` (operand + control edges, transitive).
    """

    comp: str
    names: list[str]
    index: dict[str, int]
    defs: dict[str, dict[str, Any]]
    anc: list[int]
    flops: list[float]
    bytes_: list[int]
    first_use: dict[str, int | None] = field(default_factory=dict)

    def independent(self, i: int, j: int) -> bool:
        """Neither depends on the other (can run concurrently in some
        legal schedule)."""
        return not (self.anc[i] >> j) & 1 and not (self.anc[j] >> i) & 1


def build_dag(
    defs: dict[str, dict[str, dict[str, Any]]],
    comp: str,
    comp_cache: dict[str, float] | None = None,
) -> CompDag:
    """Build the instruction-level dependency DAG of one computation."""
    from ddl25spring_tpu.obs.xla_analytics import _shape_bytes

    dd = defs.get(comp, {})
    names = list(dd)
    index = {n: i for i, n in enumerate(names)}
    if comp_cache is None:
        comp_cache = {}
    anc: list[int] = []
    flops: list[float] = []
    bytes_: list[int] = []
    first_use: dict[str, int | None] = {n: None for n in names}
    for i, n in enumerate(names):
        d = dd[n]
        deps = list(d["operands"])
        m = _CTRL_RE.search(d["line"])
        if m:
            deps += [x.strip().lstrip("%") for x in m.group(1).split(",")]
        mask = 0
        for dep in deps:
            j = index.get(dep)
            if j is None or j >= i:
                continue
            mask |= anc[j] | (1 << j)
            if first_use[names[j]] is None:
                first_use[names[j]] = i
        anc.append(mask)
        flops.append(instruction_flops(defs, comp, d, comp_cache))
        bytes_.append(_shape_bytes(d["type"]))
    return CompDag(
        comp=comp, names=names, index=index, defs=dd, anc=anc,
        flops=flops, bytes_=bytes_, first_use=first_use,
    )


def _find_done(dag: CompDag, start: str) -> str | None:
    """The ``*-done`` op consuming async op ``start`` (same comp)."""
    sd = dag.defs.get(start)
    if sd is None:
        return None
    kind = sd["opcode"].removesuffix("-start")
    done_op = kind + "-done"
    for n, d in dag.defs.items():
        if d["opcode"] == done_op and d["operands"][:1] == [start]:
            return n
    return None


def window_slack(
    dag: CompDag, op_name: str, discipline: str = "sync"
) -> dict[str, Any] | None:
    """Overlap slack of one collective: the FLOPs and bytes of provably
    independent instructions schedulable inside its window.

    Window selection (see the module docstring): a ``-start`` op uses
    its literal ``[start, done]`` pair window; a sync op uses the
    committed schedule's ``[op, first use)`` window under the ``sync``
    discipline and the maximal dataflow window (all DAG-independent
    instructions) under the ``overlap`` discipline.
    """
    i = dag.index.get(op_name)
    if i is None:
        return None
    d = dag.defs[op_name]
    is_start = d["opcode"].endswith("-start")
    slack_f = 0.0
    slack_b = 0
    n_indep = 0
    if is_start:
        done = _find_done(dag, op_name)
        j_end = dag.index.get(done, len(dag.names)) if done else len(dag.names)
        window = "pair"
        for j in range(i + 1, j_end):
            # between the pair in program order; exclude anything the
            # start feeds (a dependent cannot run while it is in flight)
            if (dag.anc[j] >> i) & 1:
                continue
            slack_f += dag.flops[j]
            slack_b += dag.bytes_[j]
            n_indep += 1
    elif discipline == "overlap":
        window = "dataflow"
        for j in range(len(dag.names)):
            if j == i or not dag.independent(i, j):
                continue
            slack_f += dag.flops[j]
            slack_b += dag.bytes_[j]
            n_indep += 1
    else:
        window = "schedule"
        use = dag.first_use.get(op_name)
        j_end = use if use is not None else len(dag.names)
        for j in range(i + 1, j_end):
            if (dag.anc[j] >> i) & 1:
                continue
            slack_f += dag.flops[j]
            slack_b += dag.bytes_[j]
            n_indep += 1
    return {
        "op": op_name,
        "computation": dag.comp,
        "window": window,
        "slack_flops": slack_f,
        "slack_bytes": slack_b,
        "independent_instructions": n_indep,
    }


# ---------------------------------------------------- schedule safety


def _groups_key(op: dict[str, Any]) -> tuple:
    """Canonical participant-group identity of one collective site."""
    groups = op.get("groups")
    if groups:
        return tuple(sorted(tuple(g) for g in groups))
    pairs = op.get("pairs")
    if pairs:
        return tuple(sorted(tuple(p) for p in pairs))
    return ()


def _participants(op: dict[str, Any]) -> set[int]:
    out: set[int] = set()
    for g in op.get("groups") or ():
        out.update(g)
    for s, t in op.get("pairs") or ():
        out.update((s, t))
    return out


def participant_streams(
    sites: list[dict[str, Any]],
) -> dict[int, list[tuple[int, str, tuple]]]:
    """Expand replica groups into per-participant collective streams:
    ``{device: [(site_index, kind, group_key), ...]}`` in program
    order.  This is the object the safety checks reason over — every
    device's view of the collective sequence it must rendezvous with.
    """
    streams: dict[int, list[tuple[int, str, tuple]]] = {}
    for idx, op in enumerate(sites):
        key = _groups_key(op)
        for dev in sorted(_participants(op)):
            streams.setdefault(dev, []).append((idx, op["kind"], key))
    return streams


def _branch_collective_signature(
    defs: dict[str, dict[str, dict[str, Any]]],
    comp: str,
    seen: set[str] | None = None,
) -> tuple:
    """The ordered collective sequence a computation (and its callees)
    issues: ``((kind, groups_text), ...)`` — the thing every
    participant of a conditional must agree on."""
    from ddl25spring_tpu.obs.xla_analytics import (
        _COLLECTIVE_RE,
        _parse_groups,
        _parse_pairs,
    )

    if seen is None:
        seen = set()
    if comp in seen:
        return ()
    seen.add(comp)
    sig: list[tuple] = []
    for d in defs.get(comp, {}).values():
        m = _COLLECTIVE_RE.search(d["line"])
        if m:
            groups = _parse_groups(d["line"])
            pairs = _parse_pairs(d["line"])
            sig.append((
                m.group(1),
                tuple(sorted(tuple(g) for g in groups)) if groups
                else tuple(sorted(tuple(p) for p in pairs)) if pairs
                else (),
            ))
        cm = _CALLS_RE.search(d["line"])
        if cm:
            sig.extend(_branch_collective_signature(defs, cm.group(1), seen))
        for attr in ("body", "condition", "true_computation",
                     "false_computation"):
            am = re.search(attr + r"=%?([\w.\-]+)", d["line"])
            if am:
                sig.extend(
                    _branch_collective_signature(defs, am.group(1), seen)
                )
    return tuple(sig)


def check_schedule_safety(
    hlo_text: str,
    defs: dict[str, dict[str, dict[str, Any]]],
    sites: list[dict[str, Any]],
    dags: dict[str, CompDag] | None = None,
) -> list[dict[str, Any]]:
    """Prove the per-participant streams match — or name the mismatch.

    Returns hazard records ``{"check", "op", "computation", "message"}``
    for every deadlock shape found (empty list == the schedule-safety
    proof holds for this module).  See the module docstring for the
    five checks.
    """
    hazards: list[dict[str, Any]] = []
    # the module's device-id space: replica ids are bounded by
    # replica_count, partition ids by num_partitions, and flattened
    # use_global_device_ids by their PRODUCT — so the product (with a
    # missing count read as 1) is the one bound valid in every mode;
    # a pmap-lowered replica-mode module (replica_count=8,
    # num_partitions=1) must not false-fire on replica id 7
    mp = _NUM_PARTITIONS_RE.search(hlo_text)
    mr = _REPLICA_COUNT_RE.search(hlo_text)
    n_devices = (
        (int(mp.group(1)) if mp else 1) * (int(mr.group(1)) if mr else 1)
        if (mp or mr) else None
    )

    by_channel: dict[int, list[dict[str, Any]]] = {}
    for op in sites:
        # (1) a device repeated inside one replica group
        for g in op.get("groups") or ():
            if len(g) != len(set(g)):
                hazards.append({
                    "check": "duplicate-participant",
                    "op": op.get("name"),
                    "computation": op.get("computation"),
                    "message": (
                        f"{op['kind']} replica group {g} repeats a "
                        "device — it would rendezvous with itself"
                    ),
                })
        cm = _CHANNEL_RE.search(op.get("line") or "")
        if cm:
            by_channel.setdefault(int(cm.group(1)), []).append(op)

    # (2) participants beyond the compiled device range — judged over
    # the expanded per-participant streams: a device id past the bound
    # owns a stream of rendezvous no real device will ever join
    streams = participant_streams(sites)
    if n_devices is not None:
        for dev in sorted(streams):
            if dev < n_devices:
                continue
            site = sites[streams[dev][0][0]]
            hazards.append({
                "check": "participant-out-of-range",
                "op": site.get("name"),
                "computation": site.get("computation"),
                "message": (
                    f"device {dev} participates in "
                    f"{len(streams[dev])} collective site(s) (first: "
                    f"{site['kind']}) but the module compiles for "
                    f"{n_devices} device(s) — the named peer never "
                    "arrives"
                ),
            })

    # (3) one channel_id, different participant groups: the rendezvous
    # identity is shared but the participant sets disagree.  Judged only
    # where the ids identify anything: jax 0.9.0 lowers EVERY shard_map
    # collective with channel_id=1 (a cross-partition marker; the
    # runtime orders the sites by program position), so a module whose
    # sites all carry one id names no rendezvous by it
    for ch, chops in by_channel.items() if len(by_channel) > 1 else ():
        keys = {_groups_key(o) for o in chops}
        if len(keys) > 1:
            hazards.append({
                "check": "channel-group-mismatch",
                "op": chops[0].get("name"),
                "computation": chops[0].get("computation"),
                "message": (
                    f"channel_id={ch} is shared by {len(chops)} "
                    "collective site(s) with DIFFERENT participant "
                    "groups — the participants wait on each other and "
                    "neither instance can complete"
                ),
            })

    # (4) conditional branches with divergent collective sequences
    for comp, dd in defs.items():
        for name, d in dd.items():
            if d["opcode"] != "conditional":
                continue
            bm = re.search(r"branches=\{([^}]*)\}", d["line"])
            branches = (
                [b.strip().lstrip("%") for b in bm.group(1).split(",")]
                if bm
                else [
                    g.group(1)
                    for g in re.finditer(
                        r"(?:true_computation|false_computation)"
                        r"=%?([\w.\-]+)",
                        d["line"],
                    )
                ]
            )
            sigs = [_branch_collective_signature(defs, b) for b in branches]
            if len({s for s in sigs}) > 1 and any(sigs):
                hazards.append({
                    "check": "divergent-branches",
                    "op": name,
                    "computation": comp,
                    "message": (
                        "conditional branches issue different collective"
                        f" sequences ({[len(s) for s in sigs]} site(s) "
                        "per branch) — a device-varying predicate "
                        "splits the mesh into participants that wait "
                        "for mismatched sequences"
                    ),
                })

    # (5) crossed async windows over overlapping-but-unequal groups
    if dags:
        for dag in dags.values():
            starts = [
                n for n, d in dag.defs.items()
                if d["opcode"].endswith("-start")
                and d["opcode"].removesuffix("-start").removesuffix("-")
                in ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")
            ]
            spans = []
            for s in starts:
                done = _find_done(dag, s)
                if done is None:
                    continue
                site = next(
                    (o for o in sites if o.get("name") == s
                     and o.get("computation") == dag.comp), None,
                )
                spans.append((
                    dag.index[s], dag.index[done], s,
                    _participants(site) if site else set(),
                ))
            spans.sort()
            for a in range(len(spans)):
                for b in range(a + 1, len(spans)):
                    s1, d1, n1, p1 = spans[a]
                    s2, d2, n2, p2 = spans[b]
                    crossed = s1 < s2 < d1 < d2
                    if not crossed or not p1 or not p2:
                        continue
                    if p1 != p2 and (p1 & p2):
                        hazards.append({
                            "check": "crossed-async-windows",
                            "op": n2,
                            "computation": dag.comp,
                            "message": (
                                f"async windows of {n1} and {n2} cross "
                                "(start-A start-B done-A done-B) over "
                                "overlapping but unequal participant "
                                f"sets {sorted(p1)} vs {sorted(p2)} — "
                                "an ordering inversion the shared "
                                "participants cannot serialize"
                            ),
                        })
    return hazards


# ------------------------------------------------------- the analysis


def _ref_spec(chip: str | None = None) -> tuple[str, dict[str, float]]:
    from ddl25spring_tpu.utils.flops import CHIP_SPECS

    kind = chip or REF_CHIP
    spec = CHIP_SPECS.get(kind)
    if not spec or not spec.get("ici_bytes_per_s"):
        kind, spec = next(
            (k, s) for k, s in CHIP_SPECS.items()
            if s.get("ici_bytes_per_s") and s.get("peak_bf16_flops")
        )
    return kind, spec


def analyze_schedule(
    hlo_text: str,
    mesh=None,
    ops: list[dict[str, Any]] | None = None,
    discipline: str = "sync",
    scalar_bytes: int = 64,
    chip: str | None = None,
) -> dict[str, Any]:
    """The whole-program schedule report for one HLO module.

    ``ops`` is the collective inventory from
    :func:`~ddl25spring_tpu.obs.xla_analytics.parse_hlo_collectives`
    (re-parsed when omitted); ``discipline`` is the strategy's issue
    discipline (``"sync"`` or ``"overlap"`` — see the module
    docstring).  Returns::

        {
          "discipline", "ref_chip",
          "slack": [per-collective slack records],
          "hazards": [schedule-safety hazard records],
          "static_overlap_bound": float | None,
          "wire_s", "hideable_s", "async_pairs",
        }
    """
    from ddl25spring_tpu.obs import xla_analytics as xa

    if ops is None:
        ops = xa.parse_hlo_collectives(hlo_text, mesh)
    defs = xa.parse_op_defs(hlo_text)
    # op-site lines for channel/group inspection: the inventory records
    # don't carry the raw line, so re-anchor each site in the def table
    sites: list[dict[str, Any]] = []
    for op in ops:
        d = defs.get(op.get("computation") or "", {}).get(op.get("name") or "")
        site = dict(op)
        site["line"] = d["line"] if d else ""
        site["groups"] = (
            xa._parse_groups(site["line"]) if site["line"] else None
        )
        sites.append(site)

    comp_cache: dict[str, float] = {}
    dags: dict[str, CompDag] = {}
    for comp in {op["computation"] for op in ops if op.get("computation")}:
        if comp in defs:
            dags[comp] = build_dag(defs, comp, comp_cache)

    kind, spec = _ref_spec(chip)
    peak = spec["peak_bf16_flops"]
    ici = spec["ici_bytes_per_s"]

    slack_records: list[dict[str, Any]] = []
    wire_s = 0.0
    hideable_s = 0.0
    n_pairs = 0
    for op in ops:
        dag = dags.get(op.get("computation") or "")
        if dag is None or op.get("name") not in dag.index:
            continue
        rec = window_slack(dag, op["name"], discipline)
        if rec is None:
            continue
        rec.update({
            "kind": op["kind"],
            "count": op["count"],
            "result_bytes": op["result_bytes"],
            "wire_bytes": op.get("wire_bytes") or 0,
            "async": bool(op.get("async")),
        })
        if rec["async"]:
            n_pairs += 1
        t_wire = rec["wire_bytes"] / ici
        t_slack = rec["slack_flops"] / peak
        rec["t_wire_s"] = t_wire
        rec["t_slack_s"] = t_slack
        slack_records.append(rec)
        if rec["result_bytes"] <= scalar_bytes or t_wire <= 0:
            continue  # scalar bookkeeping never counts toward the bound
        wire_s += op["count"] * t_wire
        hideable_s += op["count"] * min(t_wire, t_slack)

    hazards = check_schedule_safety(hlo_text, defs, sites, dags)
    return {
        "discipline": discipline,
        "ref_chip": kind,
        # the exemption threshold this analysis used — renderers filter
        # their window listings on THIS value, never a copy of it
        "scalar_bytes": scalar_bytes,
        "slack": slack_records,
        "hazards": hazards,
        "async_pairs": n_pairs,
        "wire_s": wire_s,
        "hideable_s": hideable_s,
        "static_overlap_bound": (
            hideable_s / wire_s if wire_s > 0 else None
        ),
    }


def discipline_of(meta: dict[str, Any] | None) -> str:
    """A strategy's issue discipline from its describe() meta: overlap
    and prefetch variants commit to issue-at-readiness; everything else
    issues on the committed schedule.  Rule-table strategies
    (parallel/rules.py) carry the discipline as DATA in the table —
    ``meta["discipline"]`` — which takes precedence: the strategy
    triple is mesh + rule table + issue discipline."""
    meta = meta or {}
    if meta.get("discipline") in ("sync", "overlap"):
        return meta["discipline"]
    return "overlap" if (meta.get("overlap") or meta.get("prefetch")) else "sync"
