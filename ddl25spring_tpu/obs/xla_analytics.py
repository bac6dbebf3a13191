"""Compile-time XLA analytics: collective accounting from optimized HLO.

Runtime telemetry (:mod:`ddl25spring_tpu.obs`) only speaks when a device
is reachable.  This module extracts the perf facts that do NOT need
hardware: lower a strategy's train step under a fake
``make_mesh`` on CPU, walk the *optimized* HLO of the compiled program,
and account for every cross-device collective — kind, payload bytes,
mesh axes (recovered from replica groups), and **execution count**
(collectives inside ``lax.scan``/``while`` bodies multiply by the loop's
``known_trip_count``, which XLA annotates on optimized while ops).
Paired with ``compiled.memory_analysis()`` / ``cost_analysis()`` (via
:mod:`ddl25spring_tpu.utils.compat`, which evens out what backends
report), one :func:`analyze_compiled` call yields the collective
inventory, a peak-HBM estimate, FLOP totals, and roofline projections
per chip spec — all on a machine with no accelerator at all.

The strategy registry at the bottom maps each parallelism strategy the
framework implements (DP, ZeRO-1/2/3, pipeline, het-pipeline, TP, SP,
EP) to the ``describe()`` hook its ``parallel/`` module exposes; a
strategy's ``describe()`` returns the lowerable step + example inputs +
its *analytic* collective signature, so :func:`check_signature` can pin
"plain DP is exactly grad-bytes of all-reduce over the data axis and
nothing else" as a CPU-green tier-1 test — any refactor that silently
adds a stray all-gather or breaks fusion fails CI before it ever
reaches a TPU (the comms-regression pinning contract; see
``tests/test_xla_analytics.py`` and ``tools/comms_report.py``).
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Any

# ------------------------------------------------------------------ HLO text

# bytes per element for the HLO primitive types that can appear in
# collective result shapes
_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "tf32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
}

_COLLECTIVE_KINDS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
    "collective-broadcast",
)

# `%all-reduce.5 = f32[16,4]{1,0} all-reduce(...)`: the opcode is the bare
# token before `(`; operand *references* are `%`-prefixed, so `(?<!%)`
# keeps `all-reduce(f32[] %all-reduce.3)` from double-counting.  Async
# pairs count at `-start` and never at `-done`.
_COLLECTIVE_RE = re.compile(
    r"(?<![%\w])(" + "|".join(_COLLECTIVE_KINDS) + r")(-start)?\("
)

_SHAPE_RE = re.compile(r"\b([a-z]\w*)\[([\d,]*)\]")

_COMP_HEADER_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->")

# call-site attributes that transfer control to another computation
_CALLEE_RE = re.compile(
    r"(?:body|condition|to_apply|true_computation|false_computation)="
    r"%?([\w.\-]+)"
)
_BRANCHES_RE = re.compile(r"branches=\{([^}]*)\}")
_TRIP_RE = re.compile(r'known_trip_count[\\"=:{\s]+n[\\"=:\s]+(\d+)')

_SHARDING_TILE_RE = re.compile(r"devices=\[([\d,]+)\]")
_LAST_TILE_DIMS_RE = re.compile(r"last_tile_dims=\{([^}]*)\}")


def _shape_bytes(type_str: str) -> int:
    """Total bytes of an HLO result type string (handles tuples by summing
    every ``dtype[dims]`` group it contains)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        elems = math.prod(int(d) for d in dims.split(",") if d) if dims else 1
        total += elems * _DTYPE_BYTES[dtype]
    return total


@dataclass
class _Comp:
    name: str
    lines: list[str] = field(default_factory=list)
    is_entry: bool = False


def _split_computations(hlo_text: str) -> tuple[dict[str, _Comp], str | None]:
    """Split optimized-HLO text into named computations.  Returns
    ``(computations, entry_name)``."""
    comps: dict[str, _Comp] = {}
    cur: _Comp | None = None
    entry = None
    for raw in hlo_text.splitlines():
        line = raw.strip()
        m = _COMP_HEADER_RE.match(line)
        if m and line.endswith("{"):
            cur = _Comp(m.group(2), is_entry=bool(m.group(1)))
            comps[cur.name] = cur
            if cur.is_entry:
                entry = cur.name
            continue
        if line == "}":
            cur = None
            continue
        if cur is not None and "=" in line:
            cur.lines.append(line)
    return comps, entry


def _execution_multipliers(
    comps: dict[str, _Comp], entry: str | None
) -> tuple[dict[str, int], dict[str, bool]]:
    """How many times each computation executes per entry invocation.

    Whiles multiply their body/condition by the optimizer-annotated
    ``known_trip_count``; calls/reducers/branches inherit the caller's
    count (a conditional branch runs *at most* once per visit — counted
    as once, the upper bound the signature pins care about).  Returns
    ``(multiplier, trip_known)`` — ``trip_known[c]`` is False anywhere a
    while without a recoverable trip count encloses ``c``.
    """
    mult: dict[str, int] = {}
    known: dict[str, bool] = {}
    if entry is None:
        return mult, known

    def visit(name: str, m: int, k: bool) -> None:
        comp = comps.get(name)
        if comp is None:
            return
        mult[name] = mult.get(name, 0) + m
        known[name] = known.get(name, True) and k
        for line in comp.lines:
            callees = _CALLEE_RE.findall(line)
            br = _BRANCHES_RE.search(line)
            if br:
                callees += [c.strip().lstrip("%") for c in br.group(1).split(",")]
            if not callees:
                continue
            if "= " in line and " while(" in line:
                t = _TRIP_RE.search(line)
                trip = int(t.group(1)) if t else 1
                for c in callees:
                    visit(c, m * trip, k and t is not None)
            else:
                for c in callees:
                    visit(c, m, k)

    visit(entry, 1, True)
    return mult, known


def _op_name_of_line(line: str) -> str | None:
    """The ``%name`` an HLO instruction line defines (sans ``%``)."""
    m = re.match(r"(?:ROOT\s+)?%([\w.\-]+)\s*=", line)
    return m.group(1) if m else None


def _operand_names(line: str, open_paren: int) -> list[str]:
    """``%``-operand references inside the balanced-paren argument list
    starting at ``line[open_paren]`` (skips attribute references like
    ``to_apply=%add`` that sit after the closing paren)."""
    depth = 0
    end = len(line)
    for i in range(open_paren, len(line)):
        c = line[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    return re.findall(r"%([\w.\-]+)", line[open_paren:end])


def parse_op_defs(hlo_text: str) -> dict[str, dict[str, dict[str, Any]]]:
    """Per-computation def table: ``{comp_name: {op_name: def}}`` where
    each def is ``{"opcode", "type", "operands", "root", "line"}``.

    This is the substrate the hazard rules walk — e.g. "is this f32
    collective fed by a bf16 ``convert``" (H004) or "does an all-gather
    feed a reduce-scatter" (H002) are producer-chain questions over
    these defs (:mod:`ddl25spring_tpu.analysis.engine`).
    """
    comps, _entry = _split_computations(hlo_text)
    out: dict[str, dict[str, dict[str, Any]]] = {}
    for comp in comps.values():
        defs: dict[str, dict[str, Any]] = {}
        for line in comp.lines:
            name = _op_name_of_line(line)
            if name is None:
                continue
            rhs = line.split("=", 1)[1].strip()
            # result type: a tuple type spans balanced parens; otherwise
            # it's the first space-free token
            if rhs.startswith("("):
                depth = 0
                tend = 0
                for i, c in enumerate(rhs):
                    if c == "(":
                        depth += 1
                    elif c == ")":
                        depth -= 1
                        if depth == 0:
                            tend = i + 1
                            break
                type_str, rest = rhs[:tend], rhs[tend:].lstrip()
            else:
                type_str, _, rest = rhs.partition(" ")
            om = re.match(r"([\w.\-]+)\(", rest)
            if not om:
                continue
            opcode = om.group(1)
            paren = line.find(rest, line.index("=")) + om.end() - 1
            defs[name] = {
                "opcode": opcode,
                "type": type_str,
                "operands": _operand_names(line, paren),
                "root": line.startswith("ROOT "),
                "line": line,
            }
        out[comp.name] = defs
    return out


def _sharding_attr_of_line(line: str) -> str | None:
    """The brace-balanced body of a ``sharding={...}`` op attribute
    (``last_tile_dims={...}`` nests braces, so a ``[^}]*`` regex would
    truncate it)."""
    start = line.find("sharding={")
    if start < 0:
        return None
    i = line.index("{", start)
    depth = 0
    for j in range(i, len(line)):
        if line[j] == "{":
            depth += 1
        elif line[j] == "}":
            depth -= 1
            if depth == 0:
                return line[i + 1:j]
    return None


def parse_sharding(attr: str | None) -> dict[str, Any] | None:
    """Structured view of one HLO ``sharding=`` annotation body — the
    substrate the sharding-flow verifier walks
    (:mod:`ddl25spring_tpu.analysis.shard_flow`).

    Returns ``{"raw", "replicated", "maximal", "manual", "tile"``
    (the ``devices=[...]`` tile-assignment dims), ``"trailing_subgroups"``
    (trailing tile dims that replicate/are manual rather than partition
    data dims), ``"partitioned_dims"`` (data-dim indices with >1
    partition) and ``"partitions"`` (per partitioned dim, its factor)}``
    — or None when the op carries no annotation.  A rank change between
    the global and the per-device local shape never matters here: the
    tile dims index GLOBAL data dimensions.
    """
    if attr is None:
        return None
    out: dict[str, Any] = {
        "raw": attr,
        "replicated": attr.strip() == "replicated",
        "maximal": attr.strip().startswith("maximal"),
        "manual": attr.strip() == "manual",
        "tile": None,
        "trailing_subgroups": 0,
        "partitioned_dims": [],
        "partitions": {},
    }
    m = _SHARDING_TILE_RE.search(attr)
    if not m:
        return out
    tile = [int(x) for x in m.group(1).split(",")]
    out["replicated"] = False
    trailing = 0
    ltd = _LAST_TILE_DIMS_RE.search(attr)
    if ltd:
        trailing = len([x for x in ltd.group(1).split(",") if x.strip()])
    elif "last_tile_dim_replicate" in attr:
        trailing = 1
    out["tile"] = tile
    out["trailing_subgroups"] = trailing
    data_dims = tile[: len(tile) - trailing] if trailing else tile
    out["partitioned_dims"] = [
        i for i, d in enumerate(data_dims) if d > 1
    ]
    out["partitions"] = {
        i: d for i, d in enumerate(data_dims) if d > 1
    }
    return out


def parse_input_output_aliases(hlo_text: str) -> list[dict[str, Any]]:
    """Entries of the module-level ``input_output_alias`` table — the
    buffers XLA reuses in place (donated params/opt-state).  Each entry:
    ``{"output_index": [...], "param_number": int, "param_index": [...],
    "kind": "may-alias"|"must-alias"}``.  Empty list = nothing donated.
    """
    start = hlo_text.find("input_output_alias={")
    if start < 0:
        return []
    i = hlo_text.index("{", start)
    depth = 0
    for j in range(i, len(hlo_text)):
        if hlo_text[j] == "{":
            depth += 1
        elif hlo_text[j] == "}":
            depth -= 1
            if depth == 0:
                break
    block = hlo_text[i:j + 1]
    out = []
    for m in re.finditer(
        r"\{([\d,\s]*)\}:\s*\((\d+)\s*,\s*\{([\d,\s]*)\}\s*,?\s*([\w\-]*)\)",
        block,
    ):
        out.append({
            "output_index": [int(x) for x in m.group(1).split(",") if x.strip()],
            "param_number": int(m.group(2)),
            "param_index": [int(x) for x in m.group(3).split(",") if x.strip()],
            "kind": m.group(4) or "may-alias",
        })
    return out


def parse_entry_parameters(hlo_text: str) -> list[dict[str, Any]]:
    """The entry computation's parameters: ``{"number", "name", "bytes",
    "type", "arg"}`` per input buffer, where ``arg`` is the jax-level
    argument path XLA records in the op metadata (``params['w1']``,
    ``opt_state[0]...``, ``batch[0]``) when available — the names the
    donation-miss rule (H005) reports.  ``sharding`` is the parsed
    ``sharding=`` annotation (:func:`parse_sharding`; None when the
    parameter carries none) — the per-program layout facts the
    sharding-flow verifier's cross-program contract checks walk
    (:mod:`ddl25spring_tpu.analysis.shard_flow`, rule H013)."""
    comps, entry = _split_computations(hlo_text)
    if entry is None:
        return []
    out = []
    for line in comps[entry].lines:
        m = re.match(
            r"(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.+?)\s*parameter\((\d+)\)", line
        )
        if not m:
            continue
        arg = re.search(r'op_name="([^"]+)"', line)
        out.append({
            "number": int(m.group(3)),
            "name": m.group(1),
            "bytes": _shape_bytes(m.group(2)),
            "type": m.group(2),
            "arg": arg.group(1) if arg else None,
            "sharding": parse_sharding(_sharding_attr_of_line(line)),
        })
    out.sort(key=lambda p: p["number"])
    return out


def _parse_groups(line: str) -> list[list[int]] | None:
    """Device groups of a collective op line.  Handles the explicit
    ``replica_groups={{0,1},{2,3}}`` form and (best-effort) the newer
    iota form ``replica_groups=[2,4]<=[8]`` / ``...<=[8]T(1,0)``."""
    m = re.search(r"replica_groups=\{(\{[\d,{}\s]*\})\}", line)
    if m:
        return [
            [int(x) for x in g.split(",") if x.strip()]
            for g in re.findall(r"\{([\d,\s]*)\}", m.group(1))
        ]
    m = re.search(
        r"replica_groups=\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?", line
    )
    if m:
        group_shape = [int(x) for x in m.group(1).split(",")]
        reshape = [int(x) for x in m.group(2).split(",")]
        total = math.prod(reshape)
        ids = list(range(total))
        try:
            import numpy as np

            arr = np.arange(total).reshape(reshape)
            if m.group(3):
                arr = arr.transpose([int(x) for x in m.group(3).split(",")])
            arr = arr.reshape(group_shape)
            return [list(map(int, row)) for row in arr]
        except Exception:  # noqa: BLE001 — malformed iota: groups unknown
            return [ids]
    return None


def _parse_pairs(line: str) -> list[tuple[int, int]] | None:
    m = re.search(r"source_target_pairs=\{([\d,{}\s]*)\}", line)
    if not m:
        return None
    return [
        tuple(int(x) for x in p.split(","))
        for p in re.findall(r"\{(\d+,\d+)\}", m.group(1))
    ]


def _mesh_coords(mesh) -> dict[int, tuple[int, ...]]:
    """device id -> mesh coordinates."""
    import numpy as np

    out = {}
    for coords in np.ndindex(*mesh.devices.shape):
        out[int(mesh.devices[coords].id)] = tuple(int(c) for c in coords)
    return out


def _axes_of_groups(groups, mesh) -> list[str]:
    """Mesh axes a collective communicates over: the axes whose coordinate
    varies within any device group (robust to any group ordering)."""
    coords = _mesh_coords(mesh)
    varying: set[int] = set()
    for g in groups:
        gc = [coords.get(d) for d in g]
        if any(c is None for c in gc) or len(gc) < 2:
            continue
        for dim in range(len(mesh.axis_names)):
            if len({c[dim] for c in gc}) > 1:
                varying.add(dim)
    return [mesh.axis_names[d] for d in sorted(varying)]


def _axes_of_pairs(pairs, mesh) -> list[str]:
    coords = _mesh_coords(mesh)
    varying: set[int] = set()
    for s, t in pairs:
        cs, ct = coords.get(s), coords.get(t)
        if cs is None or ct is None:
            continue
        for dim in range(len(mesh.axis_names)):
            if cs[dim] != ct[dim]:
                varying.add(dim)
    return [mesh.axis_names[d] for d in sorted(varying)]


def _wire_bytes(kind: str, result_bytes: int, group_size: int | None) -> int:
    """Per-device ICI traffic estimate for one execution, from the result
    bytes and participant count (ring-algorithm accounting; the numbers
    the roofline projection feeds on).  ``group_size`` None -> assume the
    worst case factor 2 for all-reduce, 1 otherwise."""
    n = group_size or 0
    if kind == "all-reduce":
        # ring all-reduce: reduce-scatter + all-gather, 2(n-1)/n x payload
        return int(2 * result_bytes * ((n - 1) / n if n > 1 else 1))
    if kind == "all-gather":
        # result is the gathered buffer; each device receives (n-1)/n of it
        return int(result_bytes * ((n - 1) / n if n > 1 else 1))
    if kind == "reduce-scatter":
        # result is the scattered shard; each device sends (n-1) shards
        return int(result_bytes * (n - 1 if n > 1 else 1))
    if kind == "all-to-all":
        # result bytes re-partitioned: (n-1)/n of it crosses the wire
        return int(result_bytes * ((n - 1) / n if n > 1 else 1))
    # collective-permute / broadcast: one payload per hop
    return int(result_bytes)


def parse_hlo_collectives(hlo_text: str, mesh=None) -> list[dict[str, Any]]:
    """Extract every collective op from optimized-HLO text.

    Returns one record per op *site*: ``{kind, result_bytes, dtype``
    (primary element type of the result), ``count``
    (executions per call, loop trip counts folded in), ``trip_known,
    axes, group_size, wire_bytes`` (per execution), ``source, name,
    computation, operands, pairs, async}``.  ``async`` is True for
    ``-start``/``-done`` pairs (the op overlaps with compute); ``pairs``
    carries a collective-permute's raw source-target pairs; ``name`` /
    ``computation`` / ``operands`` anchor the op in the def tables of
    :func:`parse_op_defs` for the hazard rules.  ``axes`` needs ``mesh``
    (a ``jax.sharding.Mesh`` whose device ids match the compiled
    program); without it axes are ``None``.
    """
    comps, entry = _split_computations(hlo_text)
    mult, known = _execution_multipliers(comps, entry)
    out: list[dict[str, Any]] = []
    for comp in comps.values():
        m = mult.get(comp.name, 0)
        if m == 0:
            continue  # dead computation (not reachable from entry)
        for line in comp.lines:
            cm = _COLLECTIVE_RE.search(line)
            if not cm:
                continue
            kind = cm.group(1)
            type_str = line.split("=", 1)[1].split(cm.group(0), 1)[0]
            result_bytes = _shape_bytes(type_str)
            # primary element dtype of the result
            dm = _SHAPE_RE.search(type_str)
            dtype = dm.group(1) if dm and dm.group(1) in _DTYPE_BYTES else None
            groups = _parse_groups(line)
            pairs = _parse_pairs(line)
            axes = None
            group_size = None
            if groups:
                group_size = max(len(g) for g in groups)
                if mesh is not None:
                    axes = _axes_of_groups(groups, mesh)
            elif pairs is not None:
                # permute "group" = the cycle length; use the pair count
                # per device ring (participants = distinct sources)
                group_size = len({s for s, _ in pairs}) or None
                if mesh is not None:
                    axes = _axes_of_pairs(pairs, mesh)
            src = re.search(r'source_file="([^"]+)".*?source_line=(\d+)', line)
            open_paren = line.index("(", cm.start())
            out.append({
                "kind": kind,
                "result_bytes": result_bytes,
                "dtype": dtype,
                "count": m,
                "trip_known": known.get(comp.name, True),
                "axes": axes,
                "group_size": group_size,
                "wire_bytes": _wire_bytes(kind, result_bytes, group_size),
                "source": f"{src.group(1)}:{src.group(2)}" if src else None,
                "name": _op_name_of_line(line),
                "computation": comp.name,
                "operands": _operand_names(line, open_paren),
                "pairs": pairs,
                "async": bool(cm.group(2)),
            })
    return out


# ------------------------------------------------------------- report build


def collective_totals(ops: list[dict[str, Any]]) -> dict[str, dict[str, Any]]:
    """Aggregate op-site records into per-kind totals: executed count,
    payload bytes and wire bytes across all executions."""
    tot: dict[str, dict[str, Any]] = {}
    for op in ops:
        t = tot.setdefault(op["kind"], {
            "count": 0, "result_bytes": 0, "wire_bytes": 0, "sites": 0,
        })
        t["sites"] += 1
        t["count"] += op["count"]
        t["result_bytes"] += op["result_bytes"] * op["count"]
        t["wire_bytes"] += op["wire_bytes"] * op["count"]
    return tot


def totals_by_axis(ops: list[dict[str, Any]]) -> dict[str, dict[str, Any]]:
    """Per-mesh-axis collective totals (an op over several axes counts
    toward each; axis ``"?"`` collects ops whose groups were unmappable)."""
    out: dict[str, dict[str, Any]] = {}
    for op in ops:
        for ax in (op["axes"] or ["?"]):
            t = out.setdefault(ax, {})
            k = t.setdefault(op["kind"], {"count": 0, "wire_bytes": 0})
            k["count"] += op["count"]
            k["wire_bytes"] += op["wire_bytes"] * op["count"]
    return out


def analyze_compiled(
    compiled: Any,
    mesh=None,
    meta: dict[str, Any] | None = None,
    hlo_text: str | None = None,
) -> dict[str, Any]:
    """Full compile-time report for one compiled XLA program: collective
    inventory (+ per-axis totals), memory footprint, FLOP totals, and
    roofline projections per chip spec.  Works on any backend that can
    compile the program — the intended use is CPU with a fake mesh."""
    from ddl25spring_tpu.utils.compat import (
        compiled_cost_analysis,
        compiled_memory_stats,
    )

    if hlo_text is None:
        hlo_text = compiled.as_text()
    ops = parse_hlo_collectives(hlo_text, mesh)
    aliases = parse_input_output_aliases(hlo_text)
    entry_params = parse_entry_parameters(hlo_text)
    memory = compiled_memory_stats(compiled)
    cost = compiled_cost_analysis(compiled)
    flops = float(cost.get("flops", 0.0)) if cost else None
    bytes_accessed = (
        float(cost.get("bytes accessed", 0.0)) if cost else None
    )
    totals = collective_totals(ops)
    report: dict[str, Any] = {
        "collectives": {
            "ops": ops,
            "totals": totals,
            "by_axis": totals_by_axis(ops),
        },
        "memory": memory,
        # buffer-donation accounting: the bytes the compiled program
        # aliases in place instead of double-buffering (0 = undonated);
        # aliased_params are the entry-parameter numbers the alias table
        # covers (the donation-miss hazard rule diffs these against the
        # donatable inputs — analysis/rules.py H005)
        "donation": {
            "hbm_saved_bytes": (memory or {}).get("alias_size_in_bytes", 0),
            "aliased_params": sorted({a["param_number"] for a in aliases}),
        },
        "entry_params": entry_params,
        "flops": flops if flops and flops > 0 else None,
        "bytes_accessed": bytes_accessed,
        "projection": roofline_projection(
            flops,
            bytes_accessed,
            sum(t["wire_bytes"] for t in totals.values()),
        ),
    }
    if meta:
        report["meta"] = meta
    # whole-program schedule analysis (analysis/sched.py): per-collective
    # overlap-slack windows, participant-stream safety, and the
    # per-strategy static_overlap_bound — computed once here and reused
    # by the lint context and the report tables;
    # sched breakage degrades to an error note, never costs the report
    try:
        from ddl25spring_tpu.analysis import sched as sched_mod

        report["sched"] = sched_mod.analyze_schedule(
            hlo_text, mesh, ops=ops,
            discipline=sched_mod.discipline_of(meta),
        )
    except Exception as e:  # noqa: BLE001 — degrade per report
        report["sched"] = {"error": f"{type(e).__name__}: {e}"}
    return report


def roofline_projection(
    flops: float | None,
    hbm_bytes: float | None,
    ici_bytes: float,
    chips: list[str] | None = None,
    specs: dict[str, dict[str, float]] | None = None,
) -> dict[str, Any]:
    """Project one step's time/MFU onto real chip specs from the three
    compile-time resource totals: FLOPs (MXU), bytes accessed (HBM), and
    collective wire bytes (ICI).  The projection assumes no overlap — a
    deliberate upper bound on step time; its ``bound`` field names the
    roofline the program would sit on.  ``specs`` overlays/extends
    :data:`~ddl25spring_tpu.utils.flops.CHIP_SPECS` (how
    ``tools/resnet_roofline.py`` derates a peak by MXU occupancy)."""
    from ddl25spring_tpu.utils.flops import CHIP_SPECS

    table: dict[str, dict[str, float]] = dict(CHIP_SPECS)
    if specs:
        table.update(specs)
    out: dict[str, Any] = {}
    if not flops:
        return out
    for kind in (chips or list(table)):
        spec = table.get(kind)
        if not spec:
            continue
        # a peak-only spec (a chip in PEAK_BF16_FLOPS with no full
        # CHIP_SPECS entry, e.g. v2/v3) still projects: an unknown
        # bandwidth simply doesn't bound the step
        t_compute = flops / spec["peak_bf16_flops"]
        hbm_bw = spec.get("hbm_bytes_per_s")
        ici_bw = spec.get("ici_bytes_per_s")
        t_hbm = (hbm_bytes or 0.0) / hbm_bw if hbm_bw else 0.0
        t_ici = ici_bytes / ici_bw if ici_bw else 0.0
        t_step = max(t_compute, t_hbm, t_ici)
        bound = {t_compute: "compute", t_hbm: "hbm", t_ici: "ici"}[t_step]
        out[kind] = {
            "t_compute_s": t_compute,
            "t_hbm_s": t_hbm,
            "t_ici_s": t_ici,
            "projected_step_s": t_step,
            "bound": bound,
            "projected_mfu": t_compute / t_step if t_step > 0 else None,
        }
    return out


# ------------------------------------------------------- signature checking


def check_signature(
    report: dict[str, Any], expected: dict[str, Any]
) -> list[str]:
    """Evaluate a strategy's analytic collective signature against its
    measured compile report.  Returns human-readable violations (empty =
    signature holds).  ``expected`` schema (all keys optional)::

        {
          "forbidden": ["collective-permute", ...],   # kinds that must not appear
          "scalar_bytes": 64,          # per-execution payload <= this is "scalar"
                                       #   noise, exempt from `forbidden`
          "<kind>": {
             "count": 5,               # exact executed count
             "min_count": 1, "max_count": 8,
             "min_bytes": B, "max_bytes": B2,   # total payload bytes
             "axes": ["data"],         # every op of the kind groups only here
          },
          "memory": {                  # peak-HBM budget (memory_analysis)
             "max_peak_hbm_bytes": B,
          },
          "donation": {                # buffer-donation savings: the bytes
             "min_saved_bytes": B,     #   aliased in place of fresh output
          },                           #   buffers (alias_size_in_bytes)
        }
    """
    viols: list[str] = []
    mem = report.get("memory") or {}
    want_mem = expected.get("memory")
    if want_mem and "max_peak_hbm_bytes" in want_mem:
        peak = mem.get("peak_hbm_bytes")
        if peak is None:
            viols.append("memory: no peak-HBM estimate on this backend, "
                         "cannot check the budget")
        elif peak > want_mem["max_peak_hbm_bytes"]:
            viols.append(
                f"memory: peak HBM {peak} B exceeds the "
                f"{want_mem['max_peak_hbm_bytes']} B budget"
            )
    want_don = expected.get("donation")
    if want_don and "min_saved_bytes" in want_don:
        if "alias_size_in_bytes" not in mem:
            # no memory stats != zero bytes donated: report the missing
            # instrument, not a phantom donation regression
            viols.append("donation: no aliasing stats on this backend, "
                         "cannot check the donation floor")
        elif mem["alias_size_in_bytes"] < want_don["min_saved_bytes"]:
            viols.append(
                f"donation: only {mem['alias_size_in_bytes']} B aliased "
                f"in place, expected >= {want_don['min_saved_bytes']} B — "
                "a train step stopped donating its params/opt-state "
                "buffers"
            )
    ops = report["collectives"]["ops"]
    totals = report["collectives"]["totals"]
    scalar = int(expected.get("scalar_bytes", 0))
    for kind in expected.get("forbidden", ()):
        bad = [
            o for o in ops
            if o["kind"] == kind and o["result_bytes"] > scalar
        ]
        if bad:
            viols.append(
                f"forbidden collective {kind}: {len(bad)} op site(s), "
                f"e.g. {bad[0]['result_bytes']} B at {bad[0]['source']}"
            )
    for kind, want in expected.items():
        if kind in ("forbidden", "scalar_bytes", "memory", "donation") or (
            not isinstance(want, dict)
        ):
            continue
        kops = [o for o in ops if o["kind"] == kind]
        count = sum(o["count"] for o in kops)
        tbytes = totals.get(kind, {}).get("result_bytes", 0)
        if "count" in want and count != want["count"]:
            viols.append(f"{kind}: expected exactly {want['count']} "
                         f"executions, measured {count}")
        if "min_count" in want and count < want["min_count"]:
            viols.append(f"{kind}: expected >= {want['min_count']} "
                         f"executions, measured {count}")
        if "max_count" in want and count > want["max_count"]:
            viols.append(f"{kind}: expected <= {want['max_count']} "
                         f"executions, measured {count}")
        if "min_bytes" in want and tbytes < want["min_bytes"]:
            viols.append(f"{kind}: expected >= {want['min_bytes']} total "
                         f"payload bytes, measured {tbytes}")
        if "max_bytes" in want and tbytes > want["max_bytes"]:
            viols.append(f"{kind}: expected <= {want['max_bytes']} total "
                         f"payload bytes, measured {tbytes}")
        if "axes" in want:
            allowed = set(want["axes"])
            for o in kops:
                if o["result_bytes"] <= scalar:
                    continue
                if o["axes"] is not None and not set(o["axes"]) <= allowed:
                    viols.append(
                        f"{kind}: op at {o['source']} groups over "
                        f"{o['axes']}, expected a subset of "
                        f"{sorted(allowed)}"
                    )
    return viols


# -------------------------------------------------------- strategy registry

# name -> (module path, ordered mesh axis names, default mesh sizes).
# Every module's `describe(mesh, **kw)` returns
#   {"fn": lowerable, "args": example inputs, "meta": {...},
#    "expected": signature dict for check_signature}
# — the registry hook the tentpole asks each parallel builder to expose.
STRATEGIES: dict[str, dict[str, Any]] = {
    "dp": {
        "module": "ddl25spring_tpu.parallel.dp",
        "axes": ("data",), "default_mesh": (4,),
    },
    "dp-overlap": {
        # backward-overlapped gradient buckets: each bucket's all-reduce
        # is emitted by a per-bucket custom_vjp bwd rule inside the
        # backward, buckets planned in backward-readiness order
        # (parallel/bucketing.overlap_wrap) — same signature as dp,
        # bitwise-equal params, pinned in tests/test_bucketing.py
        "module": "ddl25spring_tpu.parallel.dp",
        "axes": ("data",), "default_mesh": (4,),
        "kwargs": {"overlap": True},
    },
    "zero1": {
        "module": "ddl25spring_tpu.parallel.zero",
        "axes": ("data",), "default_mesh": (4,), "kwargs": {"stage": 1},
    },
    "zero2": {
        "module": "ddl25spring_tpu.parallel.zero",
        "axes": ("data",), "default_mesh": (4,), "kwargs": {"stage": 2},
    },
    "zero3": {
        "module": "ddl25spring_tpu.parallel.zero",
        "axes": ("data",), "default_mesh": (4,), "kwargs": {"stage": 3},
    },
    "zero3-prefetch": {
        # the scanned-LLaMA double-buffered gather-prefetch step: the
        # layer i+1 all-gather issues before layer i's compute, inside a
        # while loop whose trip count the analytics read off the HLO
        "module": "ddl25spring_tpu.parallel.zero",
        "axes": ("data",), "default_mesh": (4,),
        "kwargs": {"stage": 3, "prefetch": True},
    },
    # backward-overlapped ZeRO variants: the gradient collective (stage
    # 1 all-reduce / stage 2 reduce-scatter / stage 3 bwd reduce-
    # scatter) fires inside the backward per backward-readiness bucket
    "zero1-overlap": {
        "module": "ddl25spring_tpu.parallel.zero",
        "axes": ("data",), "default_mesh": (4,),
        "kwargs": {"stage": 1, "overlap": True},
    },
    "zero2-overlap": {
        "module": "ddl25spring_tpu.parallel.zero",
        "axes": ("data",), "default_mesh": (4,),
        "kwargs": {"stage": 2, "overlap": True},
    },
    "zero3-overlap": {
        "module": "ddl25spring_tpu.parallel.zero",
        "axes": ("data",), "default_mesh": (4,),
        "kwargs": {"stage": 3, "overlap": True},
    },
    "pipeline": {
        "module": "ddl25spring_tpu.parallel.pipeline",
        "axes": ("data", "stage"), "default_mesh": (1, 2),
    },
    "het_pipeline": {
        "module": "ddl25spring_tpu.parallel.het_pipeline",
        "axes": ("data", "stage"), "default_mesh": (1, 2),
    },
    "tp": {
        "module": "ddl25spring_tpu.parallel.tp",
        "axes": ("data", "model"), "default_mesh": (1, 2),
    },
    "sp": {
        "module": "ddl25spring_tpu.parallel.sp",
        "axes": ("data", "seq"), "default_mesh": (1, 2),
    },
    "ep": {
        "module": "ddl25spring_tpu.parallel.ep",
        "axes": ("expert",), "default_mesh": (4,),
    },
    # the serving programs (ddl25spring_tpu/serve/engine.py): TP decode
    # tick and prefill over the paged KV cache — forward-only inference
    # steps whose pinned signature is "row-parallel all-reduce over the
    # model axis ONLY" (no permutes/gathers/scatters: serve keeps the
    # vocab replicated), with HBM budgets like every training strategy
    "serve-decode": {
        "module": "ddl25spring_tpu.serve.engine",
        "axes": ("model",), "default_mesh": (2,),
        "kwargs": {"program": "decode"},
    },
    "serve-prefill": {
        "module": "ddl25spring_tpu.serve.engine",
        "axes": ("model",), "default_mesh": (2,),
        "kwargs": {"program": "prefill"},
    },
    # the TP-sharded serving trio (PR 18): the same decode/prefill
    # programs under the tightened per-chip claim — 64 KiB peak-HBM
    # budgets that only hold because the pool's head dim and the
    # Megatron splits divide residency by tp (one chip measures
    # ~75 KiB), all-reduce payloads pinned byte-exact (activation-
    # sized, UNCHANGED by tp) — and the ZeRO-3 weight-streaming decode,
    # whose double-buffered per-layer gather is count-pinned
    # (n_layers x n_buckets) with params/n + one transient layer
    # resident
    "serve-decode-tp": {
        "module": "ddl25spring_tpu.serve.engine",
        "axes": ("model",), "default_mesh": (2,),
        "kwargs": {"program": "decode", "per_chip": True},
    },
    "serve-prefill-tp": {
        "module": "ddl25spring_tpu.serve.engine",
        "axes": ("model",), "default_mesh": (2,),
        "kwargs": {"program": "prefill", "per_chip": True},
    },
    "serve-decode-zero3stream": {
        "module": "ddl25spring_tpu.serve.engine",
        "axes": ("model",), "default_mesh": (2,),
        "kwargs": {"program": "decode", "weight_stream": True},
    },
    # the speculative-decoding pair (PR 13, serve/spec.py): the tiny-
    # LLaMA drafter's k-token proposal scan over its OWN paged pool and
    # the target's single width-(k+1) verify pass — all-reduce-only
    # signatures whose counts differ by exactly the draft/target depth
    # ratio (the compile-time half of the virtual clock's FLOP-ratio
    # pricing), pools head-dim-sharded under the same H013 contract
    "serve-draft": {
        "module": "ddl25spring_tpu.serve.spec",
        "axes": ("model",), "default_mesh": (2,),
        "kwargs": {"program": "draft"},
    },
    "serve-verify": {
        "module": "ddl25spring_tpu.serve.spec",
        "axes": ("model",), "default_mesh": (2,),
        "kwargs": {"program": "verify"},
    },
    # the partition-rule-engine variants (PR 12): the strategy is DATA —
    # a mesh shape + ordered regex rule table + issue discipline
    # (parallel/rules.py) — lowered through the generic RulePartitioner
    # and pinned bitwise-identical to the bespoke dp / zero3 builders
    # (tests/test_shard_flow.py); their tables are proven covered (every
    # param leaf matched exactly once, no shadowed rule) by the
    # sharding-flow verifier's H012 (analysis/shard_flow.py)
    "dp-rules": {
        "module": "ddl25spring_tpu.parallel.rules",
        "axes": ("data",), "default_mesh": (4,),
        "kwargs": {"table": "dp"},
    },
    "zero3-rules": {
        "module": "ddl25spring_tpu.parallel.rules",
        "axes": ("data",), "default_mesh": (4,),
        "kwargs": {"table": "zero3"},
    },
}


def strategy_mesh(name: str, sizes: tuple[int, ...] | None = None):
    """Build the fake CPU mesh a strategy's describe() runs under.
    ``sizes`` maps positionally onto the strategy's axis names; extra
    trailing dims fold into the last axis (so ``zero3 --mesh 2x4`` means
    an 8-way data axis)."""
    import jax

    from ddl25spring_tpu.utils.mesh import make_mesh

    info = STRATEGIES[name]
    axes = info["axes"]
    sizes = tuple(sizes or info["default_mesh"])
    if len(sizes) > len(axes):
        folded = sizes[: len(axes) - 1] + (
            math.prod(sizes[len(axes) - 1:]),
        )
        sizes = folded
    elif len(sizes) < len(axes):
        sizes = (1,) * (len(axes) - len(sizes)) + sizes
    kw = {ax: s for ax, s in zip(axes, sizes) if s > 1}
    if not kw:  # degenerate 1-device request: keep the last axis explicit
        kw = {axes[-1]: sizes[-1]}
    devices = jax.devices("cpu")
    need = math.prod(kw.values())
    if len(devices) < need:
        raise RuntimeError(
            f"strategy {name!r} mesh {kw} needs {need} CPU devices, have "
            f"{len(devices)}; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} before "
            "importing jax"
        )
    return make_mesh(devices[:need], **kw)


def describe_strategy(
    name: str, mesh=None, **overrides: Any
) -> dict[str, Any]:
    """Resolve a strategy name to its module's ``describe()`` output."""
    import importlib

    if name not in STRATEGIES:
        raise KeyError(
            f"unknown strategy {name!r}; known: {sorted(STRATEGIES)}"
        )
    info = STRATEGIES[name]
    if mesh is None:
        mesh = strategy_mesh(name)
    mod = importlib.import_module(info["module"])
    kw = dict(info.get("kwargs", {}), **overrides)
    return mod.describe(mesh, **kw)


# The signature pins hold the collectives the FRAMEWORK issues — bucket
# count, issue point in the backward — so the one backend pass that
# rewrites exactly that is switched off for these compiles: jax 0.9.0's
# CPU pipeline folds every all-reduce of a step (the scalar loss
# reduction included) into one tuple op at the end, which makes
# dp-overlap's program identical to dp's.  A chip's compiler has its own
# combiner and thresholds; what it does there is a chip measurement.
_AS_ISSUED = {"xla_disable_hlo_passes": "cpu-all-reduce-combiner"}


def compile_strategy(
    name: str,
    mesh_sizes: tuple[int, ...] | None = None,
    lint: bool = True,
    keep_hlo: bool = False,
    **overrides: Any,
) -> dict[str, Any]:
    """Lower + compile one strategy on a fake CPU mesh and analyze it.

    Returns the :func:`analyze_compiled` report extended with
    ``{"strategy", "mesh", "lowered", "expected",
    "signature_violations", "findings"}`` — the last from the static
    hazard analyzer (:mod:`ddl25spring_tpu.analysis`), run over the same
    optimized HLO unless ``lint=False``.  ``keep_hlo=True`` additionally
    stores the optimized-HLO text under ``report["hlo_text"]`` — the
    tests' lower-once cache and ``graft_lint --shard-flow`` opt in so
    the sharding-flow walk and the bitwise rule-table pins reuse the one
    compile; the default stays off so JSON artifacts never carry
    megabytes of HLO.  A strategy whose trace/compile
    fails degrades to ``{"strategy", "error"}`` instead of raising — a dead
    strategy must not cost the others' reports.
    """
    try:
        mesh = strategy_mesh(name, mesh_sizes)
        d = describe_strategy(name, mesh, **overrides)
        compiled = d["fn"].lower(*d["args"]).compile(
            compiler_options=_AS_ISSUED
        )
        hlo_text = compiled.as_text()  # serialized once, analyze + lint
        report = analyze_compiled(
            compiled, mesh, meta=d.get("meta"), hlo_text=hlo_text
        )
    except Exception as e:  # noqa: BLE001 — degrade per strategy
        err: dict[str, Any] = {
            "strategy": name,
            "error": f"{type(e).__name__}: {e}",
        }
        try:
            err["mesh"] = {
                ax: int(s)
                for ax, s in zip(mesh.axis_names, mesh.devices.shape)
            }
        except UnboundLocalError:  # the mesh itself failed to build
            err["mesh_requested"] = list(mesh_sizes or ())
        return err
    report["strategy"] = name
    if keep_hlo:
        report["hlo_text"] = hlo_text
    report["mesh"] = {
        ax: int(s) for ax, s in zip(mesh.axis_names, mesh.devices.shape)
    }
    report["lowered"] = d.get("lowered", "train_step")
    if report["lowered"] == "train_step":
        # which leading entry parameters COULD have been donated: the
        # flattened leaves of (params, opt_state) — donate_argnums=(0, 1)
        # territory.  The donation-miss rule (H005) checks each of these
        # above its byte threshold against the alias table.
        import jax

        report["donation"]["donatable_leaves"] = len(
            jax.tree.leaves(d["args"][:2])
        )
    expected = d.get("expected")
    if expected:
        report["expected"] = expected
        report["signature_violations"] = check_signature(report, expected)
    if lint:
        attach_findings(report, compiled, strategy=name, hlo_text=hlo_text)
    return report


def attach_findings(
    report: dict[str, Any],
    compiled: Any,
    strategy=None,
    hlo_text: str | None = None,
):
    """Run the static hazard analyzer over a compiled program and attach
    its (waiver-resolved) findings to the report as ``report["findings"]``
    (a list of dicts).  Pass ``hlo_text`` when the module text is already
    in hand (``compiled.as_text()`` re-serializes the whole program).
    Lint breakage degrades to ``report["lint_error"]`` — the analytics
    must never cost the report itself."""
    try:
        from ddl25spring_tpu.analysis import engine

        report["findings"] = [
            f.to_dict()
            for f in engine.lint_hlo_text(
                hlo_text if hlo_text is not None else compiled.as_text(),
                report=report,
                strategy=strategy,
            )
        ]
    except Exception as e:  # noqa: BLE001 — degrade, keep the report
        report["lint_error"] = f"{type(e).__name__}: {e}"
    return report
