#!/usr/bin/env python3
"""From a kept profiler trace to the split the result line cannot give.

    python3 benchmark/tools/trace_scopes.py <cell>.xplane.pb [--hlo DIR] [--peek]

(keep a trace with ``benchmark/run.py ... --trace 1 --keep-trace DIR``).
Prints one JSON object: per chip, busy seconds; SELF time by scope
(phase x part) and by kernel; collective self time by kind and its exposed
part; and, over all chips, the idle gaps by the deepest program or harness
span that covers them and the host spans' own totals.

Where the names come from.  The program names its parts with
``jax.named_scope`` (the decoder under ``models/``, ``parallel/pipeline.py``,
``serve/engine.py``) and its kernels with ``pallas_call(name=...)``; both
end up in each HLO instruction's ``op_name`` metadata, e.g.
``jit(step)/transpose(jvp())/shard_map/while/body/closed_call/attn/
jvp(flash_fwd)/pallas_call``.  On a v5e (looked at by hand, PR 26) an ``XLA
Ops`` event carries no such stat: its only stats are ``device_offset_ps``,
``device_duration_ps`` and ``Time Scale Multiplier``, and the event's name
is the instruction's whole text WITHOUT its metadata; see ``op_name_of``
for what is tried.  So
the map from instruction name to ``op_name`` is read from the executable's
own HLO text, dumped by the same run (``--hlo DIR``, the directory that
``XLA_FLAGS=--xla_dump_to=DIR`` filled; module names tell programs apart).

- a scope is the OUTERMOST path component whose name is in ``SCOPES``,
  else the innermost of ``CONTAINERS`` (``schedule``, ``blocks``: what a
  scan itself adds around the parts); the phase is ``bwd`` where any
  component holds ``transpose(``, else ``fwd``; what no scope covers is
  the one bucket ``unscoped``, whose time ``unscoped_paths`` gives again by
  ``op_name`` path (less the primitive's own name), so that a scope the
  program gains after ``SCOPES`` was written shows up by name;
- collective kinds are read from the instruction's opcode (``-start`` /
  ``-done`` halves count under their kind), ``psum`` from its name where
  the opcode says nothing;
- a collective's EXPOSED part is the part of its intervals (``XLA Ops``
  and ``Async XLA Ops``) during which the innermost operation running on
  that chip's ``XLA Ops`` line is not a non-collective one.

This file reads ``trace_reduce``'s helpers and edits nothing; turning its
numbers into metrics takes ``reduce_trace`` keeping the scope of each
event (PERF.md, Open questions).
"""

from __future__ import annotations

import argparse
import bisect
import glob
import gzip
import json
import os
import re
import sys
from collections import defaultdict

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.trace_reduce import (  # noqa: E402
    DEVICE_PLANE, HOST_PLANE, INSTRUCTION, OP_LINE, gaps, load, merge, self_seconds,
    short_name,
)

ASYNC_LINE = "Async XLA Ops"
MODULE_LINE = "XLA Modules"
SCOPES = ("embed", "attn", "mlp", "head_loss", "head", "sample", "page_gather",
          "page_write", "stage_permute", "grad_allreduce", "optimizer")
# scopes AROUND the parts (a scan's own plumbing): they name an operation
# only where no part of SCOPES does
CONTAINERS = ("schedule", "blocks")
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
KINDS = ("all-reduce", "collective-permute", "all-gather", "reduce-scatter",
         "all-to-all")
SPAN_PREFIXES = ("serve.", "serve_", "train_step_", "harness_")
# event stats tried for an op_name, in order, before the HLO map
OP_NAME_STATS = ("tf_op", "op_name", "name_scope")

OPCODE = re.compile(r"[\s)}]((?:%s)(?:-start|-done)?)\(" % "|".join(KINDS))
WRAPPED = re.compile(r"^(?:[a-z_]+\()*([^()]*)\)*$")
HLO_MODULE = re.compile(r"^HloModule (\S+?),")
HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{\s*$")
HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = ")
HLO_OP_NAME = re.compile(r'op_name="([^"]+)"')
HLO_CALLS = re.compile(r"calls=%([\w.\-]+)")
HLO_REF = re.compile(r"%([\w.\-]+)")


# ------------------------------------------------------------- names


def bare(component: str) -> str:
    """``attn`` from ``transpose(jvp(attn))``."""
    m = WRAPPED.match(component)
    return m.group(1) if m else component


def scope_of(op_name: str | None) -> str:
    """``fwd.attn`` / ``bwd.mlp`` / ``optimizer`` / ``unscoped``."""
    if not op_name:
        return "unscoped"
    parts = op_name.split("/")
    scope = next((bare(p) for p in parts if bare(p) in SCOPES), None)
    if scope is None:  # the innermost container, where no part names it
        scope = next((bare(p) for p in parts[::-1] if bare(p) in CONTAINERS), None)
    if scope is None:
        return "unscoped"
    if any("transpose(" in p for p in parts):
        return f"bwd.{scope}"
    return f"fwd.{scope}" if any("jvp(" in p for p in parts) else scope


def path_of(op_name: str | None) -> str:
    """An ``op_name`` less its last component, the primitive's name."""
    if not op_name:
        return "(no op_name)"
    return op_name.rpartition("/")[0] or op_name


def kernel_of(op_name: str | None, text: str) -> str | None:
    """The name of the ``pallas_call`` behind a ``tpu_custom_call`` event:
    a component of its ``op_name``, and also part of the instruction's
    own name (``%jvp_flash_fwd_.1``); ``None`` for any other event."""
    if "tpu_custom_call" not in text:
        return None
    for p in (op_name or "").split("/"):
        if bare(p) in KERNELS:
            return bare(p)
    m = INSTRUCTION.match(text)
    name = m.group(1) if m else text
    # longest first: no kernel's name holds another's today, but stay safe
    return next((k for k in sorted(KERNELS, key=len, reverse=True) if k in name),
                "unnamed_kernel")


def kind_of(text: str) -> str | None:
    """The collective kind of an instruction's text, or ``None``."""
    m = OPCODE.search(text)
    if m:
        return re.sub(r"-(start|done)$", "", m.group(1))
    m = INSTRUCTION.match(text)
    name = (m.group(1) if m else text).lstrip("%")
    for kind in KINDS + ("psum",):
        if name.startswith(kind):
            return kind
    return None


def hlo_op_names(dump_dir: str) -> dict[str, dict[str, str]]:
    """``{module: {instruction: op_name}}`` from the optimized HLO texts
    of an ``--xla_dump_to`` directory (``.txt`` or ``.txt.gz``).

    The compiler leaves a third of the instructions of a TPU program with
    an ``op_name`` of their own; the rest are resolved here, in the order
    of the text (callees and operands come first):

    - an instruction whose own ``op_name`` lies in a known scope keeps it;
    - else a fusion takes the scope most of its fused instructions have
      (a fusion's own metadata is that of ONE of them, often a broadcast
      or an ``add_any`` from outside every scope);
    - else an instruction with no metadata at all (the compiler's own
      reshapes, copies and converts) takes its first scoped operand's."""
    out: dict[str, dict[str, str]] = {}
    paths = glob.glob(os.path.join(dump_dir, "*after_optimizations*.txt*"))
    for path in sorted(paths):
        with (gzip.open if path.endswith(".gz") else open)(path, "rt") as f:
            module, names = resolve_module(f)
        if module:
            out.setdefault(module, {}).update(names)
    return out


def resolve_module(lines) -> tuple[str | None, dict[str, str]]:
    """One module's ``{instruction: op_name}`` (see ``hlo_op_names``)."""
    module = None
    label: dict[str, str | None] = {}
    members: dict[str, list[str]] = {}  # computation -> its instructions
    comp = None
    for line in lines:
        if module is None:
            m = HLO_MODULE.match(line)
            module = m.group(1) if m else None
            continue
        m = HLO_COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            members[comp] = []
            continue
        m = HLO_INSTRUCTION.match(line)
        if not m or comp is None:
            continue
        name, body = m.group(1), line[m.end():]
        members[comp].append(name)
        own = HLO_OP_NAME.search(body)
        own = own.group(1) if own else None
        if scope_of(own) != "unscoped":
            label[name] = own
            continue
        picked = None
        calls = HLO_CALLS.search(body)
        if calls and calls.group(1) in members:
            inner = [label.get(i) for i in members[calls.group(1)]]
            inner = [n for n in inner if scope_of(n) != "unscoped"]
            votes = defaultdict(int)
            for n in inner:
                votes[scope_of(n)] += 1
            if votes:
                best = max(votes, key=votes.get)
                picked = next(n for n in inner if scope_of(n) == best)
        if picked is None and own is None:
            refs = HLO_REF.findall(body.split(", metadata=")[0])
            picked = next(
                (label[r] for r in refs if scope_of(label.get(r)) != "unscoped"), None
            )
        label[name] = picked or own
    return module, {k: v for k, v in label.items() if v}


def op_name_of(event, hlo: dict[str, dict[str, str]], module: str | None) -> str | None:
    """An event's ``op_name``: a stat of the event where the trace has
    one, else the HLO map's entry for the instruction, looked up in the
    module that was running (``module``: the name of the ``XLA Modules``
    event around it, ``jit_step(123)``); without a module, in every
    dumped one, if they agree."""
    stats = dict(event.stats)
    for key in OP_NAME_STATS:
        if isinstance(stats.get(key), str) and stats[key]:
            return stats[key]
    if not hlo:
        return None
    text = event.name
    m = INSTRUCTION.match(text)
    name = m.group(1) if m and " = " in text else text.lstrip("%")
    names = hlo.get((module or "").split("(")[0])
    if names is not None:
        return names.get(name)
    hits = {names[name] for names in hlo.values() if name in names}
    return hits.pop() if len(hits) == 1 else None


# ---------------------------------------------------------- intervals


def innermost(events: list[tuple[float, float, int]]) -> list[tuple[float, float, int]]:
    """Disjoint ``(start, end, index)`` segments: at each instant, the
    innermost of the nested events of one line."""
    out: list[tuple[float, float, int]] = []
    stack: list[tuple[float, int]] = []  # (end, index)
    t = 0.0

    def emit(until: float) -> None:
        nonlocal t
        if stack and until > t:
            out.append((t, until, stack[-1][1]))
        t = max(t, until)

    for s, e, i in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        emit(s)
        t = max(t, s)
        stack.append((min(e, stack[-1][0]) if stack else e, i))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return out


def measure(intervals) -> float:
    return sum(e - s for s, e in intervals)


def minus(a: list[tuple[float, float]], b: list[tuple[float, float]]):
    """Merged ``a`` less merged ``b`` (both sorted and disjoint)."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, t = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > t:
                out.append((t, b[k][0]))
            t = max(t, b[k][1])
            k += 1
        if e > t:
            out.append((t, e))
    return out


# ------------------------------------------------------------ reduce


def host_spans(pd) -> list[tuple[float, float, str]]:
    out = []
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            out += [
                (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
                for e in line.events if e.name.startswith(SPAN_PREFIXES)
            ]
    return sorted(out)


def name_gap(gap, spans, starts) -> str:
    """The span that covers most of ``gap``; of those that cover as much,
    the deepest (shortest)."""
    best, key = "untraced", (0.0, 0.0)
    i = bisect.bisect_right(starts, gap[1])
    for s, e, name in spans[max(0, i - 256):i]:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover > 0 and (round(cover, 9), -(e - s)) > key:
            best, key = name, (round(cover, 9), -(e - s))
    return best


def reduce_scopes(pd, hlo: dict | None = None) -> dict:
    hlo = hlo or {}
    spans = host_spans(pd)
    starts = [s for s, _, _ in spans]
    chips: dict[int, dict] = {}
    lines: dict[int, dict[str, list]] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines[int(m.group(1))] = {
                ln.name: list(ln.events) for ln in plane.lines
                if ln.name in (OP_LINE, ASYNC_LINE, MODULE_LINE)
            }
    if not lines:
        return {"chips": {}, "host_spans": span_totals(spans)}
    if spans:
        lo, hi = spans[0][0], max(e for _, e, _ in spans)
    else:
        evs = [e for per in lines.values() for e in per.get(OP_LINE, [])]
        lo = min(e.start_ns for e in evs) * 1e-9
        hi = max(e.start_ns + e.duration_ns for e in evs) * 1e-9

    def clip(events):
        out = []
        for e in events:
            s, t = max(e.start_ns * 1e-9, lo), min((e.start_ns + e.duration_ns) * 1e-9, hi)
            if t > s:
                out.append((s, t, e))
        return out

    gap_by_span: dict[str, float] = defaultdict(float)
    for chip, per in sorted(lines.items()):
        ops = clip(per.get(OP_LINE, []))
        busy = merge((s, e) for s, e, _ in ops)
        modules = sorted((s, e, ev.name) for s, e, ev in clip(per.get(MODULE_LINE, [])))
        module_starts = [s for s, _, _ in modules]

        def module_at(t: float) -> str | None:
            i = bisect.bisect_right(module_starts, t) - 1
            return modules[i][2] if i >= 0 and t < modules[i][1] else None

        names = [op_name_of(ev, hlo, module_at(s)) for s, _, ev in ops]
        kinds = [kind_of(ev.name) for _, _, ev in ops]
        scope_s = self_seconds(
            [(s, e, scope_of(n)) for (s, e, _), n in zip(ops, names)]
        )
        kernel_s = self_seconds([
            (s, e, kernel_of(n, ev.name) or "") for (s, e, ev), n in zip(ops, names)
        ])
        kernel_s.pop("", None)
        unscoped = self_seconds([
            (s, e, short_name(ev.name) if scope_of(n) == "unscoped" else "")
            for (s, e, ev), n in zip(ops, names)
        ])
        unscoped.pop("", None)
        unscoped_paths = self_seconds([
            (s, e, path_of(n) if scope_of(n) == "unscoped" else "")
            for (s, e, _), n in zip(ops, names)
        ])
        unscoped_paths.pop("", None)
        segments = innermost([(s, e, i) for i, (s, e, _) in enumerate(ops)])
        compute = merge((s, e) for s, e, i in segments if kinds[i] is None)
        coll: dict[str, list] = defaultdict(list)
        self_by_kind: dict[str, float] = defaultdict(float)
        for s, e, i in segments:
            if kinds[i] is not None:
                coll[kinds[i]].append((s, e))
                self_by_kind[kinds[i]] += e - s
        async_by_kind: dict[str, float] = defaultdict(float)
        for s, e, ev in clip(per.get(ASYNC_LINE, [])):
            kind = kind_of(ev.name)
            if kind is not None:
                coll[kind].append((s, e))
                async_by_kind[kind] += e - s
        exposed = {k: measure(minus(merge(v), compute)) for k, v in coll.items()}
        all_coll = merge(iv for v in coll.values() for iv in v)
        chips[chip] = {
            "busy_s": measure(busy),
            "compute_s": measure(compute),
            "scope_self_s": dict(sorted(scope_s.items(), key=lambda kv: -kv[1])),
            "kernel_self_s": kernel_s,
            "unscoped_top": sorted(unscoped.items(), key=lambda kv: -kv[1])[:8],
            "unscoped_paths": sorted(unscoped_paths.items(), key=lambda kv: -kv[1])[:8],
            "events_without_op_name": sum(n is None for n in names),
            "events": len(ops),
            "collective_self_s": dict(self_by_kind),
            "collective_async_s": dict(async_by_kind),
            "collective_exposed_s": exposed,
            "collective_exposed_total_s": measure(minus(all_coll, compute)),
        }
        for g in gaps(busy, lo, hi):
            gap_by_span[name_gap(g, spans, starts)] += (g[1] - g[0]) / len(lines)
    return {
        "window_s": hi - lo,
        "op_names_from": "hlo" if hlo else "event stats",
        "chips": {str(c): v for c, v in chips.items()},
        "idle_gaps_s": dict(sorted(gap_by_span.items(), key=lambda kv: -kv[1])),
        "host_spans": span_totals(spans),
    }


def span_totals(spans) -> dict[str, dict]:
    out: dict[str, dict] = {}
    for s, e, name in spans:
        cell = out.setdefault(name, {"n": 0, "total_s": 0.0})
        cell["n"] += 1
        cell["total_s"] += e - s
    return out


def peek(pd, n: int = 3) -> dict:
    """What a trace holds, for a look by hand: planes, lines, and the
    first events of each line with every stat they carry."""
    out = {}
    for plane in pd.planes:
        per = out[plane.name] = {}
        for line in plane.lines:
            events = list(line.events)
            per[line.name] = {
                "events": len(events),
                "first": [
                    {"name": e.name[:300], "stats": {k: str(v)[:200] for k, v in e.stats}}
                    for e in events[:n]
                ],
            }
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xplane")
    ap.add_argument("--hlo", default=None, metavar="DIR",
                    help="the run's --xla_dump_to directory")
    ap.add_argument("--peek", action="store_true",
                    help="print planes, lines and the first events' stats")
    args = ap.parse_args(argv)
    pd = load(args.xplane)
    if args.peek:
        print(json.dumps(peek(pd), indent=1))
        return 0
    print(json.dumps(reduce_scopes(pd, hlo_op_names(args.hlo) if args.hlo else None)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
