"""From a ``jax.profiler`` trace (``*.xplane.pb``) to numbers.

What the trace holds on a v5e (looked at by hand first, PR 25): one plane
per chip named ``/device:TPU:<i>`` with the lines ``Steps``, ``XLA
Modules``, ``XLA Ops`` and ``Async XLA Ops``.  ``XLA Ops`` has one event per
executed HLO instruction, named by the instruction's whole text (``%fusion.71
= f32[2048,50304]{...} fusion(...)``); control flow (``while``, ``cond``)
ENCLOSES the instructions of its body on the same line.  A Pallas kernel is
a ``custom-call`` whose text holds ``custom_call_target="tpu_custom_call"``
and nothing of the kernel's own name.  A plane ``/host:CPU`` holds the host
threads; ``jax.profiler.TraceAnnotation`` spans of the harness land there
under their own names, on the same clock.

- busy time of a chip: the union of its operations' intervals;
- an operation's time: its SELF time (its interval less the operations
  nested inside it), so that a ``while`` does not count its body twice;
- an idle gap: a maximal interval of the traced window with no operation,
  named after the harness span that covers most of it.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Iterable

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OP_LINE = "XLA Ops"

INSTRUCTION = re.compile(r"^%?(\S+) = \(?([a-z0-9]+\[[^\]]*\])?")
TARGET = re.compile(r'custom_call_target="([^"]+)"')

Interval = tuple[float, float]


def short_name(text: str) -> str:
    """``fusion.71 f32[2048,50304]`` from an instruction's whole text (the
    result's name, its first shape and, for a custom call, its target);
    any other event name is kept as it is."""
    m = INSTRUCTION.match(text)
    if not m or " = " not in text:
        return text
    target = TARGET.search(text)
    return " ".join(p for p in (m.group(1), m.group(2), target and target.group(1)) if p)


def load(path: str):
    import jax

    return jax.profiler.ProfileData.from_file(path)


def _events(line) -> list[tuple[float, float, str]]:
    """``(start_s, end_s, name)`` of a line's events."""
    return [
        (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
        for e in line.events
    ]


def device_ops(pd) -> dict[int, list[tuple[float, float, str]]]:
    """Per chip, the events of its operations line, sorted by start."""
    out = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name == OP_LINE:
                out[int(m.group(1))] = sorted(
                    (s, e, short_name(n)) for s, e, n in _events(line)
                )
    return out


def host_spans(pd, names: Iterable[str]) -> list[tuple[float, float, str]]:
    """The harness's own spans, from every host thread, sorted by start."""
    names = set(names)
    out = []
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            out += [ev for ev in _events(line) if ev[2] in names]
    return sorted(out)


def merge(intervals: Iterable[Interval]) -> list[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: list[Interval], lo: float, hi: float) -> list[Interval]:
    """The complement of merged ``busy`` inside ``[lo, hi]``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def self_seconds(events: list[tuple[float, float, str]]) -> dict[str, float]:
    """Total SELF time by operation name: each event's duration less the
    events nested inside it on the same line."""
    total: dict[str, float] = defaultdict(float)
    stack: list[list] = []  # [end, name, self]

    def close(until: float) -> None:
        while stack and stack[-1][0] <= until:
            end, name, own = stack.pop()
            total[name] += max(own, 0.0)

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    close(float("inf"))
    return dict(total)


def name_gap(gap: Interval, spans: list[tuple[float, float, str]],
             starts: list[float]) -> str:
    """The harness span that covers most of ``gap`` (``"untraced"`` when
    none touches it)."""
    best, best_cover = "untraced", 0.0
    # spans are sorted by start; the few that can overlap begin before the
    # gap ends, and harness spans are short, so look back a bounded number
    i = bisect.bisect_right(starts, gap[1])
    for s, e, name in spans[max(0, i - 64):i]:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def reduce_trace(pd, span_names: Iterable[str], top: int = 10) -> dict | None:
    """Everything the readers and the result line take from one trace, or
    ``None`` when the trace has no device plane (a CPU run)."""
    ops = device_ops(pd)
    if not ops:
        return None
    spans = host_spans(pd, span_names)
    if spans:
        lo, hi = spans[0][0], max(e for _, e, _ in spans)
    else:
        lo = min(evs[0][0] for evs in ops.values() if evs)
        hi = max(max(e for _, e, _ in evs) for evs in ops.values() if evs)
    window = hi - lo
    starts = [s for s, _, _ in spans]

    busy_s, op_s, gap_by_span, longest = [], defaultdict(float), defaultdict(float), []
    for chip, evs in sorted(ops.items()):
        inside = [(max(s, lo), min(e, hi), n) for s, e, n in evs
                  if min(e, hi) > max(s, lo)]
        busy = merge((s, e) for s, e, _ in inside)
        busy_s.append(sum(e - s for s, e in busy))
        for name, sec in self_seconds(inside).items():
            op_s[name] += sec / len(ops)
        for g in gaps(busy, lo, hi):
            name = name_gap(g, spans, starts)
            gap_by_span[name] += (g[1] - g[0]) / len(ops)
            longest.append((g[1] - g[0], name, chip))

    def ranked(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "chips": len(ops),
        "window_s": window,
        "busy_s": sum(busy_s) / len(busy_s),
        "busy_s_per_chip": busy_s,
        "op_self_s": dict(op_s),
        "device_ops": ranked(op_s),
        "idle_gaps": ranked(gap_by_span),
        "longest_gaps": [
            [name, sec, chip] for sec, name, chip in sorted(longest, reverse=True)[:5]
        ],
    }


def op_seconds(reduced: dict, match: Iterable[str]) -> float:
    """Self time, in the traced slice, of the operations whose name holds
    one of ``match`` (mean over chips)."""
    match = list(match)
    return sum(
        sec for name, sec in reduced["op_self_s"].items()
        if any(m in name for m in match)
    )
