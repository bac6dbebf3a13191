"""The scheduler's own host time in one step: median over the
``serve.step`` spans that lie inside the window of their SELF time, the
span less the spans inside it that wait for the device (``args.device``:
prefill, decode tick, draft, verify), in ms.  What is left is release,
admission, prompt packing and the token loop."""

import bisect

from benchmark import ring, stats


def read(record: dict, args: dict):
    steps = ring.series(record, "serve.step")
    if not steps:
        return None
    inner = []
    for name in args["device"]:
        spans = ring.series(record, name)
        if spans is None:
            return None
        inner += spans
    inner.sort()
    starts = [t for t, _ in inner]
    own = []
    for t0, dur in steps:
        if t0 + dur > record["t_close_host"]:
            continue  # its last spans were written outside the window
        lo, hi = bisect.bisect_left(starts, t0), bisect.bisect_right(starts, t0 + dur)
        own.append(dur - sum(d for _, d in inner[lo:hi]))
    med = stats.median(own)
    return None if med is None else med * 1e3
