"""The grouped expert products' share of their roofline: the least time the
chip could take for them over the time they took, as two RATES of one
steady closed loop, so that no pass has to be matched to the traced slice.

- least: every pass of the window left one sample in each of the rings
  ``serve.moe.assignments_here`` and ``serve.moe.experts_hit`` (sums over
  the layers' calls).  A pass is taken as ``L`` equal calls of the family's
  ``moe_gmm_flops_bytes`` (the larger of FLOPs over peak and bytes over
  peak, ``flops.roofline_seconds``); the sum over passes over ``window_s``
  is the least kernel-seconds a second of serving needs.  The bound of a
  mean call is at most the mean of the calls' bounds, so uneven layers can
  only make this read LOWER than the truth, never over;
- measured: the kernel's self time in the traced slice over the slice's
  length.

Both lengths are STEADY seconds: a pause of the machine (PERF.md section 7:
one pass in some runs waits 0.1 s, or seconds, with the device idle) is
taken out of the side it fell on, or a slice that held one would read as
if the kernel had done its work in half the time (168 % was read so).  In
the slice a pause is one of the trace's longest device-idle gaps, in the
window a pass's time over its kind's median (the rings of the spans
``serve.decode_tick`` and ``serve.prefill``); each counts where it is
longer than ``PAUSE_S``, which no steady gap or pass comes near (3 ms
between ticks; a prompt pass is 25-60 ms around a median of 43).

The slice (3 s) follows the window and holds some 150 ticks and 15-20
prompt passes: a slice that happens to hold fewer passes than the window's
mix reads higher (a pass is ~40 ticks' worth of expert work), by about the
share its passes are of the kernel's time, and the other way round.  Over
100 % would mean the counts are too high or the traced time leaves work
out: the reader does not clip."""

import statistics

from benchmark import flops, ring, run, trace_reduce

PAUSE_S = 0.05
PASS_SPANS = ("serve.decode_tick", "serve.prefill")


def steady(seconds: float, pauses) -> float:
    return seconds - sum(p for p in pauses if p > PAUSE_S)


def window_pauses(record: dict) -> list[float]:
    """Every pass's time over the median of its kind, in the window."""
    out = []
    for name in PASS_SPANS:
        took = [d for _, d in ring.series(record, name) or []]
        if took:
            median = statistics.median(took)
            out += [d - median for d in took]
    return out


def read(record: dict, args: dict):
    tr, peaks = record.get("trace"), record.get("peaks")
    here = ring.series(record, "serve.moe.assignments_here")
    hit = ring.series(record, "serve.moe.experts_hit")
    if not tr or not peaks or not here or not hit or len(here) != len(hit):
        return None
    sec = trace_reduce.op_seconds(tr, args["match"])
    if sec <= 0 or tr["window_s"] <= 0 or record["window_s"] <= 0:
        return None
    config = record["config"]
    count = run.load_family(run.BENCH_DIR, config).moe_gmm_flops_bytes
    calls = config["num_hidden_layers"]
    least = sum(
        calls * flops.roofline_seconds(*count(
            a / calls, h / calls, hidden=config["hidden_size"],
            width=config["moe_intermediate_size"],
        ), peaks)[0]
        for (_, a), (_, h) in zip(here, hit)
    )
    window_s = steady(record["window_s"], window_pauses(record))
    slice_s = steady(tr["window_s"], (g[1] for g in tr.get("longest_gaps", [])))
    return 100.0 * (least / window_s) / (sec / slice_s)
