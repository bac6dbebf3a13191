"""A kernel's share of its roofline in the TRACED SLICE itself: the least
time the chip could take for the passes the slice holds, over the kernel's
self time in the slice.  Both sides are of the same passes, so nothing has
to be steady and no rate stands in for a count.

Why not two rates of a steady loop, as ``moe_gmm_roofline`` reads its cell:
there the least time is a rate of the WINDOW and the measured time a rate of
the 3 s slice, which holds only if the slice's mix of ticks and prompt
passes is the window's.  In a cell whose passes take a third of the time
(``qwen3next-serve-decode128``: 35 %, 55 ms each) the slice's 15-20 passes
swing that mix by a tenth either way, the recurrent-state kernel runs in
ticks only, and both kernels run within a few per cent of the chip's rate:
a rate-based share would read over 105 % in some runs with no fault
anywhere (PERF.md section 6, PR 33).

Which passes the slice holds: the runner opens the profiler right after the
window closes and pumps while ``eng.now() - t_close < TRACE_S``; every pass
is dispatched and fetched inside one ``step``, and the program stamps its
rings at dispatch, on the clock of ``t_close_host``.  So the slice's passes
are the ring samples in ``[t_close_host, t_close_host + TRACE_S)``, but for
the tick of a step that began before the limit and dispatched it after: that
tick's time is in the trace and its count is not, so the share can read a
tick's worth (a per cent) LOW, never high.

``args``: ``match`` (the kernel's name in the trace) and ``kernel``:

- ``gdn_step``: one call a linear layer a decode tick, on as many live slots
  as the tick's sample in ``serve.active_slots`` says; the family's
  ``gdn_step_flops_bytes`` (live slots only: a dead slot is never visited);
- ``moe_gmm``: every pass's samples in ``serve.moe.assignments_here`` and
  ``serve.moe.experts_hit`` (sums over the layers' calls), taken as ``L``
  equal calls of the family's ``moe_gmm_flops_bytes``: the bound of a mean
  call is at most the mean of the calls' bounds, so uneven layers can only
  make this read lower.

Over 100 % would mean the counts are too high or the traced time leaves
work out: the reader does not clip.  ``None`` where there is nothing to
read: no trace, no peaks, no such kernel, no such ring."""

from benchmark import flops, ring, run, trace_reduce


def in_slice(record: dict, name: str):
    """Ring ``name``'s samples stamped in the traced slice."""
    runner = run.load_module(run.BENCH_DIR, "runners", record["cell"]["runner"])
    t0 = record["t_close_host"]
    return ring.series(
        {**record, "t_open_host": t0, "t_close_host": t0 + runner.TRACE_S}, name
    )


def gdn_step_least(record: dict, family, peaks: dict):
    active = in_slice(record, "serve.active_slots")
    if not active:
        return None
    config = record["config"]
    interval = config["full_attention_interval"]
    calls = config["num_hidden_layers"] // interval * (interval - 1)
    sizes = dict(
        value_heads=config["linear_num_value_heads"],
        key_heads=config["linear_num_key_heads"],
        key_dim=config["linear_key_head_dim"],
        value_dim=config["linear_value_head_dim"],
    )
    return sum(
        calls * flops.roofline_seconds(
            *family.gdn_step_flops_bytes(live, **sizes), peaks)[0]
        for _, live in active
    )


def moe_gmm_least(record: dict, family, peaks: dict):
    here = in_slice(record, "serve.moe.assignments_here")
    hit = in_slice(record, "serve.moe.experts_hit")
    if not here or not hit or len(here) != len(hit):
        return None
    config = record["config"]
    calls = config["num_hidden_layers"]
    return sum(
        calls * flops.roofline_seconds(*family.moe_gmm_flops_bytes(
            a / calls, h / calls, hidden=config["hidden_size"],
            width=config["moe_intermediate_size"],
        ), peaks)[0]
        for (_, a), (_, h) in zip(here, hit)
    )


LEAST = {"gdn_step": gdn_step_least, "moe_gmm": moe_gmm_least}


def read(record: dict, args: dict):
    tr, peaks = record.get("trace"), record.get("peaks")
    if not tr or not peaks:
        return None
    sec = trace_reduce.op_seconds(tr, args["match"])
    if sec <= 0:
        return None
    family = run.load_family(run.BENCH_DIR, record["config"])
    least = LEAST[args["kernel"]](record, family, peaks)
    return None if least is None else 100.0 * least / sec
