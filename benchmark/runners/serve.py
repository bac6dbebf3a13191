"""The ``serve`` runner: the paged server's engine, built as
``serve.driver`` builds it, around the model of the configuration's family
file (``ctx["family"]``: configuration object, seeded weights, range of
token ids, the served tokens' check; ``families/``).

``serve_model`` and ``run_serve_bench`` take three preset names and no
config (PERF.md, Open questions), so this calls what they call, one level
down: ``driver._build_engine(params, cfg, knobs, clock="wall",
temperature=0.0, trace_label=None)`` -> ``eng.warmup()`` ->
``eng.make_request / submit / step``, with ``driver.engine_knobs()``'s
defaults under the cell's own geometry.

The harness's loop pumps ``submit``/``step`` itself: ``ServeEngine.run``
replays a fixed arrival list and cannot close a loop.

- ``"loop": "closed"``: ``clients`` requests in flight, no think time; a
  client sends its next request when its last is done.  Ramp-in is set-up:
  the window opens when every client's first request has its first token.
- ``"loop": "open"``: Poisson arrivals at ``rate_rps`` whether or not the
  engine keeps up; a request's clock starts when it was DUE.  The window
  opens ``ramp_s`` seconds after the first arrival.

Every number is computed from the ``Request`` stamps (``arrival_t``,
``prefill_start_t``, ``prefill_s``, ``first_token_t``, ``done_t``,
``tokens``), which are on the engine's wall clock and each follow a host
fetch of sampled tokens, which waits for the device.
"""

from __future__ import annotations

import time

from benchmark import stats, traffic

GRACE_S = 10.0     # after the close: wait this long for first tokens
DRAIN_S = 60.0     # then let what is in flight finish, for the leak check
TRACE_S = 3.0      # the traced slice of a --trace 1 run
CHECKED = 4        # completed requests held to the reference
PROGRAM_SPANS = {  # engine methods the traced run wraps in host spans
    "_run_prefill": "serve_prefill",
    "_run_decode_tick": "serve_decode_tick",
    "_flush_releases": "serve_release",
}


def _wrap(eng, tracer) -> None:
    """Host spans around the engine's calls into its programs, from the
    benchmark's side: spans inside the program are a later PR's."""
    for attr, name in PROGRAM_SPANS.items():
        inner = getattr(eng, attr)

        def outer(*a, _inner=inner, _name=name, **kw):
            with tracer.span(_name):
                return _inner(*a, **kw)

        setattr(eng, attr, outer)


def _row(req, reason, t_open: float, t_close: float) -> dict:
    return {
        "rid": req.rid, "arrival_t": req.arrival_t,
        "sent_in_window": t_open <= req.arrival_t <= t_close,
        "prefill_start_t": req.prefill_start_t, "prefill_s": req.prefill_s,
        "first_token_t": req.first_token_t, "done_t": req.done_t,
        "n_tokens": len(req.tokens), "rejected": reason,
    }


def run(ctx: dict) -> dict:
    from ddl25spring_tpu.serve import driver

    t_run = time.perf_counter()
    cell, family, tracer = ctx["cell"], ctx["family"], ctx["tracer"]
    spec = cell["traffic"]
    cfg = family.build(ctx["config"], use_flash=False)
    params = family.init_params(cfg, ctx["seed"])
    knobs = {**driver.engine_knobs(), **cell["engine"]}
    eng = driver._build_engine(
        params, cfg, knobs, clock="wall", temperature=0.0, trace_label=None
    )
    t_warm = time.perf_counter()
    eng.warmup()
    t_warmed = time.perf_counter()
    compile_s = t_warmed - t_warm
    if ctx["trace"]:
        _wrap(eng, tracer)

    stream = traffic.requests(spec, family.vocab(cfg), ctx["seed"])
    sent: list[tuple] = []  # (Request, rejection reason or None)

    def send(arrival_t=None):
        with tracer.span("harness_client"):
            prompt, max_new = next(stream)
            req = eng.make_request(prompt, max_new, arrival_t=arrival_t)
        with tracer.span("serve_submit"):
            reason = eng.submit(req)
        sent.append((req, reason))
        return req if reason is None else None

    closed = spec["loop"] == "closed"
    if closed:
        clients: list = [None] * int(spec["clients"])

        def pump(sending: bool) -> None:
            if sending:
                for i, req in enumerate(clients):
                    if req is None or req.done_t is not None:
                        clients[i] = send()
            eng.step()

        # ramp-in: every client's first request gets its first token
        pump(True)
        first = [r for r, why in sent if why is None]
        while any(r.first_token_t is None for r in first):
            pump(True)
        t_open = eng.now()
    elif spec["loop"] == "open":
        horizon = float(spec["ramp_s"]) + ctx["seconds"] + TRACE_S
        base = eng.now()
        due = [base + t for t in traffic.poisson_arrivals(
            float(spec["rate_rps"]), horizon, ctx["seed"]
        )]
        nxt = 0

        def pump(sending: bool) -> None:
            nonlocal nxt
            while sending and nxt < len(due) and due[nxt] <= eng.now():
                send(arrival_t=due[nxt])
                nxt += 1
            if not eng.step():  # idle: wait for the next arrival
                wait = due[nxt] - eng.now() if sending and nxt < len(due) else 1e-3
                time.sleep(min(max(wait, 0.0), 0.05))

        while eng.now() < base + float(spec["ramp_s"]):
            pump(True)
        t_open = eng.now()
    else:
        raise ValueError(f"loop {spec['loop']!r} is not 'closed' or 'open'")

    # ------------------------------------------------------- the window
    t_open_host = time.perf_counter()
    tokens_open, ticks_open = eng.generated_tokens, eng.tick_wall_s.count
    while eng.now() - t_open < ctx["seconds"]:
        pump(True)
    t_close, t_close_host = eng.now(), time.perf_counter()
    tokens_close, ticks_close = eng.generated_tokens, eng.tick_wall_s.count
    ticks_ordered = ticks_close <= eng.tick_wall_s.cap
    tick_s = list(eng.tick_wall_s)[ticks_open:ticks_close] if ticks_ordered else None

    if ctx["trace"]:  # a steady slice more, under the profiler
        tracer.start()
        while eng.now() - t_close < TRACE_S:
            pump(True)
        tracer.stop()

    # the grace: nothing more is sent; every request sent in the window
    # gets its chance at a first token, so that the last few sends are
    # neither dropped from the sample nor counted as infinite
    in_window = [(r, why) for r, why in sent if t_open <= r.arrival_t <= t_close]
    t_grace = eng.now()
    while (eng.now() - t_grace < GRACE_S
           and any(why is None and r.first_token_t is None for r, why in in_window)):
        pump(False)
    t_grace_end = eng.now()
    rows = [_row(r, why, t_open, t_close) for r, why in sent]
    t_drain = eng.now()
    while not eng.drained and eng.now() - t_drain < DRAIN_S:
        pump(False)

    # ------------------------------------------------------ correctness
    leak = eng.mem_leak_check()
    done = [r for r in eng.done if r.arrival_t >= t_open][:CHECKED]
    served = family.check_served(
        cfg, params, [(r.prompt, r.tokens) for r in done], pad_to=eng.max_seq_len
    )
    mine = [r for r in rows if r["sent_in_window"]]
    failed = sum(
        1 for r in mine if r["rejected"] is not None or r["first_token_t"] is None
    )
    record = {"requests": rows, "t_close": t_close, "t_grace_end": t_grace_end}
    ttft, tpot = stats.ttft_samples_ms(record), stats.tpot_samples_ms(record)
    return {
        "setup_s": t_open_host - ctx["t_process"],
        "t_open_host": t_open_host, "t_close_host": t_close_host,
        "t_open": t_open, "t_close": t_close, "window_s": t_close - t_open,
        "t_grace_end": t_grace_end,
        "requests": rows,
        "generated_tokens": tokens_close - tokens_open,
        "tick_s": tick_s,
        "compile_s": compile_s,
        "correct": bool(
            served["ok"] and leak["ok"] and eng.drained
            and eng.pool_ok_failures == 0
        ),
        "attempted": len(mine),
        "failed": failed,
        "compared": {
            "worst_margin": {"value": served["worst_margin"], "limit": served["eps"]},
            "tokens_checked": {"value": served["tokens_checked"], "limit": ">= 1"},
            "leaked_pages": {"value": leak["leaked_pages"], "limit": 0},
            "pool_ok_failures": {"value": eng.pool_ok_failures, "limit": 0},
            "undrained": {"value": int(not eng.drained), "limit": 0},
        },
        "notes": {
            # where set-up went: imports and device init, weights and
            # engine, warm-up, ramp-in
            "setup_parts_s": [t_run - ctx["t_process"], t_warm - t_run,
                              compile_s, t_open_host - t_warmed],
            "ttft_ms": {"n": len(ttft), "p50": stats.median(ttft)},
            "tpot_ms": {"n": len(tpot), "p50": stats.median(tpot)},
            "served_check": served, "leaked_pages": leak["leaked_pages"],
            "drained": eng.drained, "pool_ok_failures": eng.pool_ok_failures,
            "rejected": dict(eng.rejected), "prefills": eng._prefills,
            "ticks_in_window": ticks_close - ticks_open,
            "prefix_hits": eng.prefix.hits if eng.prefix is not None else None,
            "knobs": {k: knobs[k] for k in sorted(cell["engine"])},
        },
    }
