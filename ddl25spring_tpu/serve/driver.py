"""The ``bench.py --serve`` driver: traffic -> engine -> telemetry.

One call (:func:`run_serve_bench`) produces the whole serving record:

1. **ramp phase** — the seeded open-loop trace (:mod:`.traffic`) drives
   a continuous-batching engine on the WALL clock: measured TTFT
   p50/p95, per-token latency, queue depth, admission counters, and
   page-pool peak occupancy (the ``telemetry.serve`` contract).
2. **continuous-vs-static A/B** — the SAME trace replayed through two
   fresh engines on the VIRTUAL clock (every compiled-program call
   advances ``tick_s``; fully deterministic on any host).  Both run to
   drain, logging their cumulative token timeline; the fixed budget is
   the midpoint of the two drain times, and "tokens delivered by the
   budget" is read off each timeline — one drain run per mode answers
   every candidate budget, and continuous batching's win (slots refill
   mid-flight instead of waiting for the batch to drain) is measured on
   identical work.
3. **artifacts** — ``serve.json`` in the obs dir (the Serving section
   of ``tools/obs_report.py``; histograms for ``tools/serve_report.py``)
   and a ``record: "serve"`` line appended to the perf ledger
   (``runs/perf_ledger.jsonl``) keyed by host
   fingerprint + workload key (git sha as the trend variable) so
   ``serve_report --check`` gates cross-run regressions.

Engine knobs resolve from ``DDL25_SERVE_*`` env (documented in the
README's serving section) so CI and operators tune pool geometry and
admission control without touching code.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any

# ledger/trend + smoke defaults: the CI smoke must be reproducible, so
# every knob that shapes the workload lands in the record's key
SMOKE_TRAFFIC = {"duration_s": 2.0, "rate_rps": 6.0, "profile": "ramp",
                 "seed": 0}


def engine_knobs(smoke: bool = False) -> dict[str, Any]:
    """Pool geometry + admission-control knobs: ``DDL25_SERVE_*`` env
    over (smoke-sized or serving-sized) defaults."""
    from ddl25spring_tpu.utils.config import env_int

    d = (
        dict(page_len=4, n_pages=16, max_slots=2, prefill_batch=2,
             max_prompt_len=8, max_queue=32, token_budget=0)
        if smoke else
        dict(page_len=16, n_pages=64, max_slots=4, prefill_batch=2,
             max_prompt_len=32, max_queue=64, token_budget=0)
    )
    eos = env_int("DDL25_SERVE_EOS", -1)
    return {
        "page_len": env_int("DDL25_SERVE_PAGE_LEN", d["page_len"]),
        "n_pages": env_int("DDL25_SERVE_N_PAGES", d["n_pages"]),
        "max_slots": env_int("DDL25_SERVE_SLOTS", d["max_slots"]),
        "prefill_batch": env_int(
            "DDL25_SERVE_PREFILL_BATCH", d["prefill_batch"]
        ),
        "max_prompt_len": env_int(
            "DDL25_SERVE_MAX_PROMPT", d["max_prompt_len"]
        ),
        "max_queue": env_int("DDL25_SERVE_MAX_QUEUE", d["max_queue"]),
        # 0 = unlimited (the knob is backpressure, not a requirement)
        "token_budget": (
            env_int("DDL25_SERVE_TOKEN_BUDGET", d["token_budget"]) or None
        ),
        "eos_id": None if eos < 0 else eos,
        # the radix prefix cache (PR 11): on by default — a workload
        # with no repeated prefixes simply never hits, and the cold
        # path is bitwise-identical; 0 disables outright
        "prefix_cache": bool(env_int("DDL25_SERVE_PREFIX", 1)),
        # speculative decoding (PR 13): off by default — DDL25_SERVE_
        # SPEC=1 enables the early-exit drafter with DDL25_SERVE_SPEC_K
        # draft tokens per round and DDL25_SERVE_DRAFT_LAYERS drafter
        # depth (greedy-only; the engine refuses spec with sampling).
        # k=2 measured best on the smoke workload (see RESULTS PR-13)
        "spec_k": (
            env_int("DDL25_SERVE_SPEC_K", 2)
            if env_int("DDL25_SERVE_SPEC", 0) else 0
        ),
        "draft_layers": env_int("DDL25_SERVE_DRAFT_LAYERS", 1),
        # TP-sharded serving (PR 18): tp > 1 runs every engine in the
        # bench under a 1-D model mesh (KV head dim + Megatron params
        # divided per chip); weight streaming additionally swaps
        # resident params for ZeRO-3 rows gathered one layer at a time
        "tp": env_int("DDL25_SERVE_TP", 1),
        "weight_stream": bool(env_int("DDL25_SERVE_WEIGHT_STREAM", 0)),
    }


def serve_model(model: str):
    """The model the bench serves: ``tiny`` (the CI smoke / test config
    — fp32 so the paged-vs-dense pin is bitwise), ``tiny-deep`` (the
    speculative smoke: same tiny dims at 6 layers, so the 1-layer
    early-exit drafter is genuinely cheap — at 2 layers the drafter
    costs ~0.56 of the target and speculation barely pays; at 6 it is
    ~0.20 and the A/B margin is robust.  Depth rides the layer scan, so
    the compile bill matches tiny's) or ``ref`` (the reference LLaMA
    workload constants, bf16)."""
    from ddl25spring_tpu.utils.config import LlamaConfig

    if model == "tiny":
        return LlamaConfig(
            vocab_size=64, dmodel=16, num_heads=2, n_layers=2,
            ctx_size=32, dtype="float32",
        )
    if model == "tiny-deep":
        return LlamaConfig(
            vocab_size=64, dmodel=16, num_heads=2, n_layers=6,
            ctx_size=32, dtype="float32",
        )
    if model == "ref":
        return LlamaConfig()
    raise ValueError(
        f"model={model!r} is not 'tiny', 'tiny-deep' or 'ref'"
    )


def _build_engine(params, cfg, knobs: dict[str, Any], **over):
    from ddl25spring_tpu.serve.engine import ServeEngine

    kw = dict(knobs)
    kw.update(over)
    return ServeEngine(params, cfg, **kw)


def ab_tick_s(trace, max_slots: int) -> float:
    """The A/B's virtual tick length, sized so decode capacity
    (``max_slots / tick_s`` tokens/s) sits at ~75% of the trace's mean
    token demand: the engine saturates, a queue forms, and the two
    admission policies differ where continuous batching exists to
    differ — slots refilling mid-flight under backlog.  An unloaded
    engine serves both policies identically and the A/B would tie."""
    if not trace:
        return 5e-3
    duration = max(r["t"] for r in trace) or 1.0
    demand = sum(r["max_new"] for r in trace) / duration  # tokens/s
    if demand <= 0:
        return 5e-3
    return min(max(max_slots / (0.75 * demand), 1e-4), 1.0)


def ab_compare(
    params, cfg, trace, knobs: dict[str, Any], *,
    tick_s: float | None = None, max_steps: int = 20_000,
    temperature: float = 0.0, sentinel: bool | None = None,
) -> dict[str, Any]:
    """Continuous vs static admission on the identical trace, virtual
    clock: run both to drain, fix the budget at the midpoint of the two
    drain walls, read tokens-delivered-by-budget off each timeline.
    ``temperature``/``sentinel`` must match the ramp engine's — the A/B
    cell lands in a ledger row keyed by the ramp's configuration.

    Both engines get ``prefill_batch=max_slots``: the static arm only
    admits into an all-idle batch, so a narrower prefill width would
    permanently cap it below ``max_slots`` concurrent sequences and the
    advantage would conflate admission policy with batch width.  Equal
    width makes the delta count exactly the ticks static admission left
    freed slots idle."""
    if tick_s is None:
        tick_s = ab_tick_s(trace, knobs["max_slots"])
    out: dict[str, Any] = {}
    engines = {}
    for adm in ("continuous", "static"):
        e = _build_engine(
            params, cfg, knobs, admission=adm, clock="virtual",
            tick_s=tick_s, temperature=temperature, sentinel=sentinel,
            prefill_batch=knobs["max_slots"],
            # replayed traffic: keep the A/B arms off the run timeline
            trace_label=None,
        )
        m = e.run(trace, max_steps=max_steps)
        engines[adm] = e
        out[adm] = {
            "drain_wall_s": m["wall_s"],
            "ticks": m["ticks"],
            "prefills": m["prefills"],
            "generated_tokens": m["generated_tokens"],
            "completed": m["completed"],
            "rejected": m["rejected"],
        }
    budget = round(
        (out["continuous"]["drain_wall_s"] + out["static"]["drain_wall_s"])
        / 2, 6,
    )
    cont = engines["continuous"].tokens_at(budget)
    stat = engines["static"].tokens_at(budget)
    out.update(
        budget_s=budget,
        tick_s=tick_s,
        continuous_tokens_at_budget=cont,
        static_tokens_at_budget=stat,
        advantage_tokens=cont - stat,
        advantage_frac=round((cont - stat) / stat, 4) if stat else None,
    )
    return out


def prefix_ab_compare(
    params, cfg, trace, knobs: dict[str, Any], *,
    tick_s: float | None = None, max_steps: int = 20_000,
    temperature: float = 0.0, sentinel: bool | None = None,
) -> dict[str, Any]:
    """Radix-prefix-cache A/B: the identical trace through a CACHED
    engine (prefix cache on) and a COLD one (off), both continuous
    admission on the virtual clock at the same ``prefill_batch =
    max_slots`` width — equal admission budget, so the only difference
    is the prefill scan work the radix hits skip.  The virtual clock
    charges each prefill for the scan it actually ran (``(max_prompt_len
    - start) / max_prompt_len`` ticks), so the advantage is
    deterministic on any host: run both to drain, fix the budget at the
    midpoint of the two drain walls, read tokens-delivered-by-budget
    off each timeline — exactly the ``ab_compare`` discipline.

    ``tokens_match`` rides along as the correctness half: every request
    completed by BOTH arms must carry the identical token string
    (prefix-cached decode reproduces the cold path bitwise in fp32;
    the full pin — COW boundary, eviction-readmit — lives in
    ``tests/test_serve_prefix.py``)."""
    if tick_s is None:
        tick_s = ab_tick_s(trace, knobs["max_slots"])
    out: dict[str, Any] = {}
    engines = {}
    for arm, cache_on in (("cached", True), ("cold", False)):
        e = _build_engine(
            params, cfg, knobs, admission="continuous", clock="virtual",
            tick_s=tick_s, temperature=temperature, sentinel=sentinel,
            prefill_batch=knobs["max_slots"], prefix_cache=cache_on,
            trace_label=None,
        )
        m = e.run(trace, max_steps=max_steps)
        engines[arm] = e
        out[arm] = {
            "drain_wall_s": m["wall_s"],
            "ticks": m["ticks"],
            "prefills": m["prefills"],
            "generated_tokens": m["generated_tokens"],
            "completed": m["completed"],
            "rejected": m["rejected"],
            "tokens_per_sec_per_chip": m["tokens_per_sec_per_chip"],
            **({
                "prefix_hit_rate": m["prefix_hit_rate"],
                "prefill_tokens_saved": m["prefill_tokens_saved"],
                "prefill_flops_saved": m["prefill_flops_saved"],
            } if cache_on else {}),
        }
    budget = round(
        (out["cached"]["drain_wall_s"] + out["cold"]["drain_wall_s"]) / 2,
        6,
    )
    cached = engines["cached"].tokens_at(budget)
    cold = engines["cold"].tokens_at(budget)
    streams = {
        arm: {r.rid: list(r.tokens) for r in e.done}
        for arm, e in engines.items()
    }
    common = set(streams["cached"]) & set(streams["cold"])
    out.update(
        budget_s=budget,
        tick_s=tick_s,
        cached_tokens_at_budget=cached,
        cold_tokens_at_budget=cold,
        advantage_tokens=cached - cold,
        advantage_frac=round((cached - cold) / cold, 4) if cold else None,
        tokens_match=all(
            streams["cached"][rid] == streams["cold"][rid]
            for rid in common
        ),
        compared_requests=len(common),
    )
    return out


def spec_ab_compare(
    params, cfg, trace, knobs: dict[str, Any], *,
    tick_s: float | None = None, max_steps: int = 20_000,
    sentinel: bool | None = None,
) -> dict[str, Any]:
    """Speculative-decoding A/B (PR 13): the identical trace through a
    SPEC engine (tiny-LLaMA drafter, k-token draft + one verify pass)
    and a plain sequential-decode one, both continuous admission on the
    virtual clock at the same ``prefill_batch = max_slots`` width —
    equal admission budget, so the only difference is how many target
    weight streams each committed token costs.  The virtual clock is
    the judge because the 2-core CPU sandbox wall clock cannot be:
    decode is memory-bandwidth-bound on a real chip (one verify pass =
    one weight stream = 1 tick, vs k ticks of sequential decode), while
    the CPU host is compute-bound and would charge the verify scan k+1
    ticks of wall time.  The drafter is charged its FLOP ratio per
    step and its full prefill scan — nothing rides free.

    ``tokens_match`` is the correctness half: greedy speculation emits
    the target's own argmax stream, so every request completed by BOTH
    arms must carry the identical tokens (the full pin — accept-all,
    reject-first, mid-draft rejection, EOS-inside-draft, page-boundary
    drafts — lives in ``tests/test_serve_spec.py``)."""
    if not knobs.get("spec_k"):
        raise ValueError("spec_ab_compare needs knobs['spec_k'] > 0")
    if tick_s is None:
        tick_s = ab_tick_s(trace, knobs["max_slots"])
    out: dict[str, Any] = {}
    engines = {}
    for arm, k in (("spec", knobs["spec_k"]), ("nospec", 0)):
        e = _build_engine(
            params, cfg, knobs, admission="continuous", clock="virtual",
            tick_s=tick_s, temperature=0.0, sentinel=sentinel,
            prefill_batch=knobs["max_slots"], spec_k=k,
            trace_label=None,
        )
        m = e.run(trace, max_steps=max_steps)
        engines[arm] = e
        out[arm] = {
            "drain_wall_s": m["wall_s"],
            "ticks": m["ticks"],
            "prefills": m["prefills"],
            "generated_tokens": m["generated_tokens"],
            "completed": m["completed"],
            "rejected": m["rejected"],
            "tokens_per_sec_per_chip": m["tokens_per_sec_per_chip"],
            **({
                "acceptance_rate": m["acceptance_rate"],
                "draft_tokens_accepted": m["draft_tokens_accepted"],
                "draft_tokens_rejected": m["draft_tokens_rejected"],
                "spec": m["spec"],
            } if k else {}),
        }
    budget = round(
        (out["spec"]["drain_wall_s"] + out["nospec"]["drain_wall_s"]) / 2,
        6,
    )
    spec_toks = engines["spec"].tokens_at(budget)
    nospec_toks = engines["nospec"].tokens_at(budget)
    streams = {
        arm: {r.rid: list(r.tokens) for r in e.done}
        for arm, e in engines.items()
    }
    common = set(streams["spec"]) & set(streams["nospec"])
    out.update(
        budget_s=budget,
        tick_s=tick_s,
        spec_tokens_at_budget=spec_toks,
        nospec_tokens_at_budget=nospec_toks,
        advantage_tokens=spec_toks - nospec_toks,
        advantage_frac=(
            round((spec_toks - nospec_toks) / nospec_toks, 4)
            if nospec_toks else None
        ),
        tokens_match=all(
            streams["spec"][rid] == streams["nospec"][rid]
            for rid in common
        ),
        compared_requests=len(common),
    )
    return out


def tp_ab_compare(
    params, cfg, trace, knobs: dict[str, Any], *,
    tick_s: float | None = None, max_steps: int = 20_000,
    temperature: float = 0.0, sentinel: bool | None = None,
) -> dict[str, Any]:
    """TP-sharded vs dense A/B (PR 18): the identical trace through a
    ``tp = knobs['tp']`` engine (KV head dim + Megatron params divided
    per chip; ZeRO-3 weight streaming when asked) and the tp=1 dense
    oracle, both continuous admission on the virtual clock at the same
    width.  Two verdicts ride out:

    - ``tokens_match`` — every request completed by BOTH arms carries
      the identical token string (the sharded engine reproduces the
      dense one bitwise in fp32; the full pin incl. prefix-cache and
      speculative paths lives in ``tests/test_serve_tp.py``);
    - ``budget_shrunk`` — the sharded arm's static per-chip residency
      (:meth:`~ddl25spring_tpu.serve.engine.ServeEngine.
      mem_budget_bytes`) comes in strictly below the dense arm's — the
      claim ``serve_report --check-tp`` and ``mem_report --check``
      gate.

    Throughput is NOT the judge here: on the 2-core CPU sandbox a
    tp=2 shard pays real cross-"chip" overhead for divided FLOPs the
    host can't bank, so the wall numbers are reported, never gated."""
    t = int(knobs.get("tp") or 1)
    if t <= 1:
        raise ValueError("tp_ab_compare needs knobs['tp'] > 1")
    if tick_s is None:
        tick_s = ab_tick_s(trace, knobs["max_slots"])
    out: dict[str, Any] = {"tp": t}
    engines = {}
    budgets = {}
    for arm, arm_tp in (("sharded", t), ("dense", 1)):
        e = _build_engine(
            params, cfg, knobs, admission="continuous", clock="virtual",
            tick_s=tick_s, temperature=temperature, sentinel=sentinel,
            prefill_batch=knobs["max_slots"], tp=arm_tp,
            weight_stream=(
                bool(knobs.get("weight_stream")) if arm_tp > 1 else False
            ),
            trace_label=None,
        )
        m = e.run(trace, max_steps=max_steps)
        engines[arm] = e
        budgets[arm] = e.mem_budget_bytes()
        out[arm] = {
            "drain_wall_s": m["wall_s"],
            "ticks": m["ticks"],
            "prefills": m["prefills"],
            "generated_tokens": m["generated_tokens"],
            "completed": m["completed"],
            "rejected": m["rejected"],
            "tokens_per_sec_per_chip": m["tokens_per_sec_per_chip"],
            "mem_budget_bytes_per_chip": budgets[arm],
            **({
                "pool_bytes_per_chip": m.get("pool_bytes_per_chip"),
                "param_bytes_per_chip": m.get("param_bytes_per_chip"),
                "weight_stream": m.get("weight_stream"),
            } if arm_tp > 1 else {}),
        }
    budget = round(
        (out["sharded"]["drain_wall_s"] + out["dense"]["drain_wall_s"])
        / 2, 6,
    )
    streams = {
        arm: {r.rid: list(r.tokens) for r in e.done}
        for arm, e in engines.items()
    }
    common = set(streams["sharded"]) & set(streams["dense"])
    out.update(
        budget_s=budget,
        tick_s=tick_s,
        tp_tokens_at_budget=engines["sharded"].tokens_at(budget),
        dense_tokens_at_budget=engines["dense"].tokens_at(budget),
        tokens_match=all(
            streams["sharded"][rid] == streams["dense"][rid]
            for rid in common
        ),
        compared_requests=len(common),
        budget_shrunk=budgets["sharded"] < budgets["dense"],
    )
    return out


def elastic_serve_run(
    params, cfg, trace, knobs: dict[str, Any], *,
    chaos, tick_s: float | None = None, replicas: int = 2,
    max_replicas: int = 4, max_iters: int = 20_000,
    temperature: float = 0.0, sentinel: bool | None = None,
    keep_requests: bool = False,
) -> dict[str, Any]:
    """Replica scale-up/down under live traffic with page-pool handoff
    (PR 14: the serving half of :mod:`ddl25spring_tpu.ft.elastic`).

    A replica set of continuous-batching engines runs the seeded trace
    in lockstep on ONE driver virtual clock (each iteration steps every
    active replica, then advances ``tick_s`` — deterministic on any
    host).  Arrivals route to the shortest non-draining queue.  The
    armed chaos faults (consumed through ``chaos.take`` at exact
    iteration indices, one-shot journal semantics identical to the
    training kinds) drive three event shapes:

    - ``traffic_spike@k[:B]`` — B deterministic extra arrivals (the
      trace's own first B requests, re-stamped to now) land at once;
      the queue-depth autoscaler answers with a scale-up when the
      backlog crosses 2x the per-replica slot count;
    - ``capacity_change@k[:N]`` — the set resizes to N replicas (grow:
      fresh engines; shrink: drain);
    - ``device_loss@k`` — one replica is lost: it stops admitting, its
      unadmitted queue re-submits to the survivors
      (:meth:`~ddl25spring_tpu.serve.engine.ServeEngine.begin_drain` —
      queued requests hold no pages, so the handoff is a plain
      re-submit), its live slots decode to completion through the
      ordinary release discipline, and only then does its page pool go
      away.  An accepted request can therefore never be lost; the
      ``--check-reshape`` gate pins ``dropped_requests == 0``.

    Every event lands as a ``kind="reshape"`` flight record
    (:func:`ddl25spring_tpu.ft.elastic.record_reshape`) and in the
    returned cell, which also splits TTFT into the reshape windows
    (event start -> drain end + a small settling pad) vs steady state —
    the p95-bounded comparison ``serve_report --check-reshape`` gates.
    """
    from ddl25spring_tpu.ft import elastic
    from ddl25spring_tpu.obs import memscope
    from ddl25spring_tpu.obs.timeline import timeline
    from ddl25spring_tpu.serve.engine import Request

    if tick_s is None:
        tick_s = ab_tick_s(trace, knobs["max_slots"])
    elastic_kinds = ("traffic_spike", "capacity_change", "device_loss")
    # graft-mem (PR 17): the survivor-mesh memory step-downs — one
    # entry per retired replica, live bytes before vs after its page
    # pool is actually dropped (mem_report --check --require-step-down)
    mem_steps: list[dict] = []

    # replica identities are assigned MONOTONICALLY and never reused:
    # ``reps.index(e)`` shifts when a drained replica leaves the list,
    # and the per-replica timeline tracks need an id that survives the
    # roster change
    next_replica = [0]

    def build():
        e = _build_engine(
            params, cfg, knobs, admission="continuous", clock="virtual",
            tick_s=tick_s, temperature=temperature, sentinel=sentinel,
            prefill_batch=knobs["max_slots"], trace_label="elastic",
        )
        e.replica_id = next_replica[0]
        next_replica[0] += 1
        return e

    reps = [build() for _ in range(replicas)]
    retired: list = []
    draining: list[tuple[Any, dict]] = []
    arrivals = sorted(trace, key=lambda r: r["t"])
    events: list[dict] = []
    rid = 0
    t = 0.0
    i = it = 0
    submitted = 0
    spike_backlog: list[dict] = []

    def route(req: Request, force: bool = False) -> None:
        """Shortest-queue routing.  ``force`` is the handoff path: a
        request a draining replica already ACCEPTED must re-admit even
        if the survivors' door policy (queue_full / token_budget) would
        bounce a NEW arrival — it was validated once and the zero-drop
        contract outranks the bound, so a rejected re-submit is seated
        directly in the shortest queue (the transient overflow is the
        honest cost of losing a replica)."""
        live = [e for e in reps if not e.draining]
        target = min(live, key=lambda e: (len(e.queue), reps.index(e)))
        if force:
            # no second trip through the door: the original submit()
            # validated it, and a counted rejection here would skew the
            # admission arithmetic for a request that then completes
            target.queue.append(req)
        else:
            target.submit(req)

    def mk(a: dict, arrival_t: float) -> Request:
        nonlocal rid, submitted
        r = Request(
            rid=rid, prompt=list(map(int, a["prompt"])),
            max_new_tokens=int(a["max_new"]), arrival_t=arrival_t,
        )
        rid += 1
        submitted += 1
        return r

    def scale_up(n_new: int, reason: str) -> None:
        import time as _time

        t0 = _time.perf_counter()
        old = len(reps)
        for _ in range(n_new):
            reps.append(build())
        ev = elastic.record_reshape(
            scope="serve", reason=reason, old=old, new=len(reps),
            wall_s=_time.perf_counter() - t0, steps_lost=0, t=round(t, 6),
        )
        ev["t_end"] = round(t, 6)  # a fresh replica serves immediately
        timeline.emit(
            "reshape_end", reason=reason, t=ev["t"], t_end=ev["t_end"],
            old=ev["old"], new=ev["new"], vt=t, engine="elastic",
        )
        events.append(ev)

    def scale_down(n_drop: int, reason: str) -> None:
        import time as _time

        t0 = _time.perf_counter()
        old = len(reps)
        victims = [e for e in reversed(reps) if not e.draining][:n_drop]
        requeued = 0
        for v in victims:
            for req in v.begin_drain():
                route(req, force=True)
                requeued += 1
                # the handoff leg of the request's span chain: accepted
                # on the victim, re-seated on a survivor without a
                # second trip through the door
                timeline.emit(
                    "serve_drain_handoff", rid=req.rid,
                    from_replica=v.replica_id, vt=t, engine="elastic",
                )
        ev = elastic.record_reshape(
            scope="serve", reason=reason, old=old,
            new=old - len(victims), wall_s=_time.perf_counter() - t0,
            steps_lost=0, t=round(t, 6), requeued=requeued,
        )
        events.append(ev)
        draining.extend((v, ev) for v in victims)

    while True:
        # arrivals whose time has come (plus any spike burst), routed
        # to the shortest live queue
        while i < len(arrivals) and arrivals[i]["t"] <= t:
            route(mk(arrivals[i], arrivals[i]["t"]))
            i += 1
        for a in spike_backlog:
            route(mk(a, t))
        spike_backlog = []

        # chaos at this iteration (journaled BEFORE acting, like every
        # chaos fire — a death mid-reshape never replays the signal)
        for f in chaos.take(it, kinds=elastic_kinds):
            if f.kind == "traffic_spike":
                burst = f.arg or max(4, len(arrivals) // 8)
                spike_backlog.extend(  # += : same-step bursts stack
                    [dict(a) for a in arrivals[:burst]]
                    or [{"prompt": [1, 2], "max_new": 4}] * burst
                )
            elif f.kind == "capacity_change":
                target = f.arg or 1
                live = sum(1 for e in reps if not e.draining)
                grow = max(0, min(target, max_replicas) - live)
                if grow:
                    scale_up(grow, "capacity_change")
                elif target < live:
                    scale_down(live - target, "capacity_change")
            elif f.kind == "device_loss":
                if sum(1 for e in reps if not e.draining) > 1:
                    scale_down(1, "device_loss")

        # queue-depth autoscaler: the traffic_spike response (half of
        # "traffic-driven autoscaling" — the spike injects the load,
        # this reacts to it).  One replica per decision, with a
        # settling cooldown so a burst scales once, not once per tick.
        backlog = sum(len(e.queue) for e in reps if not e.draining)
        live_n = sum(1 for e in reps if not e.draining)
        if (backlog > 2 * knobs["max_slots"] and live_n < max_replicas
                and (not events or t - events[-1]["t"] > 10 * tick_s)):
            scale_up(1, "traffic_spike_scale_up")

        # one lockstep tick: every replica sees the SAME driver clock
        ran = False
        for e in list(reps):
            e._vtime = t  # lockstep: one driver clock for every replica
            ran = e.step() or ran
        for v, ev in list(draining):
            if v.drained:
                ev["t_end"] = round(t, 6)
                ev["drained_slots"] = v.max_slots
                timeline.emit(
                    "reshape_end", reason=ev["reason"], t=ev["t"],
                    t_end=ev["t_end"], old=ev["old"], new=ev["new"],
                    vt=t, engine="elastic",
                )
                reps.remove(v)
                retired.append(v)
                draining.remove((v, ev))
                if memscope.enabled():
                    # the memory step-down: a drained replica's pool
                    # leaves the device WITH the replica.  Leak-check
                    # first (the pool must hold exactly its cache-held
                    # pages), then drop the pool refs and measure the
                    # live-bytes step.  Retired engines are read only
                    # for host counters after this point.
                    before = memscope.live_total_bytes()
                    leak = v.mem_leak_check()
                    v.pool = None
                    v.draft_pool = None
                    after = memscope.live_total_bytes()
                    mem_steps.append({
                        "scope": "serve",
                        "reason": ev["reason"],
                        "t": ev["t_end"],
                        "replica": v.replica_id,
                        "live_bytes_before": before,
                        "live_bytes_after": after,
                        "step_down_bytes": before - after,
                        "leak_ok": leak["ok"],
                        "leaked_pages": leak["leaked_pages"],
                    })
        t += tick_s
        it += 1
        done_feeding = i >= len(arrivals) and not spike_backlog
        idle = not ran and all(
            not e.queue and all(s is None for s in e.slots) for e in reps
        )
        if (done_feeding and idle and not draining) or it >= max_iters:
            break

    # ---- the reshape cell: windows, drops, percentiles ----------------
    def pct(xs, q):
        if not xs:
            return None
        xs = sorted(xs)
        k = min(len(xs) - 1, max(0, round(q / 100 * (len(xs) - 1))))
        return xs[k]

    pad = 5 * tick_s  # settling margin after a drain completes
    windows = [
        (ev["t"], ev.get("t_end", ev["t"]) + pad) for ev in events
    ]

    def in_window(x: float) -> bool:
        return any(a <= x <= b for a, b in windows)

    all_done = [r for e in [*reps, *retired] for r in e.done]
    ttft_window = [
        r.first_token_t - r.arrival_t for r in all_done
        if r.first_token_t is not None and in_window(r.first_token_t)
    ]
    ttft_steady = [
        r.first_token_t - r.arrival_t for r in all_done
        if r.first_token_t is not None and not in_window(r.first_token_t)
    ]
    admitted = sum(e.admitted for e in [*reps, *retired])
    completed = sum(e.completed for e in [*reps, *retired])
    rejected = sum(
        sum(e.rejected.values()) for e in [*reps, *retired]
    )
    # graft-goodput (PR 20): SLO attainment on the DRIVER's virtual
    # clock — the elastic arm is deterministic, so this attainment
    # number reproduces bit-for-bit on any host (exactly where wall
    # would be noise-bound).  Drain-window demand = the handoff
    # re-submissions: served capacity the reshape consumed twice,
    # charged against availability even though zero requests dropped.
    from ddl25spring_tpu.obs import goodput as goodput_mod

    drain_demand = sum(int(ev.get("requeued") or 0) for ev in events)
    slo_goodput = goodput_mod.serve_goodput_cell(
        all_done, clock="virtual", wall_s=t if t > 0 else None,
        n_chips=replicas, offered=submitted, rejected=rejected,
        completed=completed, dropped=max(0, admitted - completed),
        drain_demand=drain_demand,
    )
    return {
        "goodput": slo_goodput,
        "events": events,
        "tick_s": tick_s,
        "iters": it,
        "wall_virtual_s": round(t, 6),
        "replicas_start": replicas,
        "replicas_end": len(reps),
        "max_replicas": max_replicas,
        "submitted": submitted,
        "admitted": admitted,
        "completed": completed,
        "rejected": rejected,
        # accepted-then-lost across every handoff: the zero the
        # --check-reshape gate pins (run-to-drain makes it exact)
        "dropped_requests": admitted - completed,
        "generated_tokens": sum(
            e.generated_tokens for e in [*reps, *retired]
        ),
        "ttft_s_p50_steady": pct(ttft_steady, 50),
        "ttft_s_p95_steady": pct(ttft_steady, 95),
        "ttft_s_p50_reshape": pct(ttft_window, 50),
        "ttft_s_p95_reshape": pct(ttft_window, 95),
        "reshape_window_requests": len(ttft_window),
        "steady_requests": len(ttft_steady),
        **({"mem_steps": mem_steps} if mem_steps else {}),
        # test hook only (the token-exactness pin): never serialized —
        # run_serve_bench does not pass keep_requests
        **({"_requests": all_done} if keep_requests else {}),
    }


def run_serve_bench(
    *,
    smoke: bool = False,
    model: str | None = None,
    obs_dir: str | None = None,
    duration_s: float | None = None,
    rate_rps: float | None = None,
    profile: str | None = None,
    seed: int | None = None,
    budget_s: float | None = None,
    ledger_path: str | None = None,
    temperature: float = 0.0,
    sentinel: bool | None = None,
    skip_ab: bool = False,
    skip_prefix_ab: bool = False,
    skip_spec_ab: bool = False,
    skip_tp_ab: bool = False,
    serve_tp: int | None = None,
    lineage: dict[str, Any] | None = None,
) -> dict[str, Any]:
    """The whole serving bench; returns the BENCH record (one JSON line
    with ``telemetry.serve``).  ``budget_s`` bounds the wall-clock ramp
    phase (None = run to drain).  ``lineage`` (bench's
    ``{"lineage_id", "attempt"}``) stamps the run's goodput doc and
    ledger row with the retry-lineage identity."""
    import jax

    from ddl25spring_tpu.models import llama
    from ddl25spring_tpu.obs import flight, sentinels, spans
    from ddl25spring_tpu.obs.logger import git_sha, host_fingerprint
    from ddl25spring_tpu.obs.report import SERVE_BASENAME
    from ddl25spring_tpu.serve.paged_model import paged_model
    from ddl25spring_tpu.serve.traffic import TrafficSpec, synth_trace

    t_start = time.perf_counter()
    from ddl25spring_tpu.utils.config import env_int

    model = model or ("tiny" if smoke else "ref")
    cfg = serve_model(model)
    knobs = engine_knobs(smoke=smoke)
    if serve_tp is not None:  # bench.py --serve-tp over the env knob
        knobs["tp"] = int(serve_tp)
    traffic_defaults = SMOKE_TRAFFIC if smoke else {
        "duration_s": 30.0, "rate_rps": 8.0, "profile": "ramp", "seed": 0,
    }
    profile = profile or traffic_defaults["profile"]
    # decode-length jitter (PR 13): per-request max_new variation on
    # the shared profile so the speculative A/B exercises variable
    # lengths; 0 (the default) leaves every existing trace untouched.
    # Zeroed off the shared profile — the knob has no effect there, and
    # letting a no-op env var into the ledger key would orphan the
    # run's trend group for nothing
    jitter = (
        env_int("DDL25_SERVE_JITTER", 0) if profile == "shared" else 0
    )
    spec = TrafficSpec(
        seed=traffic_defaults["seed"] if seed is None else seed,
        duration_s=(
            traffic_defaults["duration_s"] if duration_s is None
            else duration_s
        ),
        rate_rps=(
            traffic_defaults["rate_rps"] if rate_rps is None else rate_rps
        ),
        profile=profile,
        vocab_size=cfg.vocab_size,
        max_new_jitter=jitter,
    )
    trace = synth_trace(spec)
    flight.annotate(
        serve_model=model, serve_profile=spec.profile,
        serve_seed=spec.seed, serve_requests=len(trace),
    )

    # resident once, here: every engine of this run (the ramp's, the A/B
    # arms', the elastic replicas') then takes the same arrays as they are
    params = paged_model(cfg).resident(
        llama.init_llama_params(jax.random.PRNGKey(0), cfg)
    )

    # --- ramp phase: wall clock, the measured serving numbers ----------
    eng = _build_engine(
        params, cfg, knobs, clock="wall", temperature=temperature,
        sentinel=sentinel, trace_label="ramp",
    )
    # compile OFF the clock: TTFT measures serving, not XLA.  This
    # covers every prefill width and, with the prefix cache on, the
    # sharing ops
    with spans.span("serve.warmup", cat="serve"):
        eng.warmup()
    with spans.span("serve.ramp", cat="serve", requests=len(trace)):
        ramp = eng.run(trace, budget_s=budget_s, max_steps=50_000)

    # --- continuous-vs-static A/B: virtual clock, deterministic -------
    ab = None
    if not skip_ab:
        with spans.span("serve.ab", cat="serve"):
            ab = ab_compare(
                params, cfg, trace, knobs,
                temperature=temperature, sentinel=sentinel,
            )

    # --- cached-vs-cold prefix A/B: virtual clock, deterministic ------
    prefix_ab = None
    if not skip_prefix_ab and knobs.get("prefix_cache"):
        with spans.span("serve.prefix_ab", cat="serve"):
            prefix_ab = prefix_ab_compare(
                params, cfg, trace, knobs,
                temperature=temperature, sentinel=sentinel,
            )

    # --- spec-on-vs-off A/B: virtual clock, deterministic -------------
    spec_ab = None
    if not skip_spec_ab and knobs.get("spec_k"):
        with spans.span("serve.spec_ab", cat="serve"):
            spec_ab = spec_ab_compare(
                params, cfg, trace, knobs, sentinel=sentinel,
            )

    # --- tp-sharded vs dense A/B: virtual clock, deterministic --------
    tp_ab = None
    if not skip_tp_ab and int(knobs.get("tp") or 1) > 1:
        with spans.span("serve.tp_ab", cat="serve"):
            tp_ab = tp_ab_compare(
                params, cfg, trace, knobs,
                temperature=temperature, sentinel=sentinel,
            )

    # --- elastic replica reshaping (PR 14): armed chaos only ----------
    # DDL25_CHAOS=traffic_spike@k / capacity_change@k:N / device_loss@k
    # drives replica scale-up/down with page-pool handoff on the
    # deterministic driver clock; the reshape cell (events, TTFT
    # windows, zero-drop proof) is what --check-reshape gates.  The
    # spec engine path is excluded for now (two pools per replica —
    # the handoff story is the same, the bookkeeping is ROADMAP work).
    reshape = None
    from ddl25spring_tpu.ft.chaos import ChaosInjector

    chaos = ChaosInjector.from_env(state_dir=obs_dir)
    elastic_armed = chaos.pending("traffic_spike") + chaos.pending(
        "capacity_change"
    ) + chaos.pending("device_loss")
    if elastic_armed and not knobs.get("spec_k"):
        with spans.span("serve.elastic", cat="serve"):
            reshape = elastic_serve_run(
                params, cfg, trace, knobs, chaos=chaos,
                temperature=temperature, sentinel=sentinel,
            )
    elif elastic_armed:
        import warnings

        warnings.warn(
            "elastic serve reshaping skipped: speculative engines "
            "(DDL25_SERVE_SPEC=1) are not covered yet", stacklevel=2,
        )

    # --- graft-mem (PR 17): measured memory vs the static bill --------
    # high-water live bytes banded against the engine's exact static
    # accounting (params + pools), pool telemetry + drain-time leak
    # check, and the elastic step-downs — mem.json + a record:"mem"
    # ledger row, gated by tools/mem_report.py --check
    mem = None
    from ddl25spring_tpu.obs import memscope

    if memscope.enabled():
        leak = (
            eng.mem_leak_check() if eng.drained
            # a budget-cut ramp still holds live slots: their pages are
            # working state, not residue — the leak gate only speaks at
            # drain (the A/B arms and the smoke trace do drain)
            else {"ok": True, "leaked_pages": 0, "leaks": [],
                  "skipped": "ramp not drained"}
        )
        mem = memscope.mem_record(
            strategy=f"serve/{model}",
            # a tp-sharded run is a different measurement than a dense
            # one (per-chip residency divides) — the mesh dict is part
            # of mem_report's trend key, so sharded rows never gate
            # unsharded history (absent at tp=1: old keys must not
            # shift)
            mesh={"replicas": 1,
                  **({"tp": eng.tp} if eng.tp > 1 else {})},
            scope_cell=eng.memscope.cell(),
            # memscope live-bytes are GLOBAL logical bytes (a fake-
            # device shard set still materializes every logical buffer
            # on the host), so the band compares against the global
            # bill; the PER-CHIP bill — the quantity tp divides — is
            # what mem_budget_bytes() defaults to and what --check-tp
            # gates through the tp_ab cell.  At tp > 1 the engine's
            # sharded placement is a SECOND logical allocation next to
            # the bench's dense host copy (kept alive for the A/B
            # oracle arms), so the static bill covers both.
            budget=memscope.budget_cell(
                eng.memscope.live_bytes_peak,
                eng.mem_budget_bytes(per_chip=False) + (
                    sum(
                        x.size * x.dtype.itemsize
                        for x in jax.tree.leaves(params)
                    ) if eng.tp > 1 else 0
                ),
                source="serve_static_accounting",
            ),
            pool=eng.mem_pool_snapshot(),
            leaks=[leak],
            reshape_steps=(
                (reshape or {}).get("mem_steps")
                if reshape is not None else None
            ),
            extra={"profile": spec.profile, "seed": spec.seed},
        )

    record: dict[str, Any] = {
        "record": "serve",
        "ts": time.time(),
        "git_sha": git_sha(),
        "host": host_fingerprint(),
        "key": {
            "model": model,
            "profile": spec.profile,
            "seed": spec.seed,
            "rate_rps": spec.rate_rps,
            "duration_s": spec.duration_s,
            "page_len": knobs["page_len"],
            "n_pages": knobs["n_pages"],
            "max_slots": knobs["max_slots"],
            # sentinel guards price into every compiled call (host
            # callback per tick), so on/off rows are different
            # measurements — keyed apart, they never gate each other
            "sentinels": bool(sentinels.resolve(sentinel)[0]),
            # a prefix-cached engine is a different measurement than a
            # cold one (the whole point of the PR-11 A/B) — keyed apart
            "prefix_cache": bool(knobs.get("prefix_cache")),
            # spec fields (and jitter) enter the key ONLY when on: a
            # pre-PR-13 row's key string must not shift under it, or
            # every existing trend group would silently orphan
            **({
                "spec": True,
                "spec_k": knobs["spec_k"],
                "draft_layers": knobs["draft_layers"],
            } if knobs.get("spec_k") else {}),
            **({"max_new_jitter": jitter} if jitter else {}),
            # tp enters the key ONLY when sharded (PR 18) — same
            # discipline as the spec keys: pre-PR-18 rows' key strings
            # must not shift, and sharded runs trend separately from
            # dense history
            **({
                "tp": knobs["tp"],
                **({"weight_stream": True}
                   if knobs.get("weight_stream") else {}),
            } if int(knobs.get("tp") or 1) > 1 else {}),
            # an elastic run (replica reshaping armed) is a different
            # measurement context than a plain ramp — keyed apart so
            # --check-reshape's "latest row" can never be a plain run
            # that legitimately carries no reshape cell (and, like the
            # spec keys, absent on every pre-PR-14 row)
            **({"elastic": True} if reshape is not None else {}),
            **({
                "shared_prefixes": spec.shared_prefixes,
                "shared_prefix_len": spec.shared_prefix_len,
                "shared_suffix_len": spec.shared_suffix_len,
            } if spec.profile == "shared" else {}),
        },
        "requests": len(trace),
        "ramp": ramp,
        **({"ab": ab} if ab is not None else {}),
        **({"prefix_ab": prefix_ab} if prefix_ab is not None else {}),
        **({"spec_ab": spec_ab} if spec_ab is not None else {}),
        **({"tp_ab": tp_ab} if tp_ab is not None else {}),
        **({"reshape": reshape} if reshape is not None else {}),
        # bounded raw samples for serve_report's histogram (the summary
        # percentiles above are what the gates read)
        "ttft_s": [round(x, 6) for x in eng.ttft_s[:512]],
        "tick_wall_s": [round(x, 6) for x in eng.tick_wall_s[:512]],
        "bench_wall_s": round(time.perf_counter() - t_start, 3),
        **({"mem": mem} if mem is not None else {}),
    }

    # --- graft-goodput (PR 20): the SLO-denominated serving verdict ----
    # The ramp is judged on its own clock (wall — it is the measured
    # phase); the elastic arm's cell (virtual clock, reproducible on
    # any host) rides as ``elastic`` when chaos armed replica
    # reshaping.  goodput.json + the record:"goodput" ledger row are
    # what serve smokes gate SLO attainment on.
    from ddl25spring_tpu.obs import goodput as goodput_mod

    slo = goodput_mod.serve_slo()
    record["goodput"] = {
        "record": "goodput",
        "scope": "serve",
        **(lineage or {}),
        "chips": ramp.get("n_chips") or 1,
        "total_wall_s": ramp.get("wall_s"),
        **goodput_mod.serve_goodput_cell(
            eng.done, clock=eng.clock, wall_s=ramp.get("wall_s"),
            n_chips=ramp.get("n_chips") or 1,
            offered=int(ramp.get("admitted") or 0)
            + int(ramp.get("rejected") or 0),
            rejected=int(ramp.get("rejected") or 0),
            completed=int(ramp.get("completed") or 0),
            # a budget-cut ramp still holds live slots: their requests
            # are in flight, not dropped — only a drained ramp may call
            # the admitted-minus-completed gap a drop
            dropped=(
                max(
                    0,
                    int(ramp.get("admitted") or 0)
                    - int(ramp.get("completed") or 0),
                )
                if eng.drained else 0
            ),
            slo=slo,
        ),
        **(
            {"elastic": reshape["goodput"]}
            if reshape is not None and reshape.get("goodput") else {}
        ),
    }

    if obs_dir:
        os.makedirs(obs_dir, exist_ok=True)
        path = os.path.join(obs_dir, SERVE_BASENAME)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(record, f, indent=1, default=str)
        os.replace(tmp, path)
        record["serve_json"] = path
        if mem is not None:  # mem.json rides next to serve.json
            record["mem_json"] = memscope.write_run_mem(mem, obs_dir)
        record["goodput_json"] = goodput_mod.write_run_goodput(
            record["goodput"], obs_dir
        )
    if ledger_path is not None:
        from ddl25spring_tpu.obs.logger import append_ledger

        try:
            record["ledger"] = append_ledger(
                ledger_record(record), ledger_path
            )
            if mem is not None:  # the record:"mem" trend row
                append_ledger(mem, ledger_path)
            append_ledger(  # the record:"goodput" trend row
                goodput_mod.ledger_row(
                    record["goodput"],
                    strategy=f"serve/{model}",
                    mesh={
                        "replicas": 1,
                        **({"tp": eng.tp} if eng.tp > 1 else {}),
                    },
                    host=record["host"],
                    git_sha=record["git_sha"],
                    extra_key={"profile": spec.profile},
                ),
                ledger_path,
            )
        except OSError as e:  # a read-only FS must not kill the line
            record["ledger_error"] = str(e)
    return record


def ledger_record(record: dict[str, Any]) -> dict[str, Any]:
    """The trend row ``serve_report --check`` gates: the summary
    numbers only (never the raw sample lists — the ledger is read by a
    stdlib tool and grows one line per run)."""
    ramp = record["ramp"]
    out = {
        "record": "serve",
        "ts": record["ts"],
        "git_sha": record["git_sha"],
        "host": record["host"],
        "key": record["key"],
        "tokens_per_sec": ramp.get("tokens_per_sec"),
        "tokens_per_sec_per_chip": ramp.get("tokens_per_sec_per_chip"),
        "ttft_s_p50": ramp.get("ttft_s_p50"),
        "ttft_s_p95": ramp.get("ttft_s_p95"),
        # the per-request TTFT decomposition (PR 16): queue-wait /
        # prefill / first-decode percentiles, so a trend regression
        # names its component ("p95 regressed because queue-wait
        # doubled") without re-running the bench
        "ttft_decomp": ramp.get("ttft_decomp"),
        "tok_latency_s_p50": ramp.get("tok_latency_s_p50"),
        "tok_latency_s_p95": ramp.get("tok_latency_s_p95"),
        "admitted": ramp.get("admitted"),
        "rejected": ramp.get("rejected"),
        "completed": ramp.get("completed"),
        "page_pool_peak_occupancy": ramp.get("page_pool_peak_occupancy"),
        # the radix prefix cache's deterministic counters (None / 0 on
        # a cold engine) — prefix_hit_rate is a GATED key on
        # shared-prefix runs (serve_report --check)
        "prefix_hit_rate": ramp.get("prefix_hit_rate"),
        "prefill_tokens_saved": ramp.get("prefill_tokens_saved"),
        "prefill_flops_saved": ramp.get("prefill_flops_saved"),
        # speculative decoding's counters (None / 0 with spec off) —
        # acceptance_rate is a GATED key on spec runs
        "acceptance_rate": ramp.get("acceptance_rate"),
        "draft_tokens_accepted": ramp.get("draft_tokens_accepted"),
        "draft_tokens_rejected": ramp.get("draft_tokens_rejected"),
        # TP-sharded serving (PR 18): shard count + measured per-chip
        # residency (what divides under tp — the trend the shrink gate
        # reads)
        "tp": ramp.get("tp"),
        "weight_stream": ramp.get("weight_stream"),
        "pool_bytes_per_chip": ramp.get("pool_bytes_per_chip"),
        "param_bytes_per_chip": ramp.get("param_bytes_per_chip"),
    }
    ab = record.get("ab")
    if ab:
        out["ab"] = {
            k: ab.get(k)
            for k in (
                "budget_s", "continuous_tokens_at_budget",
                "static_tokens_at_budget", "advantage_tokens",
                "advantage_frac",
            )
        }
    pab = record.get("prefix_ab")
    if pab:
        out["prefix_ab"] = _prefix_ab_cell(pab)
    sab = record.get("spec_ab")
    if sab:
        out["spec_ab"] = _spec_ab_cell(sab)
    tab = record.get("tp_ab")
    if tab:
        out["tp_ab"] = _tp_ab_cell(tab)
    rsh = record.get("reshape")
    if rsh:
        out["reshape"] = _reshape_cell(rsh)
    return out


def _reshape_cell(rsh: dict[str, Any]) -> dict[str, Any]:
    """The elastic-reshape summary both the ledger row and
    telemetry.serve carry — what ``serve_report --check-reshape``
    gates.  Events keep only their identity facts (full dicts live in
    serve.json)."""
    return {
        "events": [
            {
                k: ev.get(k)
                for k in ("reason", "old", "new", "t", "t_end",
                          "requeued", "wall_s")
            }
            for ev in rsh.get("events") or []
        ],
        "replicas_start": rsh.get("replicas_start"),
        "replicas_end": rsh.get("replicas_end"),
        "dropped_requests": rsh.get("dropped_requests"),
        "admitted": rsh.get("admitted"),
        "completed": rsh.get("completed"),
        "rejected": rsh.get("rejected"),
        "ttft_s_p95_steady": rsh.get("ttft_s_p95_steady"),
        "ttft_s_p95_reshape": rsh.get("ttft_s_p95_reshape"),
        "reshape_window_requests": rsh.get("reshape_window_requests"),
        "steady_requests": rsh.get("steady_requests"),
    }


def _prefix_ab_cell(pab: dict[str, Any]) -> dict[str, Any]:
    """The prefix A/B summary both the ledger row and telemetry.serve
    carry — what ``serve_report --check-prefix-ab`` gates."""
    cached = pab.get("cached") or {}
    cold = pab.get("cold") or {}
    return {
        "budget_s": pab.get("budget_s"),
        "cached_tokens_at_budget": pab.get("cached_tokens_at_budget"),
        "cold_tokens_at_budget": pab.get("cold_tokens_at_budget"),
        "advantage_tokens": pab.get("advantage_tokens"),
        "advantage_frac": pab.get("advantage_frac"),
        "tokens_match": pab.get("tokens_match"),
        "compared_requests": pab.get("compared_requests"),
        "cached_tokens_per_sec_per_chip": cached.get(
            "tokens_per_sec_per_chip"
        ),
        "cold_tokens_per_sec_per_chip": cold.get(
            "tokens_per_sec_per_chip"
        ),
        "prefix_hit_rate": cached.get("prefix_hit_rate"),
        "prefill_tokens_saved": cached.get("prefill_tokens_saved"),
        "prefill_flops_saved": cached.get("prefill_flops_saved"),
    }


def _spec_ab_cell(sab: dict[str, Any]) -> dict[str, Any]:
    """The speculative A/B summary both the ledger row and
    telemetry.serve carry — what ``serve_report --check-spec-ab``
    gates."""
    spec_arm = sab.get("spec") or {}
    nospec_arm = sab.get("nospec") or {}
    return {
        "budget_s": sab.get("budget_s"),
        "spec_tokens_at_budget": sab.get("spec_tokens_at_budget"),
        "nospec_tokens_at_budget": sab.get("nospec_tokens_at_budget"),
        "advantage_tokens": sab.get("advantage_tokens"),
        "advantage_frac": sab.get("advantage_frac"),
        "tokens_match": sab.get("tokens_match"),
        "compared_requests": sab.get("compared_requests"),
        "spec_tokens_per_sec_per_chip": spec_arm.get(
            "tokens_per_sec_per_chip"
        ),
        "nospec_tokens_per_sec_per_chip": nospec_arm.get(
            "tokens_per_sec_per_chip"
        ),
        "acceptance_rate": spec_arm.get("acceptance_rate"),
        "draft_tokens_accepted": spec_arm.get("draft_tokens_accepted"),
        "draft_tokens_rejected": spec_arm.get("draft_tokens_rejected"),
    }


def _tp_ab_cell(tab: dict[str, Any]) -> dict[str, Any]:
    """The TP A/B summary both the ledger row and telemetry.serve
    carry — what ``serve_report --check-tp`` gates."""
    tp_arm = tab.get("sharded") or {}
    dense_arm = tab.get("dense") or {}
    return {
        "tp": tab.get("tp"),
        "budget_s": tab.get("budget_s"),
        "tp_tokens_at_budget": tab.get("tp_tokens_at_budget"),
        "dense_tokens_at_budget": tab.get("dense_tokens_at_budget"),
        "tokens_match": tab.get("tokens_match"),
        "compared_requests": tab.get("compared_requests"),
        "budget_shrunk": tab.get("budget_shrunk"),
        "tp_mem_budget_bytes_per_chip": tp_arm.get(
            "mem_budget_bytes_per_chip"
        ),
        "dense_mem_budget_bytes_per_chip": dense_arm.get(
            "mem_budget_bytes_per_chip"
        ),
        "tp_tokens_per_sec_per_chip": tp_arm.get(
            "tokens_per_sec_per_chip"
        ),
        "dense_tokens_per_sec_per_chip": dense_arm.get(
            "tokens_per_sec_per_chip"
        ),
        "pool_bytes_per_chip": tp_arm.get("pool_bytes_per_chip"),
        "param_bytes_per_chip": tp_arm.get("param_bytes_per_chip"),
        "weight_stream": tp_arm.get("weight_stream"),
    }


def serve_cell(record: dict[str, Any]) -> dict[str, Any]:
    """The ``telemetry.serve`` BENCH cell — every contract key the CI
    smoke asserts (tokens/sec/chip, TTFT + per-token p50/p95, admission
    counters, pool occupancy) plus the A/B verdict."""
    ramp = record["ramp"]
    cell = {
        "tokens_per_sec_per_chip": ramp.get("tokens_per_sec_per_chip"),
        "ttft_s_p50": ramp.get("ttft_s_p50"),
        "ttft_s_p95": ramp.get("ttft_s_p95"),
        "ttft_decomp": ramp.get("ttft_decomp"),
        "tok_latency_s_p50": ramp.get("tok_latency_s_p50"),
        "tok_latency_s_p95": ramp.get("tok_latency_s_p95"),
        "admitted": ramp.get("admitted"),
        "rejected": ramp.get("rejected"),
        "rejected_by_reason": ramp.get("rejected_by_reason"),
        "completed": ramp.get("completed"),
        "generated_tokens": ramp.get("generated_tokens"),
        "queue_depth_max": ramp.get("queue_depth_max"),
        "page_pool_peak_pages": ramp.get("page_pool_peak_pages"),
        "page_pool_peak_occupancy": ramp.get("page_pool_peak_occupancy"),
        "pool_ok_failures": ramp.get("pool_ok_failures"),
        "n_chips": ramp.get("n_chips"),
        "requests": record.get("requests"),
        "key": record.get("key"),
        "prefix_hit_rate": ramp.get("prefix_hit_rate"),
        "prefill_tokens_saved": ramp.get("prefill_tokens_saved"),
        "prefill_flops_saved": ramp.get("prefill_flops_saved"),
        "prefix": ramp.get("prefix"),
        "acceptance_rate": ramp.get("acceptance_rate"),
        "draft_tokens_accepted": ramp.get("draft_tokens_accepted"),
        "draft_tokens_rejected": ramp.get("draft_tokens_rejected"),
        "spec": ramp.get("spec"),
        "tp": ramp.get("tp"),
        "weight_stream": ramp.get("weight_stream"),
        "pool_bytes_per_chip": ramp.get("pool_bytes_per_chip"),
        "param_bytes_per_chip": ramp.get("param_bytes_per_chip"),
    }
    ab = record.get("ab")
    if ab:
        cell["ab"] = {
            "budget_s": ab.get("budget_s"),
            "continuous_tokens_at_budget": ab.get(
                "continuous_tokens_at_budget"
            ),
            "static_tokens_at_budget": ab.get("static_tokens_at_budget"),
            "advantage_tokens": ab.get("advantage_tokens"),
            "advantage_frac": ab.get("advantage_frac"),
        }
    pab = record.get("prefix_ab")
    if pab:
        cell["prefix_ab"] = _prefix_ab_cell(pab)
    sab = record.get("spec_ab")
    if sab:
        cell["spec_ab"] = _spec_ab_cell(sab)
    tab = record.get("tp_ab")
    if tab:
        cell["tp_ab"] = _tp_ab_cell(tab)
    rsh = record.get("reshape")
    if rsh:
        cell["reshape"] = _reshape_cell(rsh)
    for k in ("ledger", "ledger_error", "serve_json"):
        if record.get(k):
            cell[k] = record[k]
    return cell
