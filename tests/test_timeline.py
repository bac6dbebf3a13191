"""graft-trace (``ddl25spring_tpu/obs/timeline.py`` + serve wiring +
``tools/trace_export.py``): the unified run timeline.

The load-bearing pins:

- **schema** — every declared event kind round-trips strict JSON
  through ``timeline.jsonl`` with its required payload fields, and the
  envelope ``seq`` is strictly monotone (the contract ROADMAP-5's
  FL/RL workloads emit into).
- **TTFT decomposition sums to TTFT** — ``queue_wait + prefill +
  first_decode == ttft`` exactly on the virtual clock (float-exact by
  construction), within float tolerance on the wall clock.
- **zero cost when off** — with ``DDL25_OBS=0`` the engine's token
  streams and virtual clock are BITWISE identical to an instrumented
  run, and the serve decode tick lowers to byte-identical HLO.
- **the elastic handoff narrates completely** — device_loss emits
  drain / per-request handoff / reshape / reshape_end events, and
  ``trace_export --check`` proves no admitted request's span chain is
  left without a terminal.
"""

from __future__ import annotations

import json

import jax
import numpy as np
import pytest

from ddl25spring_tpu.models import llama
from ddl25spring_tpu.obs import state
from ddl25spring_tpu.obs.recorder import flight
from ddl25spring_tpu.obs.timeline import (
    EVENT_KINDS,
    MIRRORED_FLIGHT_KINDS,
    read_timeline,
    timeline,
)
from ddl25spring_tpu.serve.engine import Reservoir, ServeEngine
from ddl25spring_tpu.utils.config import LlamaConfig

CFG = LlamaConfig(
    vocab_size=64, dmodel=16, num_heads=2, n_layers=2, ctx_size=32,
    dtype="float32",
)


@pytest.fixture(scope="module")
def params():
    return llama.init_llama_params(jax.random.PRNGKey(0), CFG)


def make_engine(params, **kw):
    # the test_serve smoke geometry: every compiled program rides the
    # session-wide _PROGRAM_CACHE shared with tests/test_serve.py
    kw.setdefault("page_len", 4)
    kw.setdefault("n_pages", 16)
    kw.setdefault("max_slots", 2)
    kw.setdefault("pages_per_seq", 4)
    kw.setdefault("prefill_batch", 1)
    kw.setdefault("max_prompt_len", 8)
    kw.setdefault("clock", "virtual")
    return ServeEngine(params, CFG, **kw)


def drain(eng, max_steps: int = 500):
    steps = 0
    while eng.queue or any(s is not None for s in eng.slots):
        eng.step()
        steps += 1
        assert steps < max_steps, "engine failed to drain"


@pytest.fixture()
def tl(tmp_path):
    """The module-singleton timeline, configured at a fresh dir and
    handed back reset afterwards (other tests share the singleton)."""
    timeline.configure(str(tmp_path))
    try:
        yield timeline
    finally:
        timeline.configure(None)


# ------------------------------------------------------- schema pins


def _fill(fields):
    return {
        f: ("device_loss" if f == "reason" else 1) for f in fields
    }


def test_every_event_kind_round_trips_strict_json(tl, tmp_path):
    with state.scoped(True):
        for kind, req in EVENT_KINDS.items():
            tl.emit(kind, vt=0.5, engine="t", replica=0, **_fill(req))
        tl.flush()
    header, events = read_timeline(str(tmp_path))
    assert header["time_origin_unix_s"] > 0
    assert header["capacity"] == tl._ring.maxlen
    assert len(events) == len(EVENT_KINDS)
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    by_kind = {e["kind"]: e for e in events}
    assert set(by_kind) == set(EVENT_KINDS)
    for kind, req in EVENT_KINDS.items():
        e = by_kind[kind]
        for f in req:
            assert f in e, f"{kind} lost required field {f}"
        # the envelope every event carries
        assert e["record"] == "event"
        assert isinstance(e["t_wall_s"], float)
        assert e["vt_s"] == 0.5 and e["engine"] == "t"
        assert e["replica"] == 0
    assert tl.counts() == {k: 1 for k in EVENT_KINDS}


def test_emit_is_gated_and_typed(tl):
    # disabled -> no-op before any validation (zero cost when off)
    assert state.enabled() is False
    assert tl.emit("serve_submit", rid=1) is None
    assert tl.emit("no_such_kind") is None
    assert tl.events() == []
    with state.scoped(True):
        with pytest.raises(ValueError, match="unknown timeline event"):
            tl.emit("no_such_kind")
        with pytest.raises(ValueError, match="missing required"):
            tl.emit("serve_submit", rid=1)  # prompt_len/max_new absent


def test_non_finite_payloads_stay_strict_json(tl, tmp_path):
    """A NaN in a payload is stringified (the flight `_json_safe`
    idiom), never written as a bare NaN literal — the strict reader
    must always be able to load the file."""
    with state.scoped(True):
        tl.emit("serve_submit", rid=1, prompt_len=4,
                max_new=float("nan"))
        tl.flush()
    _, events = read_timeline(str(tmp_path))
    assert events[0]["max_new"] == "nan"


def test_flight_tap_mirrors_only_narrating_kinds(tl):
    assert "chaos" in MIRRORED_FLIGHT_KINDS
    assert "serve_tick" not in MIRRORED_FLIGHT_KINDS
    with state.scoped(True):
        flight.record(kind="chaos", fault="device_loss", step=2)
        flight.record(kind="serve_tick", step=3)
    mirrored = tl.events("chaos")
    assert len(mirrored) == 1
    assert mirrored[0]["fault"] == "device_loss"
    # the flight envelope is renamed so the timeline's own wins
    assert "flight_seq" in mirrored[0]
    assert tl.events("serve_tick") == []
    # disabled -> the tap emits nothing
    flight.record(kind="chaos", fault="bit_flip", step=4)
    assert len(tl.events("chaos")) == 1


# ------------------------------------------------- Reservoir (sat. 2)


def test_reservoir_below_cap_is_exact_ordered_list():
    r = Reservoir(cap=8)
    for x in [3.0, 1.0, 2.0]:
        r.append(x)
    assert list(r) == [3.0, 1.0, 2.0]
    assert len(r) == 3 and bool(r)
    assert r[0] == 3.0 and r[-1] == 2.0 and r[:2] == [3.0, 1.0]
    s = r.summary()
    assert s["count"] == 3 and s["sampled"] == 3
    assert s["max"] == 3.0 and s["min"] == 1.0 and s["mean"] == 2.0


def test_reservoir_caps_memory_but_keeps_exact_extremes():
    r = Reservoir(cap=16)
    n = 10_000
    for i in range(n):
        r.append(float(i))
    assert len(r) == 16          # host memory bounded
    assert r.count == n          # exact count over the full series
    assert r.max == float(n - 1) and r.min == 0.0
    assert r.summary()["mean"] == pytest.approx((n - 1) / 2)
    assert not r or all(0.0 <= x <= n - 1 for x in r)


def test_reservoir_clear_restores_deterministic_sampling():
    a, b = Reservoir(cap=4), Reservoir(cap=4)
    for x in range(100):
        a.append(float(x))
        b.append(float(x))
    assert list(a) == list(b)  # seeded: same series, same sample
    kept = list(a)
    a.clear()
    assert len(a) == 0 and a.count == 0 and not a
    for x in range(100):
        a.append(float(x))
    assert list(a) == kept  # clear() re-arms the same RNG stream


def test_reservoir_tolerates_non_numeric_entries():
    r = Reservoir(cap=4)
    r.append((0.1, 0.2, 0.3))  # the ttft_decomp triple
    r.append((0.4, 0.5, 0.6))
    assert r.count == 2 and r.max is None and r.total == 0.0


# ------------------------------------- serve lifecycle + decomposition


def _run_traced(params, *, clock, n_req=4):
    eng = make_engine(params, clock=clock, prefill_batch=2)
    eng.warmup()
    with state.scoped(True):
        for i in range(n_req):
            req = eng.make_request([5 + i, 9, 11, 3], 6)
            assert eng.submit(req) is None
        drain(eng)
    return eng


def test_ttft_decomposition_sums_exactly_on_virtual_clock(params):
    timeline.configure(None)
    eng = _run_traced(params, clock="virtual")
    assert eng.ttft_decomp.count == len(eng.ttft_s) == 4
    for ttft, (q, p, f) in zip(eng.ttft_s, eng.ttft_decomp):
        assert q >= 0 and p >= 0
        # virtual clock: the parts re-assemble the whole EXACTLY
        assert q + p + f == pytest.approx(ttft, abs=1e-12)
    cell = eng.ttft_decomp_cell()
    assert cell["clock"] == "virtual" and cell["requests"] == 4
    for k in ("queue_wait_s_p50", "queue_wait_s_p95", "prefill_s_p50",
              "prefill_s_p95", "first_decode_s_p50",
              "first_decode_s_p95"):
        assert isinstance(cell[k], float)


def test_ttft_decomposition_sums_on_wall_clock_within_tolerance(params):
    timeline.configure(None)
    eng = _run_traced(params, clock="wall")
    assert eng.ttft_decomp.count == len(eng.ttft_s) == 4
    for ttft, (q, p, f) in zip(eng.ttft_s, eng.ttft_decomp):
        assert q + p + f == pytest.approx(ttft, abs=1e-6)


def test_request_lifecycle_events_ordered_and_vt_monotone(params):
    timeline.configure(None)
    eng = _run_traced(params, clock="virtual")
    # request-lifecycle events only: mem_sample shares the engine tag
    # but is resource telemetry, not request-scoped (no rid)
    evs = [e for e in timeline.events()
           if e.get("engine") == "serve" and e["kind"] != "mem_sample"]
    counts = {}
    for e in evs:
        counts[e["kind"]] = counts.get(e["kind"], 0) + 1
    assert counts["serve_submit"] == 4
    assert counts["serve_admit"] == 4
    assert counts["serve_prefill"] == 4
    assert counts["serve_first_token"] == 4
    assert counts["serve_done"] == 4
    # the virtual clock never runs backwards within a replica
    vts = [e["vt_s"] for e in evs if e.get("replica") == 0]
    assert vts == sorted(vts)
    # per-request ordering: submit < admit <= prefill <= first < done
    for rid in {e["rid"] for e in evs}:
        kinds = [e["kind"] for e in evs if e["rid"] == rid]
        assert kinds.index("serve_submit") < kinds.index("serve_admit")
        assert kinds.index("serve_admit") <= kinds.index("serve_prefill")
        assert kinds.index("serve_prefill") <= kinds.index(
            "serve_first_token")
        assert kinds.index("serve_first_token") < kinds.index(
            "serve_done")
    # the first_token event carries the decomposition, re-summing
    for e in evs:
        if e["kind"] == "serve_first_token":
            assert e["ttft_s"] == pytest.approx(
                e["queue_wait_s"] + e["prefill_s"]
                + e["first_decode_s"], abs=2e-6)
    assert eng.generated_tokens > 0


def test_reject_event_carries_reason(params):
    timeline.configure(None)
    eng = make_engine(params)
    with state.scoped(True):
        req = eng.make_request([1] * 9, 4)  # > max_prompt_len=8
        assert eng.submit(req) is not None
    (ev,) = timeline.events("serve_reject")
    assert ev["rid"] == req.rid and ev["reason"] == "bad_request"


def test_trace_label_none_keeps_engine_off_the_timeline(params):
    timeline.configure(None)
    eng = make_engine(params, trace_label=None)
    with state.scoped(True):
        req = eng.make_request([5, 9, 11, 3], 4)
        assert eng.submit(req) is None
        drain(eng)
    assert timeline.events() == []  # the A/B-arm discipline
    assert len(req.tokens) == 4


# ------------------------------------------------ zero cost when off


def test_disabled_run_is_bitwise_identical(params):
    """DDL25_OBS=0 leaves token streams AND the virtual clock bitwise
    unchanged — emission is host-only and consumes no RNG."""

    def run(on: bool, run_dir=None):
        eng = make_engine(params, prefill_batch=2)
        with state.scoped(on):
            if on:
                timeline.configure(run_dir)
            reqs = [
                eng.make_request([5 + i, 9, 11, 3], 6) for i in range(3)
            ]
            for r in reqs:
                assert eng.submit(r) is None
            drain(eng)
        return [r.tokens for r in reqs], eng.now(), eng._vtime

    base_tokens, base_now, base_vt = run(False)
    timeline.configure(None)
    on_tokens, on_now, on_vt = run(True)
    timeline.configure(None)
    assert on_tokens == base_tokens
    assert on_now == base_now and on_vt == base_vt


def test_decode_tick_hlo_identical_when_disabled(params):
    """The serve decode tick — the newly span-instrumented dispatch —
    lowers to byte-identical HLO whether telemetry is on or off: all
    PR 16 instrumentation is host-side."""
    from ddl25spring_tpu.serve import kv_pages
    from ddl25spring_tpu.serve.engine import make_decode_tick

    pool = kv_pages.init_page_pool(
        CFG, n_pages=16, page_len=4, max_slots=2, pages_per_seq=4,
    )
    args = (params, pool, jax.random.PRNGKey(0))

    def lower():
        tick = make_decode_tick(CFG, temperature=0.0, sentinel=False)
        return jax.jit(tick).lower(*args).as_text()

    with state.scoped(False):
        off = lower()
    with state.scoped(True):
        on = lower()
    assert on == off


# --------------------------------------- elastic handoff + exporter


def test_elastic_handoff_narrates_drain_reshape_and_chains(
    params, tmp_path
):
    """device_loss mid-traffic: the timeline carries the drain, every
    per-request handoff leg, the (mirrored) reshape and its window-end
    — and the exporter's chain check proves no admitted request was
    left without a terminal serve_done."""
    from ddl25spring_tpu.ft.chaos import ChaosInjector, parse_chaos
    from ddl25spring_tpu.serve.driver import elastic_serve_run
    from tools.trace_export import check_chains, merge

    knobs = dict(
        page_len=4, n_pages=16, max_slots=2, prefill_batch=2,
        max_prompt_len=8, max_queue=32, token_budget=None, eos_id=None,
        prefix_cache=False, spec_k=0, draft_layers=1,
    )
    prompt_a, new_a = [5, 9, 11, 3], 9
    prompt_b, new_b = [7, 2, 8], 6
    trace = [
        {"t": 0.0, "prompt": prompt_a, "max_new": new_a},
        {"t": 0.0, "prompt": prompt_b, "max_new": new_b},
        {"t": 0.001, "prompt": prompt_a, "max_new": new_a},
        {"t": 0.001, "prompt": prompt_b, "max_new": new_b},
        {"t": 0.002, "prompt": prompt_a, "max_new": new_a},
        {"t": 0.002, "prompt": prompt_b, "max_new": new_b},
    ]
    chaos = ChaosInjector(
        parse_chaos("device_loss@2"), state_dir=tmp_path / "chaos"
    )
    run_dir = tmp_path / "run"
    with state.scoped(True):
        timeline.configure(str(run_dir))
        try:
            cell = elastic_serve_run(
                params, CFG, trace, knobs, chaos=chaos, replicas=2,
            )
            timeline.flush()
        finally:
            timeline.configure(None)
    assert cell["dropped_requests"] == 0

    _, events = read_timeline(str(run_dir))
    counts = {}
    for e in events:
        counts[e["kind"]] = counts.get(e["kind"], 0) + 1
    assert counts.get("serve_submit") == 6
    assert counts.get("serve_drain", 0) >= 1
    # every requeued request got its own handoff leg, stamped with the
    # victim's stable replica id
    (drain_ev,) = [e for e in events if e["kind"] == "serve_drain"]
    assert counts.get("serve_drain_handoff", 0) == drain_ev["requeued"]
    for e in events:
        if e["kind"] == "serve_drain_handoff":
            assert e["from_replica"] == drain_ev["replica"]
    # the reshape arrives mirrored off the flight ring; its window end
    # is emitted directly when the victim finishes draining
    assert counts.get("reshape", 0) >= 1
    (end_ev,) = [e for e in events if e["kind"] == "reshape_end"]
    assert end_ev["reason"] == "device_loss"
    assert end_ev["t_end"] >= end_ev["t"]

    fails, stats = check_chains(events)
    assert fails == []
    assert stats["admitted"] == stats["complete"] > 0

    # the merged trace renders the window as a track-level span
    doc, _ = merge(str(run_dir))
    windows = [
        e for e in doc["traceEvents"]
        if e.get("cat") == "reshape_window" and e.get("ph") == "X"
    ]
    assert len(windows) == 1 and windows[0]["dur"] >= 1


def test_trace_export_merges_and_checks(params, tmp_path):
    """One obs-enabled engine run -> timeline.jsonl + trace.json ->
    trace_export writes one merged Perfetto doc whose request chains
    are complete (queue/prefill/decode X-slices + s/t/f flow arrows),
    and --check passes."""
    from ddl25spring_tpu.obs import spans
    from tools.trace_export import main as export_main

    run_dir = tmp_path / "run"
    with state.scoped(True):
        timeline.configure(str(run_dir))
        old_rec = spans.set_recorder(spans.SpanRecorder(
            process_name="test-serve"))
        try:
            eng = make_engine(params, prefill_batch=2)
            for i in range(3):
                assert eng.submit(
                    eng.make_request([5 + i, 9, 11, 3], 5)) is None
            drain(eng)
            timeline.flush()
            spans.get_recorder().save(str(run_dir / "trace.json"))
        finally:
            spans.set_recorder(old_rec)
            timeline.configure(None)

    assert export_main([str(run_dir), "--check"]) == 0
    with open(run_dir / "trace_merged.json") as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    x_names = [e["name"] for e in evs if e["ph"] == "X"]
    for name in ("queue", "prefill", "decode"):
        assert x_names.count(name) == 3
    # the host spans landed in the same doc, on the same axis
    assert "serve.decode_tick" in x_names
    assert "serve.prefill" in x_names
    # each request chain is flow-linked start/step/end
    for ph in ("s", "t", "f"):
        assert sum(1 for e in evs if e["ph"] == ph) == 3
    assert all(e.get("ts", 0) >= 0 for e in evs if e["ph"] != "M")


def test_trace_export_check_fails_on_orphan_admit(tmp_path):
    from tools.trace_export import main as export_main

    run_dir = tmp_path / "orphan"
    run_dir.mkdir()
    lines = [
        {"record": "timeline_header", "time_origin_unix_s": 1000.0,
         "capacity": 16, "pid": 1},
        {"record": "event", "seq": 0, "kind": "serve_submit",
         "t_wall_s": 0.0, "rid": 1, "prompt_len": 4, "max_new": 4,
         "engine": "serve", "replica": 0},
        {"record": "event", "seq": 1, "kind": "serve_admit",
         "t_wall_s": 0.1, "rid": 1, "slot": 0, "engine": "serve",
         "replica": 0},
        # no first_token, no terminal serve_done -> orphan
    ]
    with open(run_dir / "timeline.jsonl", "w") as f:
        for rec in lines:
            f.write(json.dumps(rec) + "\n")
    assert export_main([str(run_dir), "--check"]) == 1
    # without --check the same dir still exports (triage a torn run)
    assert export_main([str(run_dir)]) == 0


# ------------------------------------------------- report plumbing


def test_obs_report_folds_timeline_section(params, tmp_path):
    from ddl25spring_tpu.obs.report import format_report, summarize_run

    run_dir = tmp_path / "run"
    with state.scoped(True):
        timeline.configure(str(run_dir))
        try:
            eng = make_engine(params, prefill_batch=2)
            for i in range(3):
                assert eng.submit(
                    eng.make_request([5 + i, 9, 11, 3], 5)) is None
            drain(eng)
            timeline.flush()
        finally:
            timeline.configure(None)
    flight.dump(str(run_dir / "flight.json"), reason="test")
    summary = summarize_run(str(run_dir))
    tl_sum = summary["timeline"]
    assert tl_sum["counts"]["serve_first_token"] == 3
    assert 1 <= len(tl_sum["slowest_requests"]) <= 5
    slowest = tl_sum["slowest_requests"][0]
    assert slowest["ttft_s"] == max(
        r["ttft_s"] for r in tl_sum["slowest_requests"])
    for k in ("queue_wait_s", "prefill_s", "first_decode_s"):
        assert k in slowest
    text = format_report(summary)
    assert "timeline (timeline.jsonl" in text
    assert "slowest requests" in text
