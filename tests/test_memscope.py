"""graft-mem (``ddl25spring_tpu/obs/memscope.py`` + serve/bench wiring
+ ``tools/mem_report.py``): the runtime memory observatory.

The load-bearing pins:

- **leak injection fires, near-miss stays quiet** — a page seated in a
  page-table row across drain is named slot-and-rid by the detector; a
  pool whose only residue is prefix-cache-held pages passes.  A host
  list growing monotonically across a training window fires the growth
  detector ONCE naming the watch; a plateauing series never fires.
- **budget-vs-measured** — the serve engine's static bill covers its
  measured live-bytes high-water within the band, and
  ``mem_report --check`` turns the record's verdicts into exit codes.
- **zero cost when off** — with ``DDL25_MEMSCOPE=0`` token streams are
  bitwise identical and the decode tick lowers to byte-identical HLO
  (all sampling is host-side observation).
- **counter tracks** — ``trace_export`` renders ``mem_sample`` events
  as Perfetto ``"ph":"C"`` counters on the PR-16 time base, and
  ``--min-counter-tracks`` gates their presence.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import pytest

from ddl25spring_tpu.models import llama
from ddl25spring_tpu.obs import memscope, state
from ddl25spring_tpu.obs.memscope import (
    GrowthDetector,
    MemScope,
    Series,
    budget_cell,
    host_rss_bytes,
    live_array_summary,
    mem_cell,
    mem_record,
    pool_leak_check,
    pool_snapshot,
    write_run_mem,
)
from ddl25spring_tpu.obs.recorder import flight
from ddl25spring_tpu.obs.timeline import timeline
from ddl25spring_tpu.serve.engine import ServeEngine
from ddl25spring_tpu.utils.config import LlamaConfig

CFG = LlamaConfig(
    vocab_size=64, dmodel=16, num_heads=2, n_layers=2, ctx_size=32,
    dtype="float32",
)


@pytest.fixture(scope="module")
def params():
    return llama.init_llama_params(jax.random.PRNGKey(0), CFG)


def make_engine(params, **kw):
    # the test_serve smoke geometry — every compiled program rides the
    # session-wide program cache shared with tests/test_serve.py
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


# ------------------------------------------------ series + detector


def test_series_below_cap_is_exact_and_summarized():
    s = Series(cap=8)
    for v in (3, 1, 9, 4):
        s.append(v)
    assert list(s) == [3, 1, 9, 4]
    d = s.summary()
    assert d["count"] == 4 and d["sampled"] == 4
    assert d["max"] == 9 and d["min"] == 1


def test_series_caps_memory_but_keeps_exact_extremes():
    s = Series(cap=4)
    for v in range(100):
        s.append(v)
    assert len(s) == 4
    d = s.summary()
    assert d["count"] == 100 and d["sampled"] == 4
    assert d["max"] == 99 and d["min"] == 0


def test_growth_detector_fires_once_naming_the_source():
    det = GrowthDetector(window=4, min_growth_bytes=100)
    v = None
    for i in range(6):
        got = det.observe("replay_buffer", 1000 + i * 50, step=i)
        if got is not None:
            assert v is None, "detector fired twice"
            v = got
    assert v is not None
    assert v["kind"] == "mem" and v["source"] == "replay_buffer"
    assert v["growth_bytes"] >= 100 and v["window"] == 4
    # latched: the same still-growing series never re-fires
    assert det.observe("replay_buffer", 10_000, step=9) is None


def test_growth_detector_near_miss_plateau_stays_quiet():
    det = GrowthDetector(window=4, min_growth_bytes=100)
    # grows, but plateaus once inside every window -> not monotone
    series = [100, 200, 300, 300, 400, 500, 500, 600, 700, 700]
    assert all(
        det.observe("spiky", v, step=i) is None
        for i, v in enumerate(series)
    )


def test_growth_detector_below_floor_stays_quiet():
    det = GrowthDetector(window=4, min_growth_bytes=1 << 20)
    # strictly increasing, but by allocator-noise amounts
    assert all(
        det.observe("noise", 1000 + i, step=i) is None
        for i in range(10)
    )


# ------------------------------------------------- host-side probes


def test_host_rss_and_live_array_summary_sanity():
    rss = host_rss_bytes()
    assert rss is not None and rss > (1 << 20)
    x = jnp.ones((64, 64), jnp.float32)
    s = live_array_summary(top=5)
    assert s["count"] >= 1
    assert s["total_bytes"] >= x.size * 4
    assert s["largest"], s
    top = s["largest"][0]
    for k in ("shape", "dtype", "bytes", "sharding"):
        assert k in top, (k, top)
    assert sum(v["bytes"] for v in s["by_sharding"].values()) == (
        s["total_bytes"]
    )
    del x


def test_flight_dump_carries_live_array_summary(tmp_path):
    """Satellite 1: a crash dump answers 'what was resident' — the
    live-array census rides every flight.json."""
    keep = jnp.arange(4096, dtype=jnp.int32)  # resident at dump time
    path = flight.dump(str(tmp_path / "flight.json"), reason="test")
    doc = json.load(open(path))
    la = doc["live_arrays"]
    assert la["count"] >= 1
    assert la["total_bytes"] >= keep.nbytes
    assert doc["host_rss_bytes"] > (1 << 20)
    assert any(
        v["bytes"] == keep.nbytes for v in la["largest"]
    ), la["largest"]


# -------------------------------------------- scope sampling + gating


def test_memscope_sample_is_gated_and_thinned():
    resident = jnp.ones((32, 32), jnp.float32)  # noqa: F841
    scope = MemScope(label="t", every=2)
    assert scope.sample(0) is None  # obs off -> no-op
    with state.scoped(True):
        s0 = scope.sample(0)
        s1 = scope.sample(1)  # off-cadence (every=2)
        s2 = scope.sample(2)
    assert s0 is not None and s2 is not None and s1 is None
    assert scope.live_bytes_peak >= s0["live_bytes"] > 0
    assert scope.live_bytes_baseline == s0["live_bytes"]
    cell = scope.cell()
    assert cell["samples"] == 2 and cell["every"] == 2


def test_memscope_flag_gates_without_obs_state():
    scope = MemScope(label="t")
    with state.scoped(True), memscope.scoped(False):
        assert memscope.enabled() is False
        assert scope.sample(0) is None
    assert len(scope.live_bytes) == 0


def test_memscope_watch_growth_fires_into_flight(tmp_path):
    """Satellite 2: a host-side list growing monotonically across the
    window fires ONE violation naming the watch, mirrored to the
    flight ring as kind="mem"."""
    buf: list[bytes] = []
    scope = MemScope(label="train", window=4, min_growth_bytes=64)
    scope.watch("replay_buffer", lambda: len(buf) * 1024)
    with state.scoped(True):
        for i in range(8):
            buf.append(b"x")
            scope.sample(i)
    assert len(scope.violations) == 1
    v = scope.violations[0]
    assert v["source"] == "replay_buffer" and v["scope"] == "train"
    assert scope.cell()["growth_violations"] == [v]
    recs = [
        r for r in flight.snapshot()["records"]
        if r.get("kind") == "mem"
        and r.get("source") == "replay_buffer"
    ]
    assert recs, "growth violation never reached the flight ring"


def test_memscope_near_miss_watch_stays_quiet():
    sizes = [100, 200, 300, 300, 400, 500, 500, 600]  # plateaus
    it = iter(sizes)
    scope = MemScope(label="train", window=4, min_growth_bytes=64)
    scope.watch("steady_cache", lambda: next(it))
    with state.scoped(True):
        for i in range(len(sizes)):
            scope.sample(i)
    assert scope.violations == []


# ----------------------------------------------- pool telemetry


def test_pool_snapshot_and_clean_drain_leak_check(params):
    timeline.configure(None)
    eng = make_engine(params)
    with state.scoped(True):
        for i in range(3):
            assert eng.submit(
                eng.make_request([5 + i, 9, 11, 3], 4)) is None
        drain(eng)
    # the leak check first: it flushes the batched releases the drain
    # left pending, settling the device tables the snapshot reads
    leak = eng.mem_leak_check()
    assert leak["ok"] is True and leak["leaked_pages"] == 0
    assert leak["leaks"] == []
    snap = eng.mem_pool_snapshot()
    assert snap["n_pages"] == 16
    assert snap["used_pages"] == (
        snap["cache_held_pages"] + snap["table_held_pages"]
    )
    assert snap["table_held_pages"] == 0  # drained + flushed
    assert 0.0 <= snap["fragmentation"] <= 1.0
    # the histogram covers exactly the held pages (ref > 0)
    assert sum(snap["refcount_hist"].values()) == snap["used_pages"]
    # the sampler rode every tick: peak within the static bill's band
    assert eng.memscope.live_bytes_peak > 0
    budget = eng.mem_budget_bytes()
    assert budget > 0
    assert budget_cell(
        eng.memscope.live_bytes_peak, budget
    )["within_band"] is True


def test_injected_page_table_leak_is_named_by_slot_and_rid(params):
    """Satellite 2: seat a page back into a page-table row after drain
    — the detector must fail naming the slot and the last rid that
    occupied it, and the verdict must reach the flight ring."""
    timeline.configure(None)
    eng = make_engine(params)
    with state.scoped(True):
        req = eng.make_request([5, 9, 11, 3], 4)
        assert eng.submit(req) is None
        drain(eng)
        # the injection: page 7 held by slot 1's table row + refcount
        pool = dict(eng.pool)
        pool["page_table"] = pool["page_table"].at[1, 0].set(7)
        pool["refcount"] = pool["refcount"].at[7].add(1)
        pool["free"] = pool["free"].at[7].set(False)
        eng.pool = pool
        eng._slot_last_rid[1] = req.rid
        leak = eng.mem_leak_check()
    assert leak["ok"] is False
    assert leak["leaked_pages"] == 1
    (entry,) = [x for x in leak["leaks"] if x["held_by"] == "page_table"]
    assert entry["page"] == 7 and entry["slot"] == 1
    assert entry["rid"] == req.rid
    recs = [
        r for r in flight.snapshot()["records"]
        if r.get("kind") == "mem" and r.get("source") == "kv_pool_leak"
    ]
    assert recs and recs[-1]["leaked_pages"] == 1


def test_orphan_refcount_beyond_cache_budget_is_a_leak():
    import numpy as np

    pool = {
        "free": np.array([False, False, True, True]),
        "refcount": np.array([1, 1, 0, 0]),
        "page_table": np.full((2, 2), -1),
    }
    # both held pages accounted to the cache -> clean
    ok = pool_leak_check(pool, cache_held_pages=2)
    assert ok["ok"] is True and ok["leaks"] == []
    # only one accounted -> one orphan leak
    bad = pool_leak_check(pool, cache_held_pages=1)
    assert bad["ok"] is False and bad["leaked_pages"] == 1
    (entry,) = bad["leaks"]
    assert entry["held_by"] == "orphan_refcount"


def test_pool_snapshot_fragmentation_of_interleaved_free_pages():
    import numpy as np

    pool = {
        "free": np.array([True, False, True, False, True, True]),
        "refcount": np.array([0, 1, 0, 1, 0, 0]),
        "page_table": np.full((2, 2), -1),
    }
    snap = pool_snapshot(pool)
    assert snap["used_pages"] == 2 and snap["free_pages"] == 4
    assert snap["free_runs"]["count"] == 3
    assert snap["free_runs"]["max"] == 2
    assert snap["fragmentation"] == pytest.approx(1 - 2 / 4)


# ------------------------------------------------ zero cost when off


def test_tokens_bitwise_identical_with_memscope_off(params):
    """Satellite 3: DDL25_MEMSCOPE=0 under obs-on leaves token streams
    and the virtual clock bitwise unchanged — sampling is host-only."""
    timeline.configure(None)

    def run(mem_on: bool):
        eng = make_engine(params, prefill_batch=2)
        with state.scoped(True), memscope.scoped(mem_on):
            reqs = [
                eng.make_request([5 + i, 9, 11, 3], 6) for i in range(3)
            ]
            for r in reqs:
                assert eng.submit(r) is None
            drain(eng)
        return [r.tokens for r in reqs], eng.now(), eng._vtime

    off_tokens, off_now, off_vt = run(False)
    on_tokens, on_now, on_vt = run(True)
    assert on_tokens == off_tokens
    assert on_now == off_now and on_vt == off_vt


def test_decode_tick_hlo_identical_with_memscope_toggled(params):
    """Satellite 3: the decode tick lowers to byte-identical HLO with
    the scope on or off — graft-mem never touches a compiled program."""
    from ddl25spring_tpu.serve import kv_pages
    from ddl25spring_tpu.serve.engine import make_decode_tick

    pool = kv_pages.init_page_pool(
        CFG, n_pages=16, page_len=4, max_slots=2, pages_per_seq=4,
    )
    args = (params, pool, jax.random.PRNGKey(0))

    def lower():
        tick = make_decode_tick(CFG, temperature=0.0, sentinel=False)
        return jax.jit(tick).lower(*args).as_text()

    with state.scoped(True), memscope.scoped(False):
        off = lower()
    with state.scoped(True), memscope.scoped(True):
        on = lower()
    assert on == off


def test_mem_sample_timeline_events_present_iff_scope_on(
    params, tmp_path
):
    from ddl25spring_tpu.obs.timeline import read_timeline

    def run(mem_on: bool, sub: str):
        run_dir = tmp_path / sub
        timeline.configure(str(run_dir))
        try:
            with state.scoped(True), memscope.scoped(mem_on):
                eng = make_engine(params)
                assert eng.submit(
                    eng.make_request([5, 9, 11, 3], 4)) is None
                drain(eng)
                timeline.flush()
        finally:
            timeline.configure(None)
        _, events = read_timeline(str(run_dir))
        return [e for e in events if e["kind"] == "mem_sample"]

    on = run(True, "on")
    assert on, "no mem_sample events with the scope on"
    for e in on:
        assert e["live_bytes"] > 0
        assert e["engine"] == "serve"
        assert e["pool_pages"] == 16
        assert "pool_used" in e and "queue_depth" in e
    assert run(False, "off") == []


# -------------------------------------- record envelope + the gates


def _good_record(**over):
    scope = MemScope(label="t")
    with state.scoped(True):
        scope.sample(0)
    rec = mem_record(
        strategy="serve/tiny",
        mesh={"replicas": 1},
        scope_cell=scope.cell(),
        budget=budget_cell(100, 100),
        pool=None,
        leaks=[{"ok": True, "leaked_pages": 0, "leaks": []}],
    )
    rec.update(over)
    return rec


def test_mem_record_round_trips_through_mem_json_and_cell(tmp_path):
    rec = _good_record()
    assert rec["record"] == "mem" and rec["leaked_pages"] == 0
    path = write_run_mem(rec, str(tmp_path))
    assert json.load(open(path)) == json.loads(json.dumps(rec))
    cell = mem_cell(rec)
    assert cell["enabled"] is True
    assert cell["live_bytes_peak"] > 0
    assert cell["budget"]["within_band"] is True
    assert cell["leaked_pages"] == 0
    assert cell["growth_violations"] == 0


def test_budget_cell_band_semantics():
    assert budget_cell(149, 100, tol=0.5)["within_band"] is True
    assert budget_cell(151, 100, tol=0.5)["within_band"] is False
    assert budget_cell(100, None)["available"] is False
    assert budget_cell(100, 0)["available"] is False


def test_mem_report_check_passes_clean_and_fails_injected_leak(
    tmp_path,
):
    from tools.mem_report import main as mem_main

    good = tmp_path / "good"
    good.mkdir()
    write_run_mem(_good_record(), str(good))
    assert mem_main(["--run", str(good), "--check"]) == 0

    leaky = tmp_path / "leaky"
    leaky.mkdir()
    write_run_mem(_good_record(
        leaked_pages=2,
        leaks=[{"ok": False, "leaked_pages": 2, "leaks": [
            {"page": 7, "refcount": 1, "held_by": "page_table",
             "slot": 1, "rid": 3},
            {"page": 9, "refcount": 2, "held_by": "orphan_refcount"},
        ]}],
    ), str(leaky))
    assert mem_main(["--run", str(leaky), "--check"]) == 1

    breach = tmp_path / "breach"
    breach.mkdir()
    write_run_mem(
        _good_record(budget=budget_cell(200, 100, tol=0.5)),
        str(breach),
    )
    assert mem_main(["--run", str(breach), "--check"]) == 1
    # no mem.json at all -> no-data exit, distinct from a failure
    assert mem_main(["--run", str(tmp_path / "void"), "--check"]) == 2


def test_mem_report_require_step_down(tmp_path):
    from tools.mem_report import main as mem_main

    flat = tmp_path / "flat"
    flat.mkdir()
    write_run_mem(_good_record(reshape_steps=[]), str(flat))
    assert mem_main(
        ["--run", str(flat), "--check", "--require-step-down"]) == 1

    stepped = tmp_path / "stepped"
    stepped.mkdir()
    write_run_mem(_good_record(reshape_steps=[{
        "scope": "serve", "reason": "device_loss",
        "live_bytes_before": 1000, "live_bytes_after": 400,
        "step_down_bytes": 600, "leak_ok": True, "leaked_pages": 0,
    }]), str(stepped))
    assert mem_main(
        ["--run", str(stepped), "--check", "--require-step-down"]) == 0


def test_obs_report_exit_code_4_on_mem_violation(tmp_path):
    """Satellite 6: the documented exit-code matrix — a leaky mem.json
    under --check-health exits 4, distinct from health's 3."""
    from tools.obs_report import main as obs_main

    run_dir = tmp_path / "run"
    run_dir.mkdir()
    (run_dir / "metrics.jsonl").write_text("")
    write_run_mem(_good_record(leaked_pages=1), str(run_dir))
    assert obs_main([str(run_dir), "--check-health"]) == 4
    write_run_mem(_good_record(), str(run_dir))
    assert obs_main([str(run_dir), "--check-health"]) == 0


# ------------------------------------------------- counter tracks


def test_trace_export_renders_counter_tracks_and_gates(tmp_path):
    from tools.trace_export import main as export_main, merge

    run_dir = tmp_path / "run"
    run_dir.mkdir()
    lines = [
        {"record": "timeline_header", "time_origin_unix_s": 1000.0,
         "capacity": 16, "pid": 1},
    ]
    for i in range(4):
        lines.append({
            "record": "event", "seq": i, "kind": "mem_sample",
            "t_wall_s": 0.1 * i, "engine": "serve", "replica": 0,
            "live_bytes": 1000 + i, "rss_bytes": 5000 + i,
            "pool_used": i, "queue_depth": 4 - i,
            "tokens_per_s": 10.0 * i,
        })
    with open(run_dir / "timeline.jsonl", "w") as f:
        for rec in lines:
            f.write(json.dumps(rec) + "\n")

    doc, notes = merge(str(run_dir))
    counters = [e for e in doc["traceEvents"] if e.get("ph") == "C"]
    names = {e["name"] for e in counters}
    assert notes["counter_tracks"] == len(names) == 5
    # every counter rides the shared time base (t_wall_s * 1e6)
    assert sorted({e["ts"] for e in counters}) == pytest.approx(
        [0.1 * i * 1e6 for i in range(4)]
    )
    for e in counters:
        assert e["pid"] == 1_000_002
        (field,) = e["args"].keys()
        assert e["name"].startswith(f"{field} [serve/r0]")

    assert export_main(
        [str(run_dir), "--check", "--min-counter-tracks", "3"]) == 0
    assert export_main(
        [str(run_dir), "--check", "--min-counter-tracks", "6"]) == 1


def test_trace_export_counter_gate_fails_without_mem_samples(tmp_path):
    from tools.trace_export import main as export_main

    run_dir = tmp_path / "run"
    run_dir.mkdir()
    with open(run_dir / "timeline.jsonl", "w") as f:
        f.write(json.dumps({
            "record": "timeline_header", "time_origin_unix_s": 1000.0,
            "capacity": 16, "pid": 1,
        }) + "\n")
    assert export_main([str(run_dir), "--check"]) == 0
    assert export_main(
        [str(run_dir), "--check", "--min-counter-tracks", "1"]) == 1
