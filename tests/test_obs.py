"""The obs telemetry subsystem: span JSON against the Chrome-trace schema,
JSONL round-trips, ``jax.debug.callback`` counters under CPU jit, and the
zero-cost-when-disabled contract — instrumented step functions must lower
to HLO *identical* to uninstrumented ones when telemetry is off."""

import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ddl25spring_tpu import obs
from ddl25spring_tpu.obs.report import format_report, summarize_run
from ddl25spring_tpu.utils.mesh import make_mesh


@pytest.fixture(autouse=True)
def _obs_off():
    """Every test starts (and leaves) with telemetry disabled and a clean
    counter set — the global flag must never leak between tests."""
    obs.enable(False)
    obs.counters.reset()
    yield
    obs.enable(False)
    obs.counters.reset()


# ---------------------------------------------------------------- spans


def test_span_json_validates_against_chrome_trace_schema(tmp_path):
    rec = obs.SpanRecorder(process_name="test-proc")
    with rec.span("outer", cat="host", k=1), rec.span("inner"):
        time.sleep(0.002)
    rec.instant("marker", note="x")

    out = rec.to_chrome_trace()
    # JSON Object Format: traceEvents array + optional metadata
    assert isinstance(out["traceEvents"], list)
    assert out["displayTimeUnit"] in ("ms", "ns")
    json.dumps(out)  # must be serializable as-is

    phs = {e["ph"] for e in out["traceEvents"]}
    assert "X" in phs and "M" in phs and "i" in phs
    for e in out["traceEvents"]:
        assert isinstance(e["name"], str) and e["name"]
        assert isinstance(e["pid"], int)
        assert "tid" in e
        if e["ph"] == "X":  # complete events: ts + dur in microseconds
            assert e["ts"] >= 0 and e["dur"] >= 0
            assert isinstance(e["cat"], str)
        if e["ph"] == "i":
            assert e["s"] in ("g", "p", "t")
    # the inner span nests inside the outer one on the same thread
    spans = {e["name"]: e for e in out["traceEvents"] if e["ph"] == "X"}
    o, i = spans["outer"], spans["inner"]
    assert o["ts"] <= i["ts"] and i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e3
    # process_name metadata event carries the recorder's name
    meta = [e for e in out["traceEvents"] if e["ph"] == "M"]
    assert any(e["args"].get("name") == "test-proc" for e in meta)

    p = rec.save(str(tmp_path / "trace.json"))
    with open(p) as f:
        assert json.load(f)["traceEvents"]


def test_spans_threadsafe_and_disabled_is_noop():
    rec = obs.SpanRecorder()

    def worker(i):
        with rec.span(f"w{i}"):
            time.sleep(0.001)

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    names = {e["name"] for e in rec.to_chrome_trace()["traceEvents"]}
    assert {f"w{i}" for i in range(8)} <= names

    # module-level span() with telemetry disabled records nothing
    before = len(obs.get_recorder())
    with obs.span("ignored"):
        pass
    assert len(obs.get_recorder()) == before


# ---------------------------------------------------------------- rings


@pytest.fixture
def ring_cap(monkeypatch):
    """Set how many samples a ring made from here on keeps."""
    import importlib

    # the module, not the instance that ``obs`` re-exports under its name
    mod = importlib.import_module("ddl25spring_tpu.obs.counters")
    return lambda cap: monkeypatch.setattr(mod, "RING_CAP", cap)


def test_ring_keeps_samples_stamped_and_in_order(ring_cap):
    ring_cap(8)
    c = obs.CounterSet()
    for i in range(5):
        c.sample("serve.active_slots", 10 * i, t=100.0 + i)
    assert c.window("serve.active_slots", 101.0, 103.0) == [
        (101.0, 10.0), (102.0, 20.0), (103.0, 30.0)
    ]
    assert c.oldest_t("serve.active_slots") == 100.0
    # a span is written when it CLOSES, under the time it opened: a parent
    # follows its children in the ring and a window is still cut by stamp
    c.sample("x", 1.0, t=5.0)
    c.sample("x", 9.0, t=2.0)
    assert c.window("x", 0.0, 10.0) == [(5.0, 1.0), (2.0, 9.0)]
    # no stamp given: now, on perf_counter
    t0 = time.perf_counter()
    c.sample("now", 1.0)
    assert t0 <= c.oldest_t("now") <= time.perf_counter()


def test_ring_wraps_to_the_newest_and_says_how_far_back_it_reaches(ring_cap):
    ring_cap(4)
    c = obs.CounterSet()
    for i in range(10):
        c.sample("tick", float(i), t=float(i))
    assert c.window("tick", 0.0, 100.0) == [(6.0, 6.0), (7.0, 7.0), (8.0, 8.0), (9.0, 9.0)]
    # a window that opens before oldest_t has lost samples: the reader's
    # cue to return nothing rather than a wrong number
    assert c.wrapped("tick") and c.oldest_t("tick") == 6.0
    # exactly full is not wrapped: the whole series is there, so a series
    # that begins inside a window is told from one that lost its head
    d = obs.CounterSet()
    for i in range(4):
        d.sample("tick", float(i), t=float(i))
    assert not d.wrapped("tick") and not d.wrapped("never")
    assert d.oldest_t("tick") == 0.0 and len(d.window("tick", 0.0, 9.0)) == 4


def test_ring_of_a_name_never_sampled_is_empty_not_an_error():
    c = obs.CounterSet()
    assert c.window("nothing", 0.0, 1.0) == [] and c.oldest_t("nothing") is None
    c.sample("something", 1.0, t=0.5)
    c.reset()
    assert c.oldest_t("something") is None
    assert "something" not in json.dumps(c.snapshot())  # rings are not exported


def test_ring_is_threadsafe(ring_cap):
    ring_cap(1 << 12)
    c = obs.CounterSet()

    def worker(k):
        for i in range(500):
            c.sample("shared", float(k), t=float(i))

    ts = [threading.Thread(target=worker, args=(k,)) for k in range(8)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    got = c.window("shared", 0.0, 1e9)
    assert len(got) == 4000
    assert sorted(v for _, v in got) == sorted(float(k) for k in range(8) for _ in range(500))


def test_span_samples_its_ring_with_telemetry_off_and_records_nothing():
    before = len(obs.get_recorder())
    t0 = time.perf_counter()
    with obs.span("serve.step", cat="serve", rows=3, rids="1 2 3"):
        with obs.span("serve.admit"):
            time.sleep(0.002)
    t1 = time.perf_counter()
    assert len(obs.get_recorder()) == before  # Chrome JSON: gated by the flag
    (ts, dur), = obs.counters.window("serve.step", t0, t1)
    (tc, dc), = obs.counters.window("serve.admit", t0, t1)
    assert t0 <= ts <= tc and tc + dc <= ts + dur <= t1 and dc >= 0.002
    obs.instant("still_gated")
    assert len(obs.get_recorder()) == before


def test_span_records_chrome_json_only_with_telemetry_on():
    old = obs.set_recorder(obs.SpanRecorder())
    try:
        t0 = time.perf_counter()
        with obs.scoped(True):
            with obs.span("serve.prefill", cat="serve", rows=2):
                pass
        with obs.span("serve.prefill", cat="serve", rows=1):
            pass
        events = [e for e in obs.get_recorder().to_chrome_trace()["traceEvents"]
                  if e["ph"] == "X"]
    finally:
        obs.set_recorder(old)
    assert [(e["name"], e["cat"], e["args"]) for e in events] == [
        ("serve.prefill", "serve", {"rows": 2})
    ]
    # the ring has both, whatever the flag said
    assert len(obs.counters.window("serve.prefill", t0, time.perf_counter())) == 2


def test_span_lands_in_a_profiler_trace_with_its_stats(tmp_path):
    """No flag, no recorder: any open profiler session sees the span."""
    import glob

    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("serve.decode_tick", cat="serve", active=2, queue=0):
            pass
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = [
        dict(e.stats)
        for plane in jax.profiler.ProfileData.from_file(path).planes
        if plane.name == "/host:CPU"
        for line in plane.lines for e in line.events
        if e.name == "serve.decode_tick"
    ]
    assert found == [{"active": 2, "queue": 0}]


# --------------------------------------------------------------- logger


def test_metrics_jsonl_roundtrip(tmp_path):
    run = str(tmp_path / "run")
    meta = obs.run_metadata(
        mesh={"data": 2, "stage": 2}, layout="dppp", n_chips=4
    )
    assert meta["jax_version"] == jax.__version__
    with obs.MetricsLogger(run, meta=meta) as lg:
        for i in range(3):
            lg.log(
                step=i,
                wall_s=0.1 * (i + 1),
                samples=64,
                loss=jnp.float32(2.5 - i),  # jax scalar -> plain float
                label="primary",
            )
    recs = obs.read_jsonl(lg.path)
    assert len(recs) == 4
    assert recs[0]["record"] == "header"
    assert recs[0]["mesh"] == {"data": 2, "stage": 2}
    assert recs[0]["layout"] == "dppp"
    assert "git_sha" in recs[0] and "device" in recs[0]
    for i, r in enumerate(recs[1:]):
        assert r["record"] == "step" and r["step"] == i
        assert isinstance(r["loss"], float)  # coerced, not repr'd
    # appending reopens cleanly (crash-resume semantics)
    with obs.MetricsLogger(run) as lg2:
        lg2.log(step=3, wall_s=0.4)
    assert len(obs.read_jsonl(lg.path)) == 5
    # a FRESH run (meta given) truncates: re-running into a fixed run dir
    # must not pool two runs' records into one summary
    with obs.MetricsLogger(run, meta=meta) as lg3:
        lg3.log(step=0, wall_s=0.2)
    assert len(obs.read_jsonl(lg3.path)) == 2


# -------------------------------------------------------------- counters


def test_debug_callback_counters_fire_under_cpu_jit():
    obs.enable()

    @jax.jit
    def f(x):
        obs.counters.emit("t.loss", jnp.sum(x))
        return x * 2

    f(jnp.ones(4)).block_until_ready()
    f(jnp.full(4, 2.0)).block_until_ready()
    s = obs.counters.snapshot()["scalars"]["t.loss"]
    assert s["count"] == 2
    np.testing.assert_allclose(s["sum"], 4.0 + 8.0)
    np.testing.assert_allclose(s["last"], 8.0)
    assert s["min"] == 4.0 and s["max"] == 8.0


def test_mark_series_fire_inside_lax_scan():
    obs.enable()

    @jax.jit
    def f(x):
        def body(c, t):
            obs.counters.mark("t.tick", t)
            return c + 1.0, None

        out, _ = jax.lax.scan(body, x, jnp.arange(5))
        return out

    f(jnp.float32(0.0)).block_until_ready()
    series = obs.counters.snapshot()["series"]["t.tick"]
    assert [int(i) for i, _ in series] == [0, 1, 2, 3, 4]
    # host arrival times are monotone
    times = [t for _, t in series]
    assert times == sorted(times)


def test_counters_insert_nothing_when_disabled():
    def make(instrumented):
        def f(x):
            if instrumented:
                obs.counters.emit("t.x", jnp.sum(x))
                obs.counters.mark("t.m", jnp.int32(0))
            return x * 2

        return f

    x = jnp.ones(4)
    assert obs.enabled() is False
    # instrumentation helpers are trace-time no-ops when disabled, so the
    # two programs must be byte-identical: truly zero-cost
    text_instr = jax.jit(make(True)).lower(x).as_text()
    text_plain = jax.jit(make(False)).lower(x).as_text()
    assert text_instr == text_plain
    jax.jit(make(True))(x)
    assert obs.counters.snapshot()["scalars"] == {}

    with obs.scoped(True):
        assert jax.jit(make(True)).lower(x).as_text() != text_plain


# --------------------------------------- hot-path HLO equality (the pin)


def _dp_setup(devices8, instrument):
    from ddl25spring_tpu.parallel.dp import make_dp_train_step

    def loss_fn(p, batch, key):
        x, y = batch
        return jnp.mean((x @ p["w"] - y) ** 2)

    mesh = make_mesh(devices8[:2], data=2)
    tx = optax.sgd(0.1)
    step = make_dp_train_step(
        loss_fn, tx, mesh, per_shard_rng=False, instrument=instrument
    )
    p = {"w": jnp.ones((4, 2))}
    args = (
        p,
        tx.init(p),
        (jnp.ones((8, 4)), jnp.ones((8, 2))),
        jax.random.PRNGKey(0),
    )
    return step, args


def test_dp_step_hlo_identical_when_disabled(devices8):
    step_off, args = _dp_setup(devices8, instrument=False)
    plain = step_off.lower(*args).as_text()

    # default instrumentation, telemetry disabled -> identical HLO
    step_def, args = _dp_setup(devices8, instrument=None)
    assert step_def.lower(*args).as_text() == plain

    # telemetry enabled -> the callbacks actually land in the program
    with obs.scoped(True):
        step_on, args = _dp_setup(devices8, instrument=None)
        assert step_on.lower(*args).as_text() != plain


def test_instrument_true_overrides_disabled_flag(devices8):
    """Explicit ``instrument=True`` hard-enables: the counters land in the
    program even though the global flag is off (build AND trace time)."""
    assert obs.enabled() is False
    step_off, args = _dp_setup(devices8, instrument=False)
    step_on, args_on = _dp_setup(devices8, instrument=True)
    assert step_on.lower(*args_on).as_text() != step_off.lower(*args).as_text()
    jax.block_until_ready(step_on(*args_on))
    jax.effects_barrier()  # debug callbacks flush asynchronously
    assert "dp.loss" in obs.counters.snapshot()["scalars"]


def _het_setup(devices8, instrument):
    from ddl25spring_tpu.parallel.het_pipeline import make_het_pipeline_loss

    mesh = make_mesh(devices8[:2], stage=2)
    loss = make_het_pipeline_loss(
        [lambda p, x: x * p, lambda p, x: x + p],
        lambda out, b: jnp.mean((out - b["y"]) ** 2),
        (4, 8),
        [(4, 8), (4, 8)],
        mesh,
        num_microbatches=2,
        instrument=instrument,
    )
    params = (jnp.float32(2.0), jnp.float32(1.0))
    batch = {"x": jnp.ones((8, 8)), "y": jnp.zeros((8, 8))}
    return loss, (params, batch)


def test_pipeline_loss_hlo_identical_when_disabled(devices8):
    loss_off, args = _het_setup(devices8, instrument=False)
    plain = jax.jit(loss_off).lower(*args).as_text()

    loss_def, args = _het_setup(devices8, instrument=None)
    assert jax.jit(loss_def).lower(*args).as_text() == plain

    with obs.scoped(True):
        loss_on, args = _het_setup(devices8, instrument=None)
        assert jax.jit(loss_on).lower(*args).as_text() != plain


def test_pipeline_tick_counters_and_schedule_statics(devices8):
    obs.enable()
    loss, args = _het_setup(devices8, instrument=None)
    v = jax.jit(loss)(*args)
    assert np.isfinite(float(v))
    snap = obs.counters.snapshot()
    # T = M + S - 1 = 3 ticks, once per stage device
    assert len(snap["series"]["pipeline.tick"]) == 3 * 2
    assert snap["static"]["pipeline.num_stages"] == 2
    assert snap["static"]["pipeline.num_microbatches"] == 2
    np.testing.assert_allclose(
        snap["static"]["pipeline.bubble_fraction_gpipe"], 1 / 3
    )


# ---------------------------------------------------------------- report


def test_gpipe_bubble_fraction_math():
    assert obs.gpipe_bubble_fraction(1, 8) == 0.0
    np.testing.assert_allclose(obs.gpipe_bubble_fraction(2, 2), 1 / 3)
    np.testing.assert_allclose(obs.gpipe_bubble_fraction(4, 12), 0.2)


def test_summarize_run_and_format(tmp_path):
    run = str(tmp_path / "run")
    with obs.MetricsLogger(
        run,
        meta=obs.run_metadata(
            mesh={"data": 1},
            layout="dp",
            n_chips=1,
            num_stages=2,
            num_microbatches=4,
        ),
    ) as lg:
        walls = [0.10, 0.10, 0.10, 0.10, 0.10, 0.10, 0.10, 0.10, 0.10, 1.0]
        for i, w in enumerate(walls):
            lg.log(step=i, wall_s=w, samples=100, loss=1.0, label="primary")
        # flops arrive in a late supplementary header — must merge
        lg.log(record="header", flops_per_step=1e9, peak_flops_per_chip=1e10)
    obs.counters.save(run)

    s = summarize_run(run)
    ph = s["phases"]["primary"]
    assert ph["steps"] == 10
    # p50 must shrug off the one 1.0 s outlier (the GC-pause scenario)
    np.testing.assert_allclose(ph["step_s_p50"], 0.10)
    assert ph["step_s_p95"] > 0.5
    np.testing.assert_allclose(ph["steps_per_sec_p50"], 10.0)
    np.testing.assert_allclose(ph["samples_per_sec_per_chip_p50"], 1000.0)
    np.testing.assert_allclose(ph["mfu"], 1e9 / 0.10 / 1e10)
    np.testing.assert_allclose(s["bubble_fraction"], 0.2)

    text = format_report(s)
    for token in ("step p50", "step p95", "MFU", "bubble fraction", "0.2000"):
        assert token in text, f"report is missing {token!r}"


def test_summarize_run_normalizes_fused_steps(tmp_path):
    """Scan-fused phases log one record per DISPATCH of k train steps;
    the summary must report per-train-step units (steps/sec, MFU) or the
    fused phase reads k times slower than it is."""
    run = str(tmp_path / "run")
    with obs.MetricsLogger(
        run, meta=obs.run_metadata(n_chips=1)
    ) as lg:
        for i in range(6):
            # 0.4 s per dispatch of 4 fused steps = 0.1 s/step
            lg.log(step=i, wall_s=0.4, samples=400, fused_steps=4,
                   label="hbm-scan")
        lg.log(record="header", flops_per_step=1e9, peak_flops_per_chip=1e10)

    ph = summarize_run(run)["phases"]["hbm-scan"]
    assert ph["steps"] == 24 and ph["fused_steps"] == 4
    assert ph["dispatches"] == 6
    np.testing.assert_allclose(ph["step_s_p50"], 0.10)
    np.testing.assert_allclose(ph["steps_per_sec_p50"], 10.0)
    np.testing.assert_allclose(ph["samples_per_sec_per_chip_p50"], 1000.0)
    np.testing.assert_allclose(ph["mfu"], 1e9 / 0.10 / 1e10)


def test_tick_interval_collapses_shards_and_scan_restarts(tmp_path):
    """The tick series holds one arrival PER SHARD per tick, and the tick
    index restarts each scan invocation; the cadence estimate must use
    first-arrival-per-index consecutive transitions only."""
    import json as _json
    import os as _os

    run = str(tmp_path / "run")
    with obs.MetricsLogger(run, meta=obs.run_metadata()) as lg:
        lg.log(step=0, wall_s=1.0)
    # 2 shards x 3 ticks x 2 scan invocations, 0.1 s real tick interval,
    # shard echoes ~1 ms apart, 5 s between invocations
    series = []
    for t0 in (0.0, 5.0):
        for idx in range(3):
            series.append([idx, t0 + 0.1 * idx])
            series.append([idx, t0 + 0.1 * idx + 0.001])
    with open(_os.path.join(run, "counters.json"), "w") as f:
        _json.dump(
            {"scalars": {}, "series": {"pipeline.tick": series}, "static": {}},
            f,
        )
    s = summarize_run(run)
    np.testing.assert_allclose(s["tick_interval_s_p50"], 0.1, rtol=0.05)


# ---------------------------------------- satellite: StepTimer percentiles


def test_steptimer_percentiles_and_p50_rate():
    from ddl25spring_tpu.utils.tracing import StepTimer

    st = StepTimer(warmup=0)
    st.times = [0.1] * 9 + [1.0]  # one GC-pause outlier
    np.testing.assert_allclose(st.p50_step_s, 0.1)
    assert st.p95_step_s > 0.5
    np.testing.assert_allclose(st.min_step_s, 0.1)
    np.testing.assert_allclose(st.mean_step_s, 0.19)
    # the headline rate uses p50: the outlier must not skew it
    np.testing.assert_allclose(st.steps_per_sec(), 10.0)

    with pytest.raises(ValueError, match="no timed steps"):
        StepTimer().p50_step_s


# ------------------------------------- satellite: flops warning, not raise


def test_compiled_flops_warns_and_returns_none_when_unavailable(caplog):
    from ddl25spring_tpu.utils.flops import compiled_flops, mfu

    class Broken:
        def lower(self, *a, **k):
            raise RuntimeError("no cost model on this backend")

    with caplog.at_level("WARNING", logger="ddl25spring_tpu.utils.flops"):
        assert compiled_flops(Broken()) is None
    assert any("cost analysis" in r.message for r in caplog.records)
    # and the mfu path degrades to (None, None) instead of raising
    assert mfu(None, 0.1) == (None, None)
    assert mfu(1e9, 0.0) == (None, None)


# ------------------------- the shared trend ledger survives a torn tail


@pytest.mark.parametrize("kind", ["serve", "goodput", "mem"])
def test_appended_row_survives_a_torn_tail(tmp_path, kind):
    """A writer killed mid-line must not cost the gate its whole rows:
    each reader returns the one row of its kind and skips the torn one."""
    import importlib

    from ddl25spring_tpu.obs.logger import append_ledger

    led = str(tmp_path / "sub" / "ledger.jsonl")  # parent made on first use
    others = [k for k in ("serve", "goodput", "mem") if k != kind]
    for k in (others[0], kind, others[1]):
        assert append_ledger({"record": k, "strategy": f"toy-{k}"}, led) == led
    with open(led, "a") as f:
        f.write('{"record": "%s", "torn' % kind)  # killed mid-write
    reader = importlib.import_module(f"tools.{kind}_report")
    (row,) = reader.read_ledger(led)
    assert row == {"record": kind, "strategy": f"toy-{kind}"}
