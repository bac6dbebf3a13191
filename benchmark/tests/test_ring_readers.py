"""The five readers of the serving scheduler's rings, each on a hand-made
record and ring whose answers are known, and through the harness on the
tiny serving cell, where the program's rings are also held against the
harness's own numbers."""

import importlib
import json
import os

import pytest

from benchmark import ring, run, stats

# the module, not the instance that ``ddl25spring_tpu.obs`` re-exports
counters_mod = importlib.import_module("ddl25spring_tpu.obs.counters")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
NEW = ["prefill_fill_pct.serve", "slot_occupancy_pct.serve",
       "queue_wait_p95_ms.serve", "sched_host_ms.serve",
       "kv_gather_live_pct.serve"]
DEVICE = ["serve.prefill", "serve.draft_prefill", "serve.decode_tick",
          "serve.draft", "serve.verify"]


def reader(name):
    return run.load_module(BENCH, "readers", name)


@pytest.fixture
def rings(monkeypatch):
    """A fresh process-global counter set of 8 samples a name."""
    monkeypatch.setattr(counters_mod, "RING_CAP", 8)
    fresh = counters_mod.CounterSet()
    monkeypatch.setattr(counters_mod, "counters", fresh)
    return fresh


def record(**more):
    engine = {"max_slots": 32, "pages_per_seq": 24, "page_len": 16}  # 12,288 a pass
    return {"t_open_host": 100.0, "t_close_host": 200.0,
            "cell": {"engine": engine}, "requests": [], **more}


def fill(rings, name, samples):
    for t, v in samples:
        rings.sample(name, v, t=t)


def test_prefill_fill_is_prompt_tokens_over_scanned_positions(rings):
    # three passes, the first before the window
    fill(rings, "serve.prefill.prompt_tokens", [(90, 999), (110, 100), (150, 56)])
    fill(rings, "serve.prefill.scanned_positions", [(90, 2048), (110, 2048), (150, 1024)])
    assert reader("prefill_fill_pct").read(record(), {}) == pytest.approx(
        100.0 * 156 / 3072
    )


def test_no_prefill_in_the_window_reads_nothing(rings):
    fill(rings, "serve.prefill.prompt_tokens", [(90, 100), (250, 100)])
    fill(rings, "serve.prefill.scanned_positions", [(90, 2048), (250, 2048)])
    assert reader("prefill_fill_pct").read(record(), {}) is None
    # and a series that was never written
    assert reader("kv_gather_live_pct").read(record(), {}) is None
    assert reader("slot_occupancy_pct").read(record(), {}) is None
    assert reader("sched_host_ms").read(record(), {"device": DEVICE}) is None


def test_a_ring_that_wrapped_past_the_opening_reads_nothing(rings):
    """12 ticks into a ring of 8: the window's first ticks are gone, and a
    mean over the rest would be a wrong number."""
    ticks = [(95.0 + 5 * i, 32 - i) for i in range(12)]  # 95, 100, ..., 150
    fill(rings, "serve.active_slots", ticks)
    fill(rings, "serve.kv_live_positions", [(t, 100) for t, _ in ticks])
    assert rings.wrapped("serve.active_slots")
    assert rings.oldest_t("serve.active_slots") == 115.0
    assert ring.series(record(), "serve.active_slots") is None
    assert reader("slot_occupancy_pct").read(record(), {}) is None
    assert reader("kv_gather_live_pct").read(record(), {}) is None
    # the same ticks in a window that opens after the oldest kept: read
    late = record(t_open_host=120.0)
    assert reader("slot_occupancy_pct").read(late, {}) == pytest.approx(
        100.0 * (sum(32 - i for i in range(5, 12)) / 7) / 32
    )


def test_a_program_without_rings_reads_nothing(monkeypatch):
    """The parent commit's ``obs.counters`` has no ``window``: every new
    reader returns ``None`` and raises nothing."""
    class Old:
        def snapshot(self):
            return {}

    monkeypatch.setattr(counters_mod, "counters", Old())
    for name, args in [("prefill_fill_pct", {}), ("slot_occupancy_pct", {}),
                       ("kv_gather_live_pct", {}), ("sched_host_ms", {"device": DEVICE})]:
        assert reader(name).read(record(), args) is None


def test_slot_occupancy_and_live_share(rings):
    fill(rings, "serve.active_slots", [(90, 1), (110, 32), (120, 30), (130, 28), (210, 2)])
    assert reader("slot_occupancy_pct").read(record(), {}) == pytest.approx(93.75)
    # two passes in the window, each gathering 32 x 24 x 16 positions
    fill(rings, "serve.kv_live_positions", [(90, 7), (110, 4000), (120, 4400), (210, 1)])
    assert reader("kv_gather_live_pct").read(record(), {}) == pytest.approx(
        100.0 * 8400 / 24576
    )


def test_a_series_that_begins_inside_the_window_is_read_whole(rings):
    """A ring that never wrapped holds every sample: a span name first
    used after the window opened is no wrapped ring."""
    fill(rings, "serve.kv_live_positions", [(110, 4000), (120, 4400)])
    assert not rings.wrapped("serve.kv_live_positions")
    assert reader("kv_gather_live_pct").read(record(t_open_host=80.0), {}) == (
        pytest.approx(100.0 * 8400 / 24576)
    )
    # sched_host_ms: a device span (draft) that first ran inside the window
    fill(rings, "serve.draft", [(120.01, 0.03)])
    fill(rings, "serve.decode_tick", [(90.7, 0.2)])
    fill(rings, "serve.step", [(90.0, 1.0), (120.0, 0.10)])
    assert reader("sched_host_ms").read(record(), {"device": DEVICE}) == (
        pytest.approx(1e3 * 0.07)
    )


def test_queue_wait_is_from_arrival_to_the_admitting_prefill():
    def req(arrival, start, sent=True):
        return {"arrival_t": arrival, "prefill_start_t": start, "sent_in_window": sent}

    rows = [req(1.0, 1.010), req(2.0, 2.030), req(3.0, 3.020),
            req(0.5, 9.0, sent=False),  # sent before the window
            req(4.0, None)]             # never admitted: no sample
    got = reader("queue_wait_ms").read(record(requests=rows), {"q": 95})
    assert got == pytest.approx(10 + 0.95 * 2 * 10)  # p95 of 10, 20, 30 ms
    assert reader("queue_wait_ms").read(record(), {"q": 95}) is None


def test_sched_host_is_the_steps_self_time(rings):
    # step 1: 1.0 s holding a 0.5 s prefill and a 0.2 s tick -> 0.3 s own
    # step 2: 0.10 s holding a 0.06 s tick                    -> 0.04 s own
    # step 3: begins in the window, ends after it             -> left out
    fill(rings, "serve.prefill", [(90.1, 0.5), (110.1, 0.5)])
    fill(rings, "serve.decode_tick", [(90.7, 0.2), (110.7, 0.2), (120.02, 0.06), (199.95, 0.2)])
    fill(rings, "serve.admit", [(110.01, 0.05)])  # host time: not subtracted
    fill(rings, "serve.step", [(90.0, 1.0), (110.0, 1.0), (120.0, 0.10), (199.9, 0.3)])
    got = reader("sched_host_ms").read(record(), {"device": DEVICE})
    assert got == pytest.approx(1e3 * (0.3 + 0.04) / 2)


def serve_specs(bench_dir, cell):
    """The new metric files, made to apply to the tiny cell too."""
    for name in NEW:
        path = os.path.join(bench_dir, "metrics", name + ".json")
        spec = run.load_json(path)
        assert spec["workloads"] == ["olmo1b-serve-closed32"]
        spec["workloads"].append(cell)
        with open(path, "w") as f:
            json.dump(spec, f)


def test_the_five_metrics_print_in_a_traced_serving_run_only(bench_dir, monkeypatch):
    from ddl25spring_tpu.serve import driver

    seen = {}
    build, read = driver._build_engine, run.read_metrics

    def build_and_keep(*a, **kw):
        seen["eng"] = build(*a, **kw)
        return seen["eng"]

    def read_and_keep(bench_dir, specs, record):
        seen["record"] = record
        return read(bench_dir, specs, record)

    monkeypatch.setattr(driver, "_build_engine", build_and_keep)
    monkeypatch.setattr(run, "read_metrics", read_and_keep)
    serve_specs(bench_dir, "tiny-serve")
    t = run.run_cell("tiny-serve", 7, 0.7, True, bench_dir=bench_dir, allow_cpu=True)
    m = {k: v["value"] for k, v in t["metrics"].items()}
    assert set(NEW) <= set(m), m
    assert 0.0 < m["slot_occupancy_pct.serve"] <= 100.0
    assert 0.0 < m["kv_gather_live_pct.serve"] <= 100.0
    assert 0.0 < m["prefill_fill_pct.serve"] <= 100.0
    assert 0.0 <= m["queue_wait_p95_ms.serve"] and 0.0 < m["sched_host_ms.serve"]
    assert m["sched_host_ms.serve"] < 1e3 * 0.7

    # the program's rings beside the harness's own numbers, over the window
    rec, eng = seen["record"], seen["eng"]
    window = rec["t_close_host"] - rec["t_open_host"]
    ticks = [d for _, d in ring.series(rec, "serve.decode_tick")]
    assert len(ticks) == len(rec["tick_s"]) > 0  # decode_tick_ms.serve's samples
    assert stats.median(ticks) == pytest.approx(
        stats.median(rec["tick_s"]), rel=0.2, abs=2e-3
    )
    # nothing the engine does lies outside a serve.step span: the rest of
    # the window is the harness's own client loop
    steps = ring.series(rec, "serve.step")
    assert 0.5 * window < sum(d for _, d in steps) <= window
    # the prompt tokens of the passes are those of the requests they admitted
    prompt_len = {r.rid: r.prompt_len
                  for r in [*eng.done, *(s for s in eng.slots if s is not None)]}
    admitted = [r for r in rec["requests"] if r["prefill_start_t"] is not None
                and rec["t_open"] <= r["prefill_start_t"] <= rec["t_close"]]
    assert admitted
    assert ring.total(rec, "serve.prefill.prompt_tokens") == sum(
        prompt_len[r["rid"]] for r in admitted
    )
    assert m["prefill_fill_pct.serve"] == pytest.approx(
        100.0 * sum(prompt_len[r["rid"]] for r in admitted)
        / ring.total(rec, "serve.prefill.scanned_positions")
    )

    e = run.run_cell("tiny-serve", 7, 0.5, False, bench_dir=bench_dir, allow_cpu=True)
    assert not set(NEW) & set(e["metrics"])  # per-layer: --trace 1 only
    for cell in ("tiny-train", "olmo1b-train-2k", "olmo1b-dppp-2k"):
        names = [s["name"] for s in run.metric_specs(bench_dir, "train", cell, "per_layer")]
        assert not set(NEW) & set(names)
