"""The five readers of the ``mistral4`` cell's per-layer metrics, each on a
hand-made record, ring and trace whose answers are known; and silent where
there is nothing to read (a program from before the kernel and the rings)."""

import importlib
import os

import pytest

from benchmark import flops, run

counters_mod = importlib.import_module("ddl25spring_tpu.obs.counters")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MATCH = {"match": ["moe_gmm"]}
LAYERS, HELD = 6, 32


def reader(name):
    return run.load_module(BENCH, "readers", name)


@pytest.fixture
def rings(monkeypatch):
    fresh = counters_mod.CounterSet()
    monkeypatch.setattr(counters_mod, "counters", fresh)
    return fresh


def record(**more):
    config = run.load_json(os.path.join(BENCH, "configs", "mistral-small-4-ep4.json"))
    assert (config["num_hidden_layers"], config["n_routed_experts"]) == (LAYERS, HELD)
    engine = {"max_slots": 64, "pages_per_seq": 96, "page_len": 16}
    return {"t_open_host": 100.0, "t_close_host": 200.0, "window_s": 100.0,
            "cell": {"engine": engine}, "config": config, "peaks": PEAKS,
            "trace": None, **more}


def trace(gmm_s, other_s, window_s=3.0, gaps=(0.004, 0.003)):
    return {"window_s": window_s, "busy_s": gmm_s + other_s,
            "longest_gaps": [["serve_decode_tick", g, 0] for g in gaps], "op_self_s": {
        "moe_gmm.24 bf16[256,2048] tpu_custom_call": gmm_s / 2,
        "moe_gmm.26 bf16[256,4096] tpu_custom_call": gmm_s / 2,
        "fusion.374 bf16[6144,16,256]": other_s}}


def fill(rings, name, samples):
    for t, v in samples:
        rings.sample(name, v, t=t)


def test_busy_share_is_the_kernels_self_time_over_busy_time():
    assert reader("moe_gmm_busy_pct").read(record(trace=trace(1.2, 0.8)), MATCH) == (
        pytest.approx(60.0))
    assert reader("moe_gmm_busy_pct").read(record(trace=trace(0.0, 0.8)), MATCH) is None
    assert reader("moe_gmm_busy_pct").read(record(), MATCH) is None  # no trace


def test_experts_hit_and_imbalance_from_the_rings(rings):
    # two ticks in the window (one before it): 6 layers x 32 held = 192 pairs
    fill(rings, "serve.moe.experts_hit", [(90, 1), (110, 168), (150, 160)])
    fill(rings, "serve.moe.assignments_here", [(90, 1), (110, 384), (150, 384)])
    fill(rings, "serve.moe.load_max", [(90, 1), (110, 36), (150, 30)])
    assert reader("moe_experts_hit_pct").read(record(), {}) == pytest.approx(
        100.0 * 328 / (2 * 192))
    # per layer: mean 768 / 12 / 32 = 2, maxima average 66 / 12 = 5.5
    assert reader("moe_load_max_over_mean").read(record(), {}) == pytest.approx(2.75)


def test_roofline_is_least_rate_over_measured_rate(rings):
    """100 equal ticks in a 100 s window, each 6 calls of 64 assignments on
    28 experts: the bound is bytes, and a slice that spends that long a
    second in the kernel reads 100 %."""
    family = run.load_module(BENCH, "families", "mistral4")
    call_s, bound = flops.roofline_seconds(*family.moe_gmm_flops_bytes(64, 28), PEAKS)
    assert bound == "memory" and call_s == pytest.approx(
        2 * (3 * 4096 * 2048 * 28 + 2 * 4096 * 64) / 819e9)
    ticks = [(100.5 + i, 0) for i in range(100)]
    fill(rings, "serve.moe.assignments_here", [(t, 64 * LAYERS) for t, _ in ticks])
    fill(rings, "serve.moe.experts_hit", [(t, 28 * LAYERS) for t, _ in ticks])
    least_rate = 100 * LAYERS * call_s / 100.0
    rec = record(trace=trace(gmm_s=3.0 * least_rate / 0.8, other_s=1.0))
    assert reader("moe_gmm_roofline").read(rec, MATCH) == pytest.approx(80.0)
    # a pause of the machine in the slice (the same kernel time in a slice
    # 1.35 s longer, the device idle meanwhile) or in the window (one tick
    # of 5 s among ticks of 18 ms, in a window 5 s longer) moves nothing
    paused = trace(gmm_s=3.0 * least_rate / 0.8, other_s=1.0, window_s=4.35,
                   gaps=(1.35, 0.004))
    assert reader("moe_gmm_roofline").read(dict(rec, trace=paused), MATCH) == (
        pytest.approx(80.0))
    fill(rings, "serve.decode_tick", [(t, 0.018) for t, _ in ticks[:-1]] + [(199.6, 5.018)])
    late = dict(rec, t_close_host=205.0, window_s=105.0)
    assert reader("moe_gmm_roofline").read(late, MATCH) == pytest.approx(80.0)
    # no peaks (a CPU run), no trace, or no kernel in the trace: silent
    assert reader("moe_gmm_roofline").read(dict(rec, peaks=None), MATCH) is None
    assert reader("moe_gmm_roofline").read(dict(rec, trace=None), MATCH) is None
    assert reader("moe_gmm_roofline").read(
        dict(rec, trace=trace(0.0, 1.0)), MATCH) is None


def test_readers_are_silent_on_a_program_without_the_rings(rings):
    for name in ("moe_experts_hit_pct", "moe_load_max_over_mean"):
        assert reader(name).read(record(), {}) is None
    assert reader("moe_gmm_roofline").read(record(trace=trace(1.0, 1.0)), MATCH) is None


def test_latent_gather_live_reads_the_pool_the_cell_declares(rings):
    """The existing reader under the new metric's name: live positions over
    the 64 x 96 x 16 a tick gathers."""
    spec = run.load_json(os.path.join(BENCH, "metrics", "latent_gather_live_pct.serve.json"))
    assert spec["reader"] == "kv_gather_live_pct"
    assert spec["workloads"] == ["mistral4-serve-decode64"]
    fill(rings, "serve.kv_live_positions", [(110, 30000), (150, 32000)])
    assert reader(spec["reader"]).read(record(), {}) == pytest.approx(
        100.0 * 62000 / (2 * 64 * 96 * 16))


def test_the_new_metrics_apply_to_the_new_cell_only():
    new = {"moe_gmm_busy_pct.serve", "moe_gmm_roofline.serve", "moe_experts_hit_pct.serve",
           "moe_load_max_over_mean.serve", "latent_gather_live_pct.serve"}
    mine = {s["name"] for s in run.metric_specs(BENCH, "serve", "mistral4-serve-decode64", "per_layer")}
    old = {s["name"] for s in run.metric_specs(BENCH, "serve", "olmo1b-serve-closed32", "per_layer")}
    assert new <= mine and not new & old
    assert "kv_gather_live_pct.serve" in old - mine
    # the accepted serving metrics whose files list their cells stay with
    # those cells (the files are not this PR's to edit); the rest apply
    listed = {"kv_gather_live_pct.serve", "prefill_fill_pct.serve",
              "slot_occupancy_pct.serve", "queue_wait_p95_ms.serve",
              "sched_host_ms.serve"}
    assert mine - new == old - listed
