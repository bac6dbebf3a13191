"""The readers of the ``qwen3next`` cell's per-layer metrics, each on a
hand-made record, ring and trace whose answers are known; silent where there
is nothing to read (a program from before the kernel and the rings); and the
new cell's files found by name."""

import importlib
import os

import pytest

from benchmark import flops, run

counters_mod = importlib.import_module("ddl25spring_tpu.obs.counters")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "qwen3next-serve-decode128"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
GDN = {"match": ["gdn_step"], "kernel": "gdn_step"}
GMM = {"match": ["moe_gmm"], "kernel": "moe_gmm"}
LAYERS, LINEAR, HELD = 8, 6, 128
NEW = {"gdn_step_busy_pct.serve", "gdn_step_roofline.serve",
       "hybrid_moe_gmm_busy_pct.serve", "hybrid_moe_gmm_roofline.serve",
       "hybrid_moe_experts_hit_pct.serve", "hybrid_moe_load_max_over_mean.serve",
       "hybrid_kv_gather_live_pct.serve", "gdn_chunk_fill_pct.serve"}


def reader(name):
    return run.load_module(BENCH, "readers", name)


@pytest.fixture
def rings(monkeypatch):
    fresh = counters_mod.CounterSet()
    monkeypatch.setattr(counters_mod, "counters", fresh)
    return fresh


def record(**more):
    cell, config = run.load_cell(BENCH, CELL)
    assert (config["num_hidden_layers"], config["num_experts"]) == (LAYERS, HELD)
    return {"t_open_host": 100.0, "t_close_host": 200.0, "window_s": 100.0,
            "cell": cell, "config": config, "peaks": PEAKS, "trace": None, **more}


def trace(gdn_s, gmm_s, other_s, window_s=3.0, gaps=(0.004, 0.003)):
    return {"window_s": window_s, "busy_s": gdn_s + gmm_s + other_s,
            "longest_gaps": [["serve_decode_tick", g, 0] for g in gaps], "op_self_s": {
        "gdn_step.3 (f32[128,32,128], f32[128,6,32,128,128]) tpu_custom_call": gdn_s,
        "moe_gmm.24 bf16[1280,512] tpu_custom_call": gmm_s,
        "fusion.374 bf16[12288,16,2,256]": other_s}}


def fill(rings, name, samples):
    for t, v in samples:
        rings.sample(name, v, t=t)


def test_the_cells_files_load_by_name_and_state_the_issues_traffic():
    cell, config = run.load_cell(BENCH, CELL)
    assert cell["runner"] == "serve" and cell["chips"] == 1
    assert cell["engine"] == {
        "max_slots": 128, "prefill_batch": 8, "max_prompt_len": 512, "page_len": 16,
        "pages_per_seq": 96, "n_pages": 12288, "max_queue": 256, "logit_probe": 128,
        "prefix_cache": False}
    t = cell["traffic"]
    assert (t["loop"], t["clients"], t["pool_size"], t["pool_seed"]) == ("closed", 128, 256, 0)
    assert t["prompt_len"] == {"kind": "lognormal", "median": 192, "sigma": 0.6,
                               "min": 32, "max": 512}
    assert t["max_new"] == {"kind": "lognormal", "median": 384, "sigma": 0.5,
                            "min": 128, "max": 1024}
    family = run.load_family(BENCH, config)
    assert family.__name__.endswith("families_qwen3next")
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                   "vocab_size": 151936}
    # the longest request fits its pages, and the pool holds every slot's
    e = cell["engine"]
    assert 512 + 1024 <= e["pages_per_seq"] * e["page_len"]
    assert e["max_slots"] * e["pages_per_seq"] == e["n_pages"]
    mine = {s["name"] for s in run.metric_specs(BENCH, "serve", CELL, "per_layer")}
    other = {s["name"] for s in run.metric_specs(
        BENCH, "serve", "mistral4-serve-decode64", "per_layer")}
    assert NEW <= mine and not NEW & other
    generic = {"compile_s.serve", "compiles_in_window.serve", "decode_tick_ms.serve",
               "device_idle_pct.serve", "hbm_peak_gb.serve", "prefill_ms.serve",
               "prefill_share_pct.serve"}
    assert mine - NEW == generic
    e2e = {s["name"] for s in run.metric_specs(BENCH, "serve", CELL, "end_to_end")}
    assert e2e == {"serve_tokens_s_chip", "ttft_p95_ms", "tpot_p95_ms", "setup_s"}


def test_busy_shares_tell_the_two_kernels_apart():
    rec = record(trace=trace(gdn_s=0.3, gmm_s=0.9, other_s=0.8))
    assert reader("moe_gmm_busy_pct").read(rec, GDN) == pytest.approx(15.0)
    assert reader("moe_gmm_busy_pct").read(rec, GMM) == pytest.approx(45.0)
    spec = run.load_json(os.path.join(BENCH, "metrics", "gdn_step_busy_pct.serve.json"))
    assert spec["reader"] == "moe_gmm_busy_pct" and spec["args"] == {"match": ["gdn_step"]}


def test_gdn_roofline_cannot_read_over_100_on_exact_counts(rings):
    """The slice (the 3 s after the window's close at 200) holds 60 ticks of
    120 live slots: six calls a tick, bound by bytes.  A kernel that takes
    exactly the least time reads 100 %, a slower one less; ticks of the
    window, of the drain after the slice, and how long the slice or the
    window lasted move nothing."""
    family = run.load_module(BENCH, "families", "qwen3next")
    call_s, bound = flops.roofline_seconds(*family.gdn_step_flops_bytes(120), PEAKS)
    state = 32 * 128 * 128
    assert bound == "memory" and call_s == pytest.approx(
        120 * (2 * state * 4 + 2 * (2 * 16 * 128 + 2 * 32 * 128) + 8 * 32) / 819e9)
    fill(rings, "serve.active_slots",
         [(100.5 + i, 128) for i in range(99)]            # the window's
         + [(200.01 + 0.05 * i, 120) for i in range(59)]  # the slice's
         + [(202.999, 0)]                                 # ... one with none live
         + [(203.2 + i, 90) for i in range(5)])           # the drain's
    least = 59 * LINEAR * call_s
    exact = record(trace=trace(gdn_s=least, gmm_s=1.0, other_s=1.0))
    assert reader("slice_roofline").read(exact, GDN) == pytest.approx(100.0)
    rec = dict(exact, trace=trace(gdn_s=least / 0.4, gmm_s=1.0, other_s=1.0))
    assert reader("slice_roofline").read(rec, GDN) == pytest.approx(40.0)
    paused = trace(gdn_s=least / 0.4, gmm_s=1.0, other_s=1.0, window_s=4.35,
                   gaps=(1.35, 0.004))
    assert reader("slice_roofline").read(dict(rec, trace=paused), GDN) == (
        pytest.approx(40.0))
    assert reader("slice_roofline").read(dict(rec, window_s=7.0), GDN) == (
        pytest.approx(40.0))
    # no peaks (a CPU run), no trace, or no such kernel in the trace: silent
    assert reader("slice_roofline").read(dict(rec, peaks=None), GDN) is None
    assert reader("slice_roofline").read(dict(rec, trace=None), GDN) is None
    assert reader("slice_roofline").read(
        dict(rec, trace=trace(0.0, 1.0, 1.0)), GDN) is None


def test_hybrid_moe_roofline_reads_this_files_widths_in_the_slice(rings):
    """8 calls a pass of width 512 over a hidden size of 2,048: 50 ticks and
    10 prompt passes in the slice, each by its own counts."""
    family = run.load_module(BENCH, "families", "qwen3next")
    tick_s, bound = flops.roofline_seconds(*family.moe_gmm_flops_bytes(320, 118), PEAKS)
    pass_s, _ = flops.roofline_seconds(*family.moe_gmm_flops_bytes(900, 128), PEAKS)
    assert bound == "memory"
    stamps = [(150.0, 1, 1)] + [(200.02 + 0.05 * i, 320, 118) for i in range(50)] + [
        (200.04 + 0.25 * i, 900, 128) for i in range(10)] + [(204.0, 320, 118)]
    fill(rings, "serve.moe.assignments_here", [(t, a * LAYERS) for t, a, _ in stamps])
    fill(rings, "serve.moe.experts_hit", [(t, h * LAYERS) for t, _, h in stamps])
    least = LAYERS * (50 * tick_s + 10 * pass_s)
    rec = record(trace=trace(gdn_s=0.5, gmm_s=least / 0.8, other_s=1.0))
    assert reader("slice_roofline").read(rec, GMM) == pytest.approx(80.0)
    for name, args in (("gdn_step_roofline.serve", GDN),
                       ("hybrid_moe_gmm_roofline.serve", GMM)):
        spec = run.load_json(os.path.join(BENCH, "metrics", f"{name}.json"))
        assert spec["reader"] == "slice_roofline" and spec["args"] == args


def test_experts_hit_imbalance_and_gather_from_the_rings(rings):
    # two ticks in the window (one before it): 8 layers x 128 held = 1,024 pairs
    fill(rings, "serve.moe.experts_hit", [(90, 1), (110, 940), (150, 950)])
    fill(rings, "serve.moe.assignments_here", [(90, 1), (110, 2560), (150, 2560)])
    fill(rings, "serve.moe.load_max", [(90, 1), (110, 72), (150, 56)])
    assert reader("hybrid_moe_experts_hit_pct").read(record(), {}) == pytest.approx(
        100.0 * 1890 / (2 * 1024))
    # per layer: mean 5120 / 16 / 128 = 2.5, maxima average 128 / 16 = 8
    assert reader("hybrid_moe_load_max_over_mean").read(record(), {}) == (
        pytest.approx(3.2))
    spec = run.load_json(os.path.join(
        BENCH, "metrics", "hybrid_kv_gather_live_pct.serve.json"))
    assert spec["reader"] == "kv_gather_live_pct" and spec["workloads"] == [CELL]
    fill(rings, "serve.kv_live_positions", [(110, 60000), (150, 64000)])
    assert reader(spec["reader"]).read(record(), {}) == pytest.approx(
        100.0 * 124000 / (2 * 128 * 96 * 16))


def test_chunk_fill_is_live_chunks_over_scanned_chunks(rings):
    # three passes, one before the window: 8 rows x W / 64 chunks scanned
    fill(rings, "serve.gdn.chunks_live", [(90, 5), (110, 9), (150, 21)])
    fill(rings, "serve.gdn.chunks_scanned", [(90, 16), (110, 32), (150, 64)])
    assert reader("gdn_chunk_fill_pct").read(record(), {}) == pytest.approx(
        100.0 * 30 / 96)


def test_readers_are_silent_on_a_program_without_the_rings(rings):
    for name in ("hybrid_moe_experts_hit_pct", "hybrid_moe_load_max_over_mean",
                 "gdn_chunk_fill_pct"):
        assert reader(name).read(record(), {}) is None
    for args in (GDN, GMM):
        assert reader("slice_roofline").read(
            record(trace=trace(1.0, 1.0, 1.0)), args) is None
