"""The ``zaya`` cell's files found by name, and the UNEDITED readers of its
seven per-layer metrics, each on a hand-made record, ring and trace whose
answers are known, with this configuration's keys (20 layers, 16 experts
held of 16, hidden 2,048, expert width 2,048); silent where there is nothing
to read (a program from before the kernel and the rings)."""

import importlib
import os

import pytest

from benchmark import flops, run

counters_mod = importlib.import_module("ddl25spring_tpu.obs.counters")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "zaya1-serve-decode64"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
GMM = {"match": ["moe_gmm"], "kernel": "moe_gmm"}
LAYERS, HELD = 20, 16
NEW = {"top1_moe_gmm_busy_pct.serve", "top1_moe_gmm_roofline.serve",
       "top1_moe_experts_hit_pct.serve", "top1_moe_load_max_over_mean.serve",
       "cca_kv_gather_live_pct.serve", "cca_prefill_ms.serve",
       "top1_decode_tick_ms.serve"}
GENERIC = {"compile_s.serve", "compiles_in_window.serve", "decode_tick_ms.serve",
           "device_idle_pct.serve", "hbm_peak_gb.serve", "prefill_ms.serve",
           "prefill_share_pct.serve"}


def reader(name):
    return run.load_module(BENCH, "readers", name)


def spec_of(metric):
    return run.load_json(os.path.join(BENCH, "metrics", f"{metric}.json"))


@pytest.fixture
def rings(monkeypatch):
    fresh = counters_mod.CounterSet()
    monkeypatch.setattr(counters_mod, "counters", fresh)
    return fresh


def record(**more):
    cell, config = run.load_cell(BENCH, CELL)
    return {"t_open_host": 100.0, "t_close_host": 200.0, "window_s": 100.0,
            "cell": cell, "config": config, "peaks": PEAKS, "trace": None, **more}


def trace(gmm_s, other_s, window_s=3.0):
    return {"window_s": window_s, "busy_s": gmm_s + other_s,
            "longest_gaps": [["serve_decode_tick", 0.004, 0]], "op_self_s": {
        "moe_gmm.22 bf16[64,2048] tpu_custom_call": gmm_s,
        "fusion.653 bf16[6144,16,256]": other_s}}


def fill(rings, name, samples):
    for t, v in samples:
        rings.sample(name, v, t=t)


def test_the_cells_files_load_by_name_and_state_the_issues_traffic():
    cell, config = run.load_cell(BENCH, CELL)
    assert (cell["config"], cell["runner"], cell["chips"]) == ("zaya1-8b-pp2", "serve", 1)
    assert cell["engine"] == {
        "max_slots": 64, "prefill_batch": 8, "max_prompt_len": 512, "page_len": 16,
        "pages_per_seq": 96, "n_pages": 6144, "max_queue": 128, "logit_probe": 128,
        "prefix_cache": False}
    latent, _ = run.load_cell(BENCH, "mistral4-serve-decode64")
    assert cell["traffic"] == latent["traffic"]  # the latent cell's, on purpose
    t = cell["traffic"]
    assert (t["loop"], t["clients"], t["pool_size"], t["pool_seed"]) == ("closed", 64, 128, 0)
    assert t["prompt_len"] == {"kind": "lognormal", "median": 192, "sigma": 0.6,
                               "min": 32, "max": 512}
    assert t["max_new"] == {"kind": "lognormal", "median": 384, "sigma": 0.5,
                            "min": 128, "max": 1024}
    assert len(cell["why"]) <= 200
    family = run.load_family(BENCH, config)
    assert family.__name__.endswith("families_zaya")
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 40}
    # the keys the unedited readers read
    assert (config["num_hidden_layers"], config["num_experts"], config["hidden_size"],
            config["moe_intermediate_size"]) == (LAYERS, HELD, 2048, 2048)
    assert (config["vocab_size"], config["tie_word_embeddings"],
            config["num_experts_per_tok"]) == (262272, True, 1)
    # the longest request fits its pages, and the pool holds every slot's
    e = cell["engine"]
    assert 512 + 1024 <= e["pages_per_seq"] * e["page_len"]
    assert e["max_slots"] * e["pages_per_seq"] == e["n_pages"]


def test_metric_specs_list_the_seven_new_and_the_seven_generic_for_this_cell_only():
    mine = {s["name"] for s in run.metric_specs(BENCH, "serve", CELL, "per_layer")}
    assert mine == NEW | GENERIC
    for other in ("mistral4-serve-decode64", "qwen3next-serve-decode128",
                  "olmo1b-serve-closed32"):
        theirs = {s["name"] for s in run.metric_specs(BENCH, "serve", other, "per_layer")}
        assert not NEW & theirs and GENERIC <= theirs
    e2e = {s["name"] for s in run.metric_specs(BENCH, "serve", CELL, "end_to_end")}
    assert e2e == {"serve_tokens_s_chip", "ttft_p95_ms", "tpot_p95_ms", "setup_s"}


@pytest.mark.parametrize("metric, twin", [
    ("top1_moe_gmm_busy_pct.serve", "hybrid_moe_gmm_busy_pct.serve"),
    ("top1_moe_gmm_roofline.serve", "hybrid_moe_gmm_roofline.serve"),
    ("top1_moe_experts_hit_pct.serve", "hybrid_moe_experts_hit_pct.serve"),
    ("top1_moe_load_max_over_mean.serve", "hybrid_moe_load_max_over_mean.serve"),
    ("cca_kv_gather_live_pct.serve", "hybrid_kv_gather_live_pct.serve"),
])
def test_each_new_metric_is_its_twins_file_over_the_same_reader(metric, twin):
    """The twin's file but for the metric it moves: this cell is judged on
    ``serve_tokens_s_chip`` alone (a closed loop's throughput IS its ticks
    and passes), so nothing of it may move a tail it does not list."""
    mine, theirs = spec_of(metric), spec_of(twin)
    assert mine.pop("workloads") == [CELL]
    assert theirs.pop("workloads") == ["qwen3next-serve-decode128"]
    assert (mine.pop("moves"), theirs.pop("moves")) == ("serve_tokens_s_chip", "tpot_p95_ms")
    assert mine == theirs
    assert os.path.isfile(os.path.join(BENCH, "readers", f"{mine['reader']}.py"))


@pytest.mark.parametrize("metric, generic, tail", [
    ("cca_prefill_ms.serve", "prefill_ms.serve", "ttft_p95_ms"),
    ("top1_decode_tick_ms.serve", "decode_tick_ms.serve", "tpot_p95_ms"),
])
def test_pass_and_tick_are_listed_under_the_metric_the_cell_is_judged_on(
        metric, generic, tail):
    """``prefill_ms.serve`` moves ``ttft_p95_ms`` and ``decode_tick_ms.serve``
    moves ``tpot_p95_ms``, on which this cell is not judged (its runs' tails
    spread wider than half those bounds): the same readers under names of
    this cell's, moving the throughput that a closed loop's prompt passes
    and ticks cost."""
    mine, theirs = spec_of(metric), spec_of(generic)
    assert mine.pop("workloads") == [CELL] and "workloads" not in theirs
    assert (mine.pop("moves"), theirs.pop("moves")) == ("serve_tokens_s_chip", tail)
    assert mine == theirs
    bench = run.load_json(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    listed = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    assert CELL not in listed[tail]["workloads"]
    assert CELL not in listed[generic]["workloads"]
    assert CELL in listed["serve_tokens_s_chip"]["workloads"]


def test_the_prompt_pass_reader_takes_the_median_of_the_windows_passes():
    mine = spec_of("cca_prefill_ms.serve")
    rows = [{"prefill_s": 0.018, "prefill_start_t": 1.0}, {"prefill_s": 0.020, "prefill_start_t": 2.0},
            {"prefill_s": 0.5, "prefill_start_t": 9.0}, {"prefill_s": None, "prefill_start_t": None}]
    record = {"requests": rows, "t_open": 0.0, "t_close": 5.0}
    assert reader(mine["reader"]).read(record, {}) == pytest.approx(19.0)
    assert reader(mine["reader"]).read({**record, "requests": []}, {}) is None


def test_the_benchmark_file_lists_the_cell_where_its_metrics_report():
    bench = run.load_json(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["chips"]) == ("zaya1-8b-pp2", 1)
    (config,) = [c for c in bench["configs"] if c["name"] == "zaya1-8b-pp2"]
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["file"] == "benchmark/configs/zaya1-8b-pp2.json"
    # `prefill_ms.serve` moves `ttft_p95_ms` and `decode_tick_ms.serve` moves
    # `tpot_p95_ms`, neither of which the cell lists
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    assert listed == NEW | GENERIC - {"prefill_ms.serve", "decode_tick_ms.serve"}
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            spec = spec_of(m["name"])
            assert m["workloads"] == [CELL]
            assert {k: m[k] for k in ("unit", "better", "source", "layer", "moves")} == {
                k: spec[k] for k in ("unit", "better", "source", "layer", "moves")}
    # both tails are printed and NOT listed: `ttft_p95_ms` spreads 1.3-3.3 %
    # where half its bound of 0.01 admits a new cell, and the driver's two
    # sets read `tpot_p95_ms` 3.25 % / 2.08 % against 2.5 % (PERF.md section
    # 6, PR 36); every listed per-layer metric moves a metric the cell lists
    e2e = {m["name"] for m in bench["end_to_end"] if CELL in m.get("workloads", ())}
    assert e2e == {"serve_tokens_s_chip"}
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["moves"] in e2e | {"setup_s"}


def test_busy_share_reads_the_kernel_by_name():
    rec = record(trace=trace(gmm_s=0.9, other_s=2.1))
    args = spec_of("top1_moe_gmm_busy_pct.serve")["args"]
    assert args == {"match": ["moe_gmm"]}
    assert reader("moe_gmm_busy_pct").read(rec, args) == pytest.approx(30.0)
    assert reader("moe_gmm_busy_pct").read(record(trace=trace(0.0, 1.0)), args) is None


def test_top1_roofline_cannot_read_over_100_on_exact_counts(rings):
    """20 equal calls a pass at hidden 2,048 and width 2,048: 50 ticks of 64
    assignments on 11 experts a layer and 8 prompt passes of 400 on 16 in the
    slice (the 3 s after the close at 200), each by its own counts.  A kernel
    that takes exactly the least time reads 100 %, a slower one less; passes
    of the window and of the drain move nothing."""
    family = run.load_module(BENCH, "families", "zaya")
    tick_s, bound = flops.roofline_seconds(*family.moe_gmm_flops_bytes(
        64, 11, hidden=2048, width=2048), PEAKS)
    pass_s, _ = flops.roofline_seconds(*family.moe_gmm_flops_bytes(
        400, 16, hidden=2048, width=2048), PEAKS)
    # a tick is bound by its hit experts' bytes: 11 x 25.2 MB
    assert bound == "memory" and tick_s == pytest.approx(
        2 * (3 * 2048 * 2048 * 11 + 2 * 2048 * 64) / 819e9)
    stamps = [(150.0, 1, 1)] + [(200.02 + 0.05 * i, 64, 11) for i in range(50)] + [
        (200.04 + 0.3 * i, 400, 16) for i in range(8)] + [(204.0, 64, 11)]
    fill(rings, "serve.moe.assignments_here", [(t, a * LAYERS) for t, a, _ in stamps])
    fill(rings, "serve.moe.experts_hit", [(t, h * LAYERS) for t, _, h in stamps])
    least = LAYERS * (50 * tick_s + 8 * pass_s)
    args = spec_of("top1_moe_gmm_roofline.serve")["args"]
    assert args == GMM
    exact = record(trace=trace(gmm_s=least, other_s=1.0))
    assert reader("slice_roofline").read(exact, args) == pytest.approx(100.0)
    slower = dict(exact, trace=trace(gmm_s=least / 0.8, other_s=1.0))
    assert reader("slice_roofline").read(slower, args) == pytest.approx(80.0)
    assert reader("slice_roofline").read(dict(slower, window_s=7.0), args) == (
        pytest.approx(80.0))
    # no peaks (a CPU run), no trace, or no such kernel in the trace: silent
    assert reader("slice_roofline").read(dict(slower, peaks=None), args) is None
    assert reader("slice_roofline").read(dict(slower, trace=None), args) is None
    assert reader("slice_roofline").read(
        dict(slower, trace=trace(0.0, 1.0)), args) is None


def test_experts_hit_imbalance_and_gather_from_the_rings(rings):
    # two ticks in the window (one before it): 20 layers x 16 held = 320 pairs
    fill(rings, "serve.moe.experts_hit", [(90, 1), (110, 220), (150, 228)])
    fill(rings, "serve.moe.assignments_here", [(90, 1), (110, 1280), (150, 1280)])
    fill(rings, "serve.moe.load_max", [(90, 1), (110, 400), (150, 240)])
    assert reader(spec_of("top1_moe_experts_hit_pct.serve")["reader"]).read(
        record(), {}) == pytest.approx(100.0 * 448 / (2 * 320))
    # per layer: mean 2560 / 40 / 16 = 4, maxima average 640 / 40 = 16
    assert reader(spec_of("top1_moe_load_max_over_mean.serve")["reader"]).read(
        record(), {}) == pytest.approx(4.0)
    fill(rings, "serve.kv_live_positions", [(110, 30000), (150, 31000)])
    assert reader(spec_of("cca_kv_gather_live_pct.serve")["reader"]).read(
        record(), {}) == pytest.approx(100.0 * 61000 / (2 * 64 * 96 * 16))


def test_readers_are_silent_on_a_program_without_the_rings(rings):
    for metric in ("top1_moe_experts_hit_pct.serve",
                   "top1_moe_load_max_over_mean.serve",
                   "cca_kv_gather_live_pct.serve"):
        assert reader(spec_of(metric)["reader"]).read(record(), {}) is None
    assert reader("slice_roofline").read(record(trace=trace(1.0, 1.0)), GMM) is None
