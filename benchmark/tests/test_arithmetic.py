"""FLOPs against a hand count, percentiles, window arithmetic, and the
traffic generator's promise that the seed does not change the work."""

import numpy as np
import pytest

from benchmark import flops, stats, traffic
from benchmark.run import load_module, BENCH_DIR


LLAMA = load_module(BENCH_DIR, "families", "llama")


def test_train_flops_of_the_15m_config_by_hand():
    from ddl25spring_tpu.utils.config import LlamaConfig

    # LlamaConfig(): dmodel 288, ffn 1152, 6 layers, vocab 4096, ctx 256
    # a layer: 4 * 288^2 = 331,776 and 3 * 288 * 1152 = 995,328 -> 1,327,104
    # six layers 7,962,624; unembed 288 * 4096 = 1,179,648; together 9,142,272
    assert LLAMA.matmul_params(288, 1152, 6, 4096) == 9_142_272
    # 6 x that = 54,853,632; attention 6 * 6 * 256 * 288 = 2,654,208
    assert LLAMA.flops_per_token(288, 1152, 6, 4096, 256) == 57_507_840
    # and as the train runner asks for it: from the configuration object
    assert LLAMA.train_flops_per_token(LlamaConfig()) == 57_507_840


def test_flash_flops_and_bytes_by_hand():
    from ddl25spring_tpu.utils.config import LlamaConfig

    # one head, ctx 4, head_dim 2: QK^T and PV are 2*4*4*2 = 64 each, 128
    # together, halved by the mask: 64; q, k, v, o of 8 bf16 elements: 64 B
    assert LLAMA.flash_flops_bytes(1, 4, 1, 2, backward=False) == (64.0, 64.0)
    assert LLAMA.flash_flops_bytes(1, 4, 1, 2, backward=True) == (160.0, 128.0)
    # as the train runner asks for it: one call a layer, both directions
    cfg = LlamaConfig(dmodel=2, num_heads=1, n_layers=3, ctx_size=4)
    assert LLAMA.flash_calls(cfg, 1) == {
        "calls": 3, "forward": (64.0, 64.0), "backward": (160.0, 128.0)}
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds(64.0, 64.0, peaks) == (6.4, "memory")
    assert flops.roofline_seconds(6400.0, 64.0, peaks) == (64.0, "compute")


def test_percentile_interpolates_like_numpy():
    xs = [5.0, 1.0, 9.0, 3.0, 7.0, 11.0]
    for q in (0, 25, 50, 95, 100):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert stats.percentile([], 95) is None and stats.median([4.0]) == 4.0


def row(arrival, first=None, done=None, n=0, pre_t=None, pre_s=None, why=None,
        open_=10.0, close=20.0):
    return {"arrival_t": arrival, "first_token_t": first, "done_t": done,
            "n_tokens": n, "prefill_start_t": pre_t, "prefill_s": pre_s,
            "rejected": why, "sent_in_window": open_ <= arrival <= close}


def test_serving_window_arithmetic():
    rec = {
        "t_open": 10.0, "t_close": 20.0, "t_grace_end": 30.0, "window_s": 10.0,
        "requests": [
            # sent before the window opens: in no latency sample, though
            # the prefill that admitted it inside the window is counted
            row(9.0, 11.0, 12.0, 5, pre_t=10.5, pre_s=0.5),
            # one dispatch serving three requests: counted ONCE
            row(12.0, 13.0, 15.0, 5, pre_t=12.5, pre_s=0.5),
            row(12.1, 13.0, 17.0, 9, pre_t=12.5, pre_s=0.5),
            row(12.2, 13.0, 25.0, 9, pre_t=12.5, pre_s=0.5),  # done after the close
            row(19.0, None, None, 0),             # no first token by the grace's end
            row(19.5, None, None, 0, why="queue_full"),
            row(21.0, 22.0, 23.0, 3, pre_t=21.5, pre_s=0.5),  # sent after the close
        ],
    }
    assert sorted(stats.ttft_samples_ms(rec)) == pytest.approx(
        [800.0, 900.0, 1000.0, 10500.0, 11000.0])
    assert sorted(stats.tpot_samples_ms(rec)) == pytest.approx([500.0, 500.0])
    ttft = load_module(BENCH_DIR, "readers", "ttft_ms")
    assert ttft.read(rec, {"q": 50}) == pytest.approx(1000.0)
    tpot = load_module(BENCH_DIR, "readers", "tpot_ms")
    assert tpot.read(rec, {"q": 95}) == pytest.approx(500.0)
    assert stats.distinct_prefills(rec["requests"], 10.0, 20.0) == [(10.5, 0.5), (12.5, 0.5)]
    share = load_module(BENCH_DIR, "readers", "prefill_share_pct")
    assert share.read(rec, {}) == pytest.approx(10.0)
    assert load_module(BENCH_DIR, "readers", "prefill_ms").read(rec, {}) == 500.0


def test_train_rate_is_all_tokens_over_all_time():
    rec = {"tokens": 5 * 8192, "window_s": 2.0, "chips": 4, "step_s": [0.4] * 5}
    assert load_module(BENCH_DIR, "readers", "train_tokens_s_chip").read(rec, {}) == 5120.0
    assert load_module(BENCH_DIR, "readers", "step_ms").read(rec, {}) == 400.0


SPEC = {"pool_size": 32, "pool_seed": 0,
        "prompt_len": {"kind": "lognormal", "median": 96, "sigma": 0.6, "min": 16, "max": 256},
        "max_new": {"kind": "lognormal", "median": 64, "sigma": 0.5, "min": 16, "max": 128},
        "tokens": {"kind": "uniform"}}


def test_every_seed_sends_the_same_sizes_in_the_same_order():
    def draw(seed):
        g = traffic.requests(SPEC, 50304, seed)
        return [next(g) for _ in range(40)]

    a, b = draw(1), draw(2**31 + 11)
    sizes = [(len(p), n) for p, n in a]
    assert sizes == [(len(p), n) for p, n in b]           # the work is the same
    assert [p for p, _ in a] != [p for p, _ in b]          # the tokens are not
    pool = traffic.request_pool(SPEC)
    assert sizes[:32] == pool and sizes[32:] == pool[:8]   # the pool, cycled
    lens = [p for p, _ in pool]
    assert min(lens) >= 16 and max(lens) <= 256 and 80 <= np.median(lens) <= 112
    assert lens != sorted(lens)                            # shuffled by the file
    assert all(1 <= t < 50304 for t in a[0][0])


def test_shared_prefixes_head_every_prompt():
    spec = dict(SPEC, shared_prefix={"count": 2, "len": 8},
                prompt_len={"kind": "fixed", "value": 4})
    g = traffic.requests(spec, 1000, 3)
    heads = {tuple(next(g)[0][:8]) for _ in range(40)}
    assert len(heads) == 2


def test_zipf_tokens_are_in_range_and_skewed():
    toks = next(traffic.train_batches({"tokens": {"kind": "zipf", "a": 1.1}}, 50304, 4, 2048, 9))
    assert toks.shape == (4, 2048) and toks.min() >= 1 and toks.max() < 50304
    assert (toks == 1).mean() > 0.05  # rank 1 carries ~9 % of a Zipf(1.1) over 50k


def test_poisson_arrivals_keep_their_rate():
    ts = traffic.poisson_arrivals(50.0, 20.0, 2**31 + 3)
    assert ts == sorted(ts) and 0 <= ts[0] and ts[-1] < 20.0
    assert 850 < len(ts) < 1150
    assert traffic.poisson_arrivals(0.0, 20.0, 5) == []
