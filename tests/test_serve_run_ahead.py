"""The serving engine runs ahead: on the wall clock the next decode tick goes
to the device before the program ahead of it is fetched, reading its tokens
and its key where that program left them, on the device.

- The streams are the ones the fetch-first order serves, token for token,
  for the dense, latent, hybrid and top-1 models (a twin whose
  ``_runs_ahead`` always says no is the fetch-first order), and at a
  temperature bitwise the ones of a twin whose key is split on the host and
  whose programs sample with the half they are handed (the order of keys
  before the split moved into the programs).
- ``serve.tick_ahead`` records 0, and nothing goes to the device before the
  fetch, where the next step could differ from that tick: a slot completes
  at the program in flight, ``eos_id`` is set, a queued request is
  admittable, speculation is on.
- No run ends with a program unfetched (``drained``, ``mem_leak_check``),
  and a pool that runs out still counts ``pool_ok_failures``, from the flag
  that rides the packed vector.
"""

from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench_run
from ddl25spring_tpu import obs
from ddl25spring_tpu.models import llama
from ddl25spring_tpu.serve import kv_pages
from ddl25spring_tpu.serve.engine import ServeEngine, make_decode_tick, make_prefill
from ddl25spring_tpu.utils.config import LlamaConfig

BENCH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark")
DENSE = LlamaConfig(vocab_size=64, dmodel=32, num_heads=4, n_layers=2,
                    ctx_size=32, dtype="float32")
KNOBS = dict(page_len=4, n_pages=48, max_slots=3, pages_per_seq=6,
             prefill_batch=2, max_prompt_len=8, trace_label=None)
# (prompt length, max_new_tokens) of a closed loop's requests, in order
TRAFFIC = [(5, 7), (3, 4), (8, 9), (2, 3), (6, 8), (4, 5), (7, 2), (3, 6)]


def _family_model(name: str):
    """(config, params) of a tiny model of the family: every width shrunk,
    the structure (latent pages; gated delta-rule state beside pages;
    convolutional attention with a carried router, top-1) as published."""
    if name == "dense":
        return DENSE, llama.init_llama_params(jax.random.PRNGKey(0), DENSE)
    file, config = {"latent": ("mistral4", "mistral-small-4-ep4"),
                    "hybrid": ("qwen3next", "qwen3-next-80b-ep4"),
                    "top1": ("zaya", "zaya1-8b-pp2")}[name]
    family = bench_run.load_module(BENCH, "families", file)
    published = bench_run.load_json(os.path.join(BENCH, "configs", f"{config}.json"))
    small = dict(published, hidden_size=32, head_dim=8, moe_intermediate_size=16,
                 vocab_size=64, run={"dtype": "float32"})
    if name == "latent":
        small.update(
            num_attention_heads=4, num_key_value_heads=4, q_lora_rank=16,
            kv_lora_rank=8, qk_nope_head_dim=4, qk_rope_head_dim=4, qk_head_dim=8,
            v_head_dim=8, n_routed_experts=4, num_experts_per_tok=2,
            num_hidden_layers=2,
            published=dict(published["published"], n_routed_experts=8))
    elif name == "hybrid":
        small.update(
            num_attention_heads=4, num_key_value_heads=2, linear_num_key_heads=2,
            linear_num_value_heads=4, linear_key_head_dim=8,
            linear_value_head_dim=8, shared_expert_intermediate_size=16,
            num_experts=4, num_experts_per_tok=3, num_hidden_layers=4,
            published=dict(published["published"], num_experts=16))
    else:
        small.update(
            num_attention_heads=8, num_key_value_heads=2, num_experts=4,
            router_hidden_size=12, num_hidden_layers=3,
            run={"dtype": "float32", "high_prec": "float32"},
            deployment=dict(published["deployment"], expert_offset=0,
                            experts_held=4))
    cfg = family.build(small)
    return cfg, family.init_params(cfg, 0)


@pytest.fixture(scope="module")
def dense_params():
    return llama.init_llama_params(jax.random.PRNGKey(0), DENSE)


@pytest.fixture(autouse=True)
def _clean_rings():
    obs.counters.reset()
    yield
    obs.counters.reset()


def engine(cfg, params, *, fetch_first: bool = False, **kw):
    eng = ServeEngine(params, cfg, **{**KNOBS, "clock": "wall", **kw})
    if fetch_first:
        eng._runs_ahead = lambda: False
    return eng


def closed_loop(eng, traffic=TRAFFIC, clients=3, seed=0):
    """Serve ``traffic`` with ``clients`` requests in flight, a client
    sending its next request when its last is done (as the benchmark's
    closed loop does); returns the requests in the order they were sent."""
    rng = np.random.default_rng(seed)
    todo = [(rng.integers(1, 64, n).tolist(), m) for n, m in traffic]
    sent, steps = [], 0
    while True:
        live = [r for r in sent if r.done_t is None]
        while todo and len(live) < clients:
            req = eng.make_request(*todo.pop(0))
            assert eng.submit(req) is None
            sent.append(req)
            live.append(req)
        if not live and eng.drained:
            break
        eng.step()
        steps += 1
        # at most one tick is left unfetched, and then the engine is busy
        assert eng._in_flight is None or not eng.drained
        assert steps < 500
    assert eng._in_flight is None and eng.drained
    assert eng.mem_leak_check()["ok"] and eng.pool_ok_failures == 0
    return sent


def ahead_samples():
    return [int(v) for _, v in obs.counters.window(
        "serve.tick_ahead", 0.0, time.perf_counter())]


@pytest.mark.parametrize("name", ["dense", "latent", "hybrid", "top1"])
def test_greedy_streams_run_ahead_are_the_fetch_first_streams(name):
    cfg, params = _family_model(name)
    ahead = engine(cfg, params)
    got = [list(r.tokens) for r in closed_loop(ahead)]
    ran_ahead = ahead_samples()
    obs.counters.reset()
    twin = engine(cfg, params, fetch_first=True)
    want = [list(r.tokens) for r in closed_loop(twin)]
    assert got == want
    assert [len(t) for t in got] == [m for _, m in TRAFFIC]
    # the one engine ran most of its ticks ahead, the other none
    assert len(ran_ahead) == ahead._ticks and sum(ran_ahead) > ahead._ticks // 2
    assert set(ahead_samples()) == {0} and twin._ticks == ahead._ticks


def _host_split_twin(cfg, params, temperature):
    """An engine whose key is split on the host (``key, sub = split(key)``
    before every pass) and whose programs sample with the ``sub`` they are
    handed: the order of keys before the split moved into the programs.
    Fetch-first, as that order was."""
    real_split = jax.random.split

    def sampling_with_the_key_handed(body):
        def program(*args):
            saved = jax.random.split
            jax.random.split = lambda key: (key, key)  # the body's own split
            try:
                return body(*args)
            finally:
                jax.random.split = saved

        return jax.jit(program, donate_argnums=(1,))

    twin = engine(cfg, params, fetch_first=True, temperature=temperature)
    tick = sampling_with_the_key_handed(
        make_decode_tick(cfg, temperature=temperature))
    prefill = sampling_with_the_key_handed(make_prefill(
        cfg, max_prompt_len=KNOBS["max_prompt_len"], temperature=temperature))

    def host_split(program):
        def call(*args):
            *head, key = args
            key, sub = real_split(key)
            pool, packed, _handed_back = program(*head, sub)
            return pool, packed, key

        return call

    twin._tick, twin._prefill = host_split(tick), host_split(prefill)
    return twin


def test_sampled_streams_are_bitwise_a_host_split_twins(dense_params):
    got = [list(r.tokens)
           for r in closed_loop(engine(DENSE, dense_params, temperature=0.9))]
    assert sum(ahead_samples()) > 0
    want = [list(r.tokens)
            for r in closed_loop(_host_split_twin(DENSE, dense_params, 0.9))]
    assert got == want
    # and the temperature is felt: greedy serves other streams
    greedy = [list(r.tokens) for r in closed_loop(engine(DENSE, dense_params))]
    assert greedy != got


def test_the_tokens_stay_on_the_device(dense_params):
    """The tick takes the params, the pool and the key: no token is
    uploaded.  Between steps with nothing in flight, the pool's
    ``last_tok`` is the host mirror of every live slot."""
    eng = engine(DENSE, dense_params, fetch_first=True)
    handed = []
    inner = eng._tick

    def tick(*args):
        handed.append(len(args))
        return inner(*args)

    eng._tick = tick
    for n, m in TRAFFIC[:3]:
        assert eng.submit(eng.make_request(list(range(1, n + 1)), m)) is None
    while not eng.drained:
        eng.step()
        on_device = np.asarray(eng.pool["last_tok"])
        for slot, req in enumerate(eng.slots):
            if req is not None:
                assert on_device[slot] == eng._slot_last_tok[slot] == req.tokens[-1]
    assert handed and set(handed) == {3}


def test_a_tick_behind_a_completion_is_dispatched_after_the_fetch(dense_params):
    """Two requests of 3 and 6 tokens in one pass: the tick behind the pass
    and the next go ahead; the second completes the first request, so the
    third is dispatched after it is fetched; then ahead again."""
    eng = engine(DENSE, dense_params)
    sent = closed_loop(eng, [(4, 3), (5, 6)], clients=2)
    assert [len(r.tokens) for r in sent] == [3, 6]
    assert ahead_samples() == [1, 1, 0, 1, 1]
    assert eng._ticks == 5


@pytest.mark.parametrize("case", ["eos", "admittable", "spec"])
def test_nothing_goes_ahead_where_the_next_step_could_differ(dense_params, case):
    kw = {"eos": dict(eos_id=63), "spec": dict(spec_k=2),
          "admittable": dict(prefill_batch=1)}[case]
    eng = engine(DENSE, dense_params, **kw)
    dispatched_ahead = []
    inner = eng._dispatch_tick

    def dispatch(ahead, *a, **k):
        dispatched_ahead.append(ahead)
        return inner(ahead, *a, **k)

    eng._dispatch_tick = dispatch
    if case == "admittable":
        # three at once, one a pass: while two wait for a pass of their own
        # (with a slot free for the next), no tick goes ahead
        sent = closed_loop(eng, [(4, 6), (5, 6), (3, 6)], clients=3)
        samples = ahead_samples()
        assert samples[:2] == [0, 0] and 1 in samples[2:]
        assert dispatched_ahead == [bool(s) for s in samples]
    else:
        sent = closed_loop(eng, TRAFFIC[:4])
        samples = ahead_samples()
        assert samples and set(samples) == {0} and not any(dispatched_ahead)
        # one sample a tick, a speculative round counted as one
        assert len(samples) == eng._ticks
    assert all(r.done_t is not None for r in sent)


def test_an_exhausted_pool_is_counted_from_the_packed_flag(dense_params):
    """Every page held on the device behind the host's back: the pass and
    the ticks that open a page find none, and each says so in the one
    vector it hands back."""
    eng = engine(DENSE, dense_params)
    eng.pool = {**eng.pool, **kv_pages.ref_pages(
        kv_pages.accounting(eng.pool), jnp.arange(eng.n_pages, dtype=jnp.int32))}
    assert eng.submit(eng.make_request([1, 2, 3], 8)) is None
    while not eng.drained:
        eng.step()
    # the pass (positions 0-2 open one page) and the ticks at positions 4
    # and 8 (the next two pages)
    assert eng.pool_ok_failures == 3
    assert sum(ahead_samples()) > 0


def open_loop(eng, arrivals, seed=1):
    """Requests that arrive between steps, whatever is in flight:
    ``arrivals[i]`` (prompt, max_new) pairs are submitted before step
    ``i``; prompts share prefixes so that the radix cache matches, and the
    pool is too small for every request at once."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(1, 64, 6).tolist()
    sent, steps = [], 0
    while steps < len(arrivals) or not eng.drained:
        for n, m in arrivals[steps] if steps < len(arrivals) else ():
            prompt = shared[: n // 2] + rng.integers(1, 64, n - n // 2).tolist()
            req = eng.make_request(prompt, m)
            assert eng.submit(req) is None
            sent.append(req)
        eng.step()
        steps += 1
        assert steps < 500
    assert eng._in_flight is None
    assert eng.mem_leak_check()["ok"] and eng.pool_ok_failures == 0
    return [list(r.tokens) for r in sent]


def test_an_arrival_that_meets_a_tick_in_flight_waits_for_its_landing(dense_params):
    """Open-loop arrivals land between steps while a tick is in flight:
    the engine fetches that tick (which may complete a slot) and releases
    what it completed BEFORE it admits, so that admission never bills
    pages the device still holds; the streams are the fetch-first twin's."""
    rng = np.random.default_rng(7)
    arrivals = [[(int(rng.integers(2, 9)), int(rng.integers(2, 9)))
                 for _ in range(int(rng.poisson(0.6)))] for _ in range(60)]
    kw = dict(prefix_cache=True, n_pages=12, max_slots=3)
    got = open_loop(engine(DENSE, dense_params, **kw), arrivals)
    assert sum(ahead_samples()) > 0
    want = open_loop(engine(DENSE, dense_params, fetch_first=True, **kw), arrivals)
    assert got == want and len(got) == sum(map(len, arrivals))
