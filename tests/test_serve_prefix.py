"""The radix prefix cache (PR 11): refcounted page sharing, COW, LRU
eviction, and the bitwise cached==cold contract.

The load-bearing pins:

- **prefix-cached == cold, bitwise** — fp32 greedy decode through a
  radix hit (shared full pages + a copy-on-write partial page)
  reproduces the dense oracle token for token, including across an
  eviction-then-readmit of the same prefix.
- **pool invariant under interleavings** — a seeded fuzz of
  allocate/adopt(COW)/release/evict keeps ``free == (refcount == 0)``,
  ``used + free == n_pages``, and ``refcount[p] == table references +
  cache reference`` exactly (no double-free, no leak, the COW copy
  reachable from exactly one page table).
- **the saved work is countable** — prefill_tokens_saved /
  prefill_flops_saved / prefix_hit_rate are deterministic on the seeded
  shared-prefix trace, and the cached engine strictly beats the cold
  one on the virtual clock at equal admission budget.
"""

from __future__ import annotations

import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl25spring_tpu.models import decode as dm, llama
from ddl25spring_tpu.serve import kv_pages
from ddl25spring_tpu.serve.engine import ServeEngine
from ddl25spring_tpu.serve.prefix import PrefixCache
from ddl25spring_tpu.serve.traffic import (
    PROFILES,
    TrafficSpec,
    synth_trace,
)
from ddl25spring_tpu.utils.config import LlamaConfig

from conftest import cached_lowering

CFG = LlamaConfig(
    vocab_size=64, dmodel=16, num_heads=2, n_layers=2, ctx_size=32,
    dtype="float32",
)


@pytest.fixture(scope="module")
def params():
    return llama.init_llama_params(jax.random.PRNGKey(0), CFG)


def dense_greedy(params, prompt: list[int], max_new: int) -> list[int]:
    """The dense-cache oracle, compiled once per (|prompt|, max_new)."""

    def build():
        toks = dm.generate(
            params, jnp.asarray([prompt], jnp.int32), CFG,
            max_new_tokens=max_new, temperature=0.0,
        )
        return [int(t) for t in np.asarray(toks)[0]]

    return cached_lowering(("serve-dense", tuple(prompt), max_new), build)


def make_engine(params, **kw):
    kw.setdefault("page_len", 4)
    kw.setdefault("n_pages", 16)
    kw.setdefault("max_slots", 2)
    kw.setdefault("pages_per_seq", 4)
    kw.setdefault("prefill_batch", 1)
    kw.setdefault("max_prompt_len", 8)
    kw.setdefault("clock", "virtual")
    kw.setdefault("prefix_cache", True)
    return ServeEngine(params, CFG, **kw)


def drain(eng, max_steps: int = 500):
    steps = 0
    while eng.queue or any(s is not None for s in eng.slots):
        eng.step()
        steps += 1
        assert steps < max_steps, "engine failed to drain"


def serve_tokens(eng, requests: list[tuple[list[int], int]]) -> list[list]:
    """Submit sequentially (each drains before the next arrives — the
    shape that makes every later request a clean cache-hit candidate)
    and return per-request token lists."""
    out = []
    for prompt, max_new in requests:
        r = eng.make_request(prompt, max_new)
        assert eng.submit(r) is None
        drain(eng)
        out.append(list(r.tokens))
    return out


def assert_pool_invariants(eng):
    """The PR-11 pool contract, reconciled between device state and the
    host radix tree: ``free`` is exactly the zero-refcount set, and
    every reference is accounted — one per page-table entry holding the
    page (live or pending release) plus one iff the cache holds a node
    on it.  Equality rules out double-frees, leaks, and a COW copy
    reachable from two tables at once."""
    refcount = np.asarray(jax.device_get(eng.pool["refcount"]))
    free = np.asarray(jax.device_get(eng.pool["free"]))
    table = np.asarray(jax.device_get(eng.pool["page_table"]))
    n_pages = free.shape[0]
    assert (free == (refcount == 0)).all()
    assert int(free.sum()) + int((refcount > 0).sum()) == n_pages
    assert (refcount >= 0).all()
    table_refs = np.bincount(
        table[table >= 0].ravel(), minlength=n_pages
    )[:n_pages]
    cache_pages = eng.prefix.pages()
    assert len(cache_pages) == len(set(cache_pages))  # one node per page
    cache_refs = np.zeros((n_pages,), np.int64)
    for p in cache_pages:
        cache_refs[p] = 1
    assert (refcount == table_refs + cache_refs).all(), (
        refcount.tolist(), table_refs.tolist(), cache_pages,
    )


# ------------------------------------------------ kv_pages refcount ops


def _tiny_pool(n_pages=6, page_len=4, max_slots=3, pages_per_seq=4):
    return kv_pages.init_page_pool(
        CFG, n_pages=n_pages, page_len=page_len, max_slots=max_slots,
        pages_per_seq=pages_per_seq,
    )


def test_adopt_prefix_shares_by_reference_and_cow_copies_bitwise():
    pool = _tiny_pool()
    # slot 0 allocates page for its position-0 page and fills the pool
    # rows with recognizable values
    pool, ok = kv_pages.reserve_pages(
        pool, jnp.arange(3), jnp.zeros((3,), jnp.int32),
        jnp.asarray([True, False, False]),
    )
    assert bool(ok)
    src = int(np.asarray(pool["page_table"])[0, 0])
    k = pool["k"].at[src].set(
        jax.random.normal(jax.random.PRNGKey(7), pool["k"].shape[1:])
    )
    pool = {**pool, "k": k, "v": k + 1.0}
    # rows 1 and 2 both adopt slot 0's page as a COW source
    pool, ok = kv_pages.adopt_prefix(
        pool,
        jnp.asarray([1, 2, -1]),
        jnp.full((3, 4), -1, jnp.int32),
        jnp.asarray([src, src, -1]),
    )
    assert bool(ok)
    table = np.asarray(pool["page_table"])
    c1, c2 = int(table[1, 0]), int(table[2, 0])
    # two adopters of the same source each get their OWN copy — the COW
    # page is reachable from exactly one table
    assert len({src, c1, c2}) == 3
    kp = np.asarray(pool["k"])
    np.testing.assert_array_equal(kp[c1], kp[src])
    np.testing.assert_array_equal(kp[c2], kp[src])
    np.testing.assert_array_equal(
        np.asarray(pool["v"])[c1], np.asarray(pool["v"])[src]
    )
    rc = np.asarray(pool["refcount"])
    assert rc[src] == 1 and rc[c1] == 1 and rc[c2] == 1


def test_adopt_prefix_by_reference_bumps_refcount():
    pool = _tiny_pool()
    pool, ok = kv_pages.reserve_pages(
        pool, jnp.arange(3), jnp.zeros((3,), jnp.int32),
        jnp.asarray([True, False, False]),
    )
    page = int(np.asarray(pool["page_table"])[0, 0])
    adopt = np.full((3, 4), -1, np.int32)
    adopt[1, 0] = page
    pool, ok = kv_pages.adopt_prefix(
        pool, jnp.asarray([-1, 1, -1]), jnp.asarray(adopt),
        jnp.full((3,), -1, jnp.int32),
    )
    assert bool(ok)
    rc = np.asarray(pool["refcount"])
    assert rc[page] == 2
    # releasing ONE owner keeps the page resident; the second frees it
    pool = kv_pages.release_slots(
        pool, jnp.asarray([True, False, False])
    )
    assert np.asarray(pool["refcount"])[page] == 1
    assert not bool(np.asarray(pool["free"])[page])
    pool = kv_pages.release_slots(
        pool, jnp.asarray([False, True, False])
    )
    assert np.asarray(pool["refcount"])[page] == 0
    assert bool(np.asarray(pool["free"])[page])


def test_adopt_prefix_all_or_nothing_when_cow_cannot_fit():
    pool = _tiny_pool(n_pages=2)
    # exhaust the pool: two slots take one page each
    pool, ok = kv_pages.reserve_pages(
        pool, jnp.arange(3), jnp.zeros((3,), jnp.int32),
        jnp.asarray([True, True, False]),
    )
    assert bool(ok) and int(np.asarray(pool["free"]).sum()) == 0
    before_rc = np.asarray(pool["refcount"]).copy()
    before_tb = np.asarray(pool["page_table"]).copy()
    src = int(before_tb[0, 0])
    adopt = np.full((3, 4), -1, np.int32)
    adopt[2, 0] = src
    pool, ok = kv_pages.adopt_prefix(
        pool, jnp.asarray([-1, -1, 2]), jnp.asarray(adopt),
        jnp.asarray([-1, -1, src]),
    )
    # the COW copy cannot fit: NOTHING adopted, not even the
    # by-reference entry of the same row
    assert not bool(ok)
    np.testing.assert_array_equal(np.asarray(pool["refcount"]), before_rc)
    np.testing.assert_array_equal(
        np.asarray(pool["page_table"]), before_tb
    )


def test_ref_unref_roundtrip_and_pad_rows():
    pool = _tiny_pool()
    pool, _ = kv_pages.reserve_pages(
        pool, jnp.arange(3), jnp.zeros((3,), jnp.int32),
        jnp.asarray([True, False, False]),
    )
    page = int(np.asarray(pool["page_table"])[0, 0])
    pool = kv_pages.ref_pages(pool, jnp.asarray([page, -1, -1]))
    assert np.asarray(pool["refcount"])[page] == 2
    pool = kv_pages.release_slots(
        pool, jnp.asarray([True, False, False])
    )
    # the cache reference keeps the page out of the free set
    assert not bool(np.asarray(pool["free"])[page])
    pool = kv_pages.unref_pages(pool, jnp.asarray([page, -1, -1]))
    assert bool(np.asarray(pool["free"])[page])
    assert int(np.asarray(pool["refcount"]).sum()) == 0


# ------------------------------------------------------- radix tree


def test_radix_match_always_leaves_a_suffix_token():
    c = PrefixCache(page_len=4)
    prompt = [1, 2, 3, 4, 5, 6]
    assert c.match(prompt).matched == 0
    c.insert(prompt, [10, 11, -1, -1])
    # the identical prompt matches page-granularly but NEVER the whole
    # prompt — the engine must run the model once for the first token
    m = c.match(prompt)
    assert m.matched < len(prompt)
    assert m.matched == 4 and m.pages == [10] and m.cow_src == -1
    # a longer prompt with the same prefix takes full page + partial
    m = c.match(prompt + [7, 8])
    assert m.matched == 6 and m.pages == [10] and m.cow_src == 11


def test_radix_insert_claims_each_page_once():
    c = PrefixCache(page_len=4)
    prompt = [1, 2, 3, 4, 5, 6]
    assert c.insert(prompt, [10, 11, -1, -1]) == [10, 11]
    assert c.held_pages == 2 and sorted(c.pages()) == [10, 11]
    # same content at the same position claims nothing new
    assert c.insert(prompt, [20, 21, -1, -1]) == []
    assert c.held_pages == 2
    # a divergent suffix under the shared first page claims its own tail
    assert c.insert([1, 2, 3, 4, 9], [20, 22, -1, -1]) == [22]
    assert c.held_pages == 3


def test_radix_evicts_lru_leaves_first_and_respects_pins():
    c = PrefixCache(page_len=2)
    c.insert([1, 2, 3], [10, 11, -1])   # full 10, partial 11
    c.insert([5, 6, 7], [20, 21, -1])   # full 20, partial 21
    c.match([1, 2, 3])  # touch the first chain: second is now LRU
    assert c.evictable_pages(set()) == 4
    # a pinned leaf protects itself AND its parent (children first)
    assert c.evictable_pages({21}) == 2
    got = c.evict(2, {21})
    assert got == [11, 10]  # LRU-touched chain survives the pin? no:
    # 21 pinned -> 20 not fully evictable -> the first chain goes,
    # leaf (11) before its parent (10)
    assert c.held_pages == 2 and c.evictions == 2
    # re-inserting the evicted prefix claims fresh pages again
    assert c.insert([1, 2, 3], [30, 31, -1]) == [30, 31]


# ------------------------------------------- bitwise cached == cold


PREFIX = [11, 12, 13, 14, 15, 16]  # full page (4) + partial tail (2)


def test_prefix_cached_decode_matches_dense_across_cow_boundary(params):
    """The tentpole pin: a radix hit that shares one full page by
    reference AND copy-on-write duplicates the partial tail page
    reproduces the dense fp32 greedy decode bitwise, token for token."""
    reqs = [
        # cold: populates full node [11..14] + PARTIAL node [15,16]
        (PREFIX, 3),
        (PREFIX + [31, 32], 4),   # hit: ref page + COW the partial
        (PREFIX + [41, 42], 4),   # second hit (same COW source again)
    ]
    eng = make_engine(params)
    # warming every prefill width (the off-the-clock compile path)
    # must leave engine and pool state untouched
    eng.warmup()
    assert bool(np.asarray(jax.device_get(eng.pool["free"])).all())
    assert eng.admitted == 0 and eng._prefills == 0
    got = serve_tokens(eng, reqs)
    for (prompt, max_new), tokens in zip(reqs, got):
        assert tokens == dense_greedy(params, prompt, max_new), prompt
    s = eng.prefix.stats()
    assert s["hits"] == 2 and s["lookups"] == 3
    assert s["hit_tokens"] == 2 * len(PREFIX)  # matched: page + partial
    # SAVED counts every matched position: rows start at their own
    # matched length, the COW'd partial page included, so the pass
    # computes none of the 6
    assert eng.prefill_tokens_saved == 2 * len(PREFIX)
    assert eng.prefill_flops_saved > 0
    assert eng.pool_ok_failures == 0
    assert_pool_invariants(eng)


def test_prefix_cache_survives_eviction_then_readmit(params):
    """LRU eviction is only ever a MISS: after page pressure evicts the
    cached prefix, readmitting the same prompt recomputes it bitwise
    (and re-caches it — the next request hits again)."""
    eng = make_engine(params, n_pages=6, max_slots=1)
    others = [
        ([51, 52, 53, 54, 55, 56], 2),
        ([61, 62, 63, 64, 65, 66], 2),
    ]
    reqs = (
        [(PREFIX, 2)] + others          # fill the cache: 6 pages held
        + [(PREFIX, 2), (PREFIX, 2)]    # evicted -> miss, then hit again
    )
    got = serve_tokens(eng, reqs)
    for (prompt, max_new), tokens in zip(reqs, got):
        assert tokens == dense_greedy(params, prompt, max_new), prompt
    s = eng.prefix.stats()
    assert s["evictions"] > 0
    # the readmitted prefix missed (no hit), the one after it hit
    assert s["hits"] >= 1
    assert eng.pool_ok_failures == 0
    assert_pool_invariants(eng)


@pytest.mark.parametrize("tp", [1, 2])
def test_refcount_pool_invariant_under_interleavings(params, tp):
    """Satellite: seeded property-style sweep.  Random shared-prefix
    traffic against a TIGHT pool (evictions, COW, backpressure, and
    mid-flight completions all interleave) keeps the refcount pool
    invariant exact at every scheduler step, and a full teardown frees
    every page (no leak, no double-free).  tp=2 (PR 18) runs the
    identical sweep on the head-dim-sharded pool: the sharing ops'
    refcount accounting is layout-oblivious, so the invariant holds
    bit-for-bit on the replicated accounting buffers."""
    for seed in (0, 1, 2):
        rng = np.random.RandomState(seed)
        eng = make_engine(
            params, n_pages=8, max_slots=2, prefill_batch=2, tp=tp,
        )
        prefixes = [
            [int(x) for x in rng.randint(1, CFG.vocab_size, size=6)]
            for _ in range(3)
        ]
        for _ in range(40):
            if rng.uniform() < 0.6:
                k = int(rng.randint(len(prefixes)))
                suffix = [int(x) for x in rng.randint(
                    1, CFG.vocab_size, size=2
                )]
                eng.submit(eng.make_request(
                    prefixes[k] + suffix, int(rng.randint(1, 4))
                ))
            eng.step()
            assert_pool_invariants(eng)
        drain(eng)
        eng.step()  # flush the final releases
        assert_pool_invariants(eng)
        # teardown: evict the whole cache; the pool must drain to empty
        evicted = eng.prefix.evict(eng.n_pages, set())
        if evicted:
            pages = np.full((eng.n_pages,), -1, np.int32)
            pages[: len(evicted)] = evicted
            eng.pool = kv_pages.unref_pages(eng.pool, jnp.asarray(pages))
        assert eng.prefix.held_pages == 0
        refcount = np.asarray(jax.device_get(eng.pool["refcount"]))
        assert (refcount == 0).all(), (seed, refcount.tolist())
        assert bool(np.asarray(jax.device_get(eng.pool["free"])).all())


def test_cached_engine_strictly_faster_on_the_virtual_clock(params):
    """The perf claim the A/B gates: identical shared-prefix trace,
    identical admission budget — the cached engine drains sooner on the
    virtual clock (prefill charged for the scan it actually ran) and
    emits the identical tokens."""
    spec = TrafficSpec(
        seed=0, duration_s=2.0, rate_rps=6.0, profile="shared",
        vocab_size=CFG.vocab_size,
    )
    trace = synth_trace(spec)
    assert len(trace) >= 4
    walls, streams = {}, {}
    for arm, on in (("cached", True), ("cold", False)):
        eng = make_engine(params, prefix_cache=on, prefill_batch=2)
        eng.run(trace, max_steps=5_000)
        m = eng.metrics()
        walls[arm] = m["wall_s"]
        streams[arm] = {r.rid: list(r.tokens) for r in eng.done}
        if on:
            assert m["prefix_hit_rate"] > 0
            assert m["prefill_tokens_saved"] > 0
            assert m["prefill_flops_saved"] > 0
        else:
            assert m["prefix_hit_rate"] is None
            assert m["prefill_tokens_saved"] == 0
    assert walls["cached"] < walls["cold"], walls
    common = set(streams["cached"]) & set(streams["cold"])
    assert common
    for rid in common:
        assert streams["cached"][rid] == streams["cold"][rid]


def test_driver_prefix_ab_gates_green(params):
    """driver.prefix_ab_compare on the seeded shared trace: skipped
    prefill work, a strict virtual-clock win, matching tokens — and
    tools/serve_report.check_prefix_ab passes the resulting cell."""
    from ddl25spring_tpu.serve import driver
    from tools import serve_report

    knobs = driver.engine_knobs(smoke=True)
    assert knobs["prefix_cache"] is True  # DDL25_SERVE_PREFIX default
    spec = TrafficSpec(
        seed=0, duration_s=2.0, rate_rps=6.0, profile="shared",
        vocab_size=CFG.vocab_size,
    )
    pab = driver.prefix_ab_compare(
        params, CFG, synth_trace(spec), knobs
    )
    assert pab["advantage_tokens"] > 0
    assert pab["tokens_match"] is True
    assert pab["cached"]["prefill_tokens_saved"] > 0
    assert (pab["cached"]["tokens_per_sec_per_chip"]
            > pab["cold"]["tokens_per_sec_per_chip"])
    row = {
        "key": {"profile": "shared"},
        "prefix_hit_rate": pab["cached"]["prefix_hit_rate"],
        "prefix_ab": driver._prefix_ab_cell(pab),
    }
    assert serve_report.check_prefix_ab([row]) == []
    # the full-doc shape (serve.json) judges identically
    doc = {"key": {"profile": "shared"},
           "ramp": {"prefix_hit_rate":
                    pab["cached"]["prefix_hit_rate"]},
           "prefix_ab": pab}
    assert serve_report.check_prefix_ab([doc]) == []


# --------------------------------------------------- report gates


def test_check_prefix_ab_fails_on_defects():
    from tools import serve_report

    assert serve_report.check_prefix_ab(
        [{"key": {"profile": "shared"}}]
    ) != []  # no cell at all
    bad = {
        "key": {"profile": "shared"},
        "prefix_hit_rate": 0.0,
        "prefix_ab": {
            "budget_s": 1.0,
            "cached_tokens_at_budget": 10,
            "cold_tokens_at_budget": 12,
            "advantage_tokens": -2,
            "tokens_match": False,
            "compared_requests": 3,
            "cached_tokens_per_sec_per_chip": 5.0,
            "cold_tokens_per_sec_per_chip": 6.0,
            "prefill_tokens_saved": 0,
        },
    }
    fails = serve_report.check_prefix_ab([bad])
    assert len(fails) == 5  # saved, tps, budget, match, hit-rate
    assert any("tokens_match" in f or "token-for-token" in f
               for f in fails)
    # tokens_match=True over ZERO compared requests is vacuous — the
    # gate must treat an empty comparison as a failure, not a pass
    vacuous = {
        "key": {"profile": "shared"},
        "prefix_hit_rate": 0.5,
        "prefix_ab": {
            **bad["prefix_ab"],
            "advantage_tokens": 2,
            "prefill_tokens_saved": 8,
            "cached_tokens_per_sec_per_chip": 7.0,
            "tokens_match": True,
            "compared_requests": 0,
        },
    }
    fails = serve_report.check_prefix_ab([vacuous])
    assert len(fails) == 1 and "compared request" in fails[0]


def test_check_group_gates_prefix_hit_rate_on_shared_runs():
    from tools import serve_report

    def row(hit):
        return {
            "key": {"profile": "shared"},
            "tokens_per_sec_per_chip": 10.0,
            "ttft_s_p95": 0.1,
            "prefix_hit_rate": hit,
        }

    assert serve_report.check_group([row(0.8), row(0.7)]) == []
    fails = serve_report.check_group([row(0.8), row(0.8), row(0.1)])
    assert any("prefix_hit_rate" in f for f in fails)
    # NOT gated off the shared profile (random prompts may simply miss)
    cold = [dict(r, key={"profile": "ramp"})
            for r in (row(0.8), row(0.8), row(0.0))]
    assert serve_report.check_group(cold) == []


# -------------------------------------------------------- traffic


def test_shared_profile_shape_and_determinism():
    spec = TrafficSpec(
        seed=5, duration_s=3.0, rate_rps=8.0, profile="shared",
    )
    trace = synth_trace(spec)
    assert len(trace) > 4
    plen = spec.shared_prefix_len + spec.shared_suffix_len
    assert all(len(r["prompt"]) == plen for r in trace)
    # every prompt starts with one of the K system prompts
    heads = {tuple(r["prompt"][: spec.shared_prefix_len]) for r in trace}
    assert 1 <= len(heads) <= spec.shared_prefixes
    assert synth_trace(spec) == trace
    assert synth_trace(TrafficSpec(
        seed=6, duration_s=3.0, rate_rps=8.0, profile="shared",
    )) != trace


def test_traffic_profiles_replay_across_process_restarts():
    """Satellite: every profile (flat/ramp/spike + shared) replays the
    IDENTICAL trace for the same seed in a fresh process — the A/B
    gates and the ledger trend depend on it."""
    specs = [
        {"seed": 3, "duration_s": 2.0, "rate_rps": 8.0, "profile": p}
        for p in PROFILES
    ]
    local = [synth_trace(TrafficSpec(**s)) for s in specs]
    code = (
        "import json, sys\n"
        "from ddl25spring_tpu.serve.traffic import TrafficSpec, "
        "synth_trace\n"
        "specs = json.loads(sys.argv[1])\n"
        "print(json.dumps([synth_trace(TrafficSpec(**s)) "
        "for s in specs]))\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", code, json.dumps(specs)],
        capture_output=True, text=True, check=True,
    )
    assert json.loads(r.stdout) == local
