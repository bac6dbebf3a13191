"""The wide prefill pass (PR 27): every position of a prompt batch in one
pass, at a width taken from the batch.

What it is held to is the one-token step it replaced as the prefill's
body (``serve/spec.py``'s ``_position_step``: the decode tick's block at
explicit positions), run position by position on the same pool, on the
15M preset in float32:

- the pass writes the same pages: tables, refcounts and free set equal,
  every K/V row equal within ``KV_RTOL`` / ``KV_ATOL`` (a matmul over T
  rows sums in another order than T matmuls over one);
- the first tokens and the greedy stream that follows are identical;
- the dense decode path (``models/decode.decode_step``), which shares no
  block with the pass, leaves the same K and V and the same first tokens;
- the pass's shape ``(rows, width)`` is the ladder's cheapest that holds
  the batch's rows and its longest unmatched suffix (PR 34: a request
  admitted alone is not padded to ``prefill_batch`` rows, and leaves what
  it leaves at the full shape), a batch of radix hits rides a narrower
  pass than its cold twin, and nothing compiles after ``warmup()``;
- a pool too small fails the pass whole.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl25spring_tpu import obs
from ddl25spring_tpu.models import llama
from ddl25spring_tpu.serve import kv_pages
from ddl25spring_tpu.serve.engine import (
    ServeEngine,
    make_decode_tick,
    make_prefill,
    pass_shapes,
    prefill_widths,
    shape_for,
)
from ddl25spring_tpu.serve.spec import _position_step
from ddl25spring_tpu.utils.config import LlamaConfig

CFG = LlamaConfig(dtype="float32")  # the 15M preset
PAGE_LEN, PAGES_PER_SEQ, SLOTS, N_PAGES = 16, 8, 4, 24
MAX_PROMPT = 64
# float32 holds 2^-24 = 6e-8 a rounding; K and V reach 1.6 after six
# blocks of sums over 288 and 1,152 terms taken in another order, and
# differ by at most 1.3e-6 here: eight times of room
KV_RTOL, KV_ATOL = 1e-4, 1e-5
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@pytest.fixture(scope="module")
def params():
    return llama.init_llama_params(jax.random.PRNGKey(0), CFG)


def fresh_pool(n_pages=N_PAGES):
    return kv_pages.init_page_pool(
        CFG, n_pages=n_pages, page_len=PAGE_LEN, max_slots=SLOTS,
        pages_per_seq=PAGES_PER_SEQ,
    )


def tokens_of(seed, n):
    return np.random.default_rng(seed).integers(1, CFG.vocab_size, n).tolist()


@pytest.fixture(scope="module")
def step():
    return jax.jit(_position_step(CFG, None))


def serial(step, params, pool, prompts, lo, hi, valid):
    """The one-token step over positions ``[lo[s], hi[s])`` of each slot,
    position by position; returns the pool and each slot's argmax after
    its LAST position."""
    last = np.zeros((SLOTS,), np.int32)
    for i in range(int(max(hi))):
        tok = np.asarray(
            [p[i] if i < len(p) else 0 for p in prompts], np.int32
        )
        writing = valid & (i >= lo) & (i < hi)
        pool, g, _absmax, ok = step(
            params, pool, jnp.asarray(tok), jnp.full((SLOTS,), i, jnp.int32),
            jnp.asarray(writing), pool["active"],
        )
        assert bool(ok)
        last = np.where(i == hi - 1, np.asarray(g), last)
    return pool, last


# rows are slots here (slot_ids = 0..3): three prompts of different
# lengths and starts across page boundaries, and a padding row
CASES = {
    # two cold rows and one that starts past a full adopted page
    "cold_and_page_hit": ([37, 50, 21, 0], [0, 16, 0, 0]),
    # a start inside a page (the COW'd partial page of a radix hit), one
    # at the last position but one, and a one-token prompt
    "partial_page_starts": ([64, 33, 1, 0], [5, 31, 0, 0]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_wide_pass_equals_the_one_token_step(params, step, case):
    lens, starts = (np.asarray(a, np.int32) for a in CASES[case])
    prompts = [tokens_of(7 + s, n) for s, n in enumerate(lens)]
    valid = lens > 0
    slot_ids = np.where(valid, np.arange(SLOTS), -1).astype(np.int32)

    # what a hit finds: its first `starts` positions already in pages of
    # its table (the one-token step wrote them; adoption seats the same)
    pool = kv_pages.activate_slots(
        fresh_pool(), jnp.asarray(slot_ids), jnp.asarray(valid)
    )
    pool, _ = serial(step, params, pool, prompts, np.zeros_like(starts),
                     starts, valid)

    ref, ref_first = serial(step, params, pool, prompts, starts, lens, valid)
    ref = {**ref, "seq_len": jnp.asarray(lens)}

    width = next(w for w in prefill_widths(MAX_PROMPT)
                 if w >= (lens - starts).max())
    packed = np.zeros((SLOTS, width), np.int32)
    for s, p in enumerate(prompts):
        packed[s, : lens[s] - starts[s]] = p[starts[s]:]
    prefill = jax.jit(make_prefill(CFG, max_prompt_len=MAX_PROMPT,
                                   sentinel=False))
    got, out, _key = prefill(
        params, pool, jnp.asarray(packed), jnp.asarray(lens),
        jnp.asarray(starts), jnp.asarray(slot_ids), jax.random.PRNGKey(0),
    )
    assert int(out[-1]) == 1  # the pool flag rides last
    first = out[:SLOTS]

    # the same pages in the same tables (the one reservation allocates
    # in the order the position-by-position walk did) ...
    for key in ("page_table", "refcount", "free", "seq_len", "active"):
        np.testing.assert_array_equal(got[key], ref[key], err_msg=key)
    # ... holding the same K and V (the trash page, last, is never read)
    for key in ("k", "v"):
        np.testing.assert_allclose(
            got[key][:-1], ref[key][:-1], rtol=KV_RTOL, atol=KV_ATOL,
            err_msg=key,
        )
    written = int(np.ceil(lens[valid] / PAGE_LEN).sum())
    assert int((~np.asarray(got["free"])).sum()) == written
    # the first tokens, and the greedy stream after them
    np.testing.assert_array_equal(np.asarray(first)[valid], ref_first[valid])
    # which the pass left at its slots for the tick to read
    np.testing.assert_array_equal(
        np.asarray(got["last_tok"])[valid], ref_first[valid]
    )
    tick = jax.jit(make_decode_tick(CFG, sentinel=False))
    ref = {**ref, "last_tok": jnp.asarray(
        np.where(valid, ref_first, 0).astype(np.int32)
    )}
    for _ in range(6):
        got, a, _ = tick(params, got, jax.random.PRNGKey(0))
        ref, b, _ = tick(params, ref, jax.random.PRNGKey(0))
        assert int(a[-1]) == int(b[-1]) == 1
        np.testing.assert_array_equal(np.asarray(a)[:SLOTS][valid],
                                      np.asarray(b)[:SLOTS][valid])


@pytest.mark.parametrize("lens", [
    (37, 64, 21, 0),   # the full width, and a padding row
    (1, 16, 17, 48),   # one token; a page exactly full; one position past it
])
def test_wide_pass_writes_the_dense_paths_keys_and_values(params, lens):
    """The one-token step shares ``_paged_block`` with the pass, so a
    fault in the block would pass the comparison above.  The dense
    decode path (``models/decode.decode_step``: its own block over a
    contiguous ``[L, B, max_len, H, hd]`` cache) shares none of it: a
    cold batch's pages hold its K and V, and the first tokens are its
    argmax."""
    from ddl25spring_tpu.models.decode import decode_step, init_kv_cache

    lens = np.asarray(lens, np.int32)
    prompts = [tokens_of(20 + s, n) for s, n in enumerate(lens)]
    valid = lens > 0
    slot_ids = np.where(valid, np.arange(SLOTS), -1).astype(np.int32)
    packed = np.zeros((SLOTS, MAX_PROMPT), np.int32)
    for s, p in enumerate(prompts):
        packed[s, : lens[s]] = p

    dense = jax.jit(lambda p, c, t, i: decode_step(p, c, t, i, CFG))
    cache = init_kv_cache(CFG, SLOTS, MAX_PROMPT)
    want_first = np.zeros((SLOTS,), np.int32)
    for i in range(MAX_PROMPT):
        logits, cache = dense(params, cache, jnp.asarray(packed[:, i]),
                              jnp.int32(i))
        want_first = np.where(i == lens - 1, np.argmax(logits, -1), want_first)

    prefill = jax.jit(make_prefill(CFG, max_prompt_len=MAX_PROMPT,
                                   sentinel=False))
    pool = kv_pages.activate_slots(
        fresh_pool(), jnp.asarray(slot_ids), jnp.asarray(valid)
    )
    pool, out, _key = prefill(
        params, pool, jnp.asarray(packed), jnp.asarray(lens),
        jnp.zeros((SLOTS,), jnp.int32), jnp.asarray(slot_ids),
        jax.random.PRNGKey(0),
    )
    assert int(out[-1]) == 1  # the pool flag rides last
    first = out[:SLOTS]
    np.testing.assert_array_equal(np.asarray(first)[valid], want_first[valid])
    table = np.asarray(pool["page_table"])
    for key, want in zip(("k", "v"), cache):
        pages = np.asarray(pool[key])  # [n_pages + 1, L, page_len, H, hd]
        for s in np.flatnonzero(valid):
            n = int(lens[s])
            got = np.concatenate(
                [pages[p] for p in table[s, : -(-n // PAGE_LEN)]], axis=1
            )[:, :n]
            np.testing.assert_allclose(
                got, np.asarray(want)[:, s, :n], rtol=KV_RTOL, atol=KV_ATOL,
                err_msg=f"{key} of slot {s}",
            )


def test_a_pool_too_small_fails_the_pass_whole(params):
    """Two prompts of 3 pages each against 5 free pages: ``ok`` is false
    and NOTHING is reserved (``reserve_pages`` is all-or-nothing and the
    pass calls it once)."""
    lens = np.asarray([40, 40, 0, 0], np.int32)
    slot_ids = np.asarray([0, 1, -1, -1], np.int32)
    packed = np.zeros((SLOTS, MAX_PROMPT), np.int32)
    packed[:2, :40] = [tokens_of(1, 40), tokens_of(2, 40)]
    prefill = jax.jit(make_prefill(CFG, max_prompt_len=MAX_PROMPT,
                                   sentinel=False))
    args = (jnp.asarray(packed), jnp.asarray(lens),
            jnp.zeros((SLOTS,), jnp.int32), jnp.asarray(slot_ids),
            jax.random.PRNGKey(0))
    pool, out, _key = prefill(params, fresh_pool(n_pages=5), *args)
    assert int(out[-1]) == 0  # the pool flag rides last
    assert bool(np.asarray(pool["free"]).all())
    assert int(np.asarray(pool["refcount"]).sum()) == 0
    assert (np.asarray(pool["page_table"]) == -1).all()
    # one page more and the same pass fits
    pool, out, _key = prefill(params, fresh_pool(n_pages=6), *args)
    assert int(out[-1]) == 1 and int((~np.asarray(pool["free"])).sum()) == 6


# --------------------------------------------------------- the ladder


@pytest.mark.parametrize("prefill_batch,max_prompt_len,shapes", [
    (8, 256, ((2, 128), (2, 256), (4, 256), (8, 256))),  # the dense cell's
    (8, 512, ((2, 256), (2, 512), (4, 512), (8, 512))),  # the expert cells'
    (4, 64, ((1, 32), (1, 64), (2, 64), (4, 64))),
    (16, 7, ((4, 4), (4, 7), (8, 7), (16, 7))),
    (2, 64, ((1, 32), (1, 64), (2, 64))),  # a quarter and a half are one row
    (3, 8, ((1, 4), (1, 8), (3, 8))),
    (1, 8, ((1, 4), (1, 8))),  # one row count
    (8, 1, ((2, 1), (4, 1), (8, 1))),  # one width
    (1, 1, ((1, 1),)),
])
def test_the_ladder_is_a_quarter_of_the_rows_at_two_widths_then_full_width(
        prefill_batch, max_prompt_len, shapes):
    """A function of ``prefill_batch`` and ``max_prompt_len`` alone, at
    most four shapes, cheapest first, the last of them the full one, and
    every batch the scheduler can admit rides one of them."""
    got = pass_shapes(prefill_batch, max_prompt_len)
    assert got == shapes and len(got) <= 4
    assert got[-1] == (prefill_batch, max_prompt_len)
    assert [r * w for r, w in got] == sorted(r * w for r, w in got)
    assert prefill_widths(max_prompt_len) == tuple(sorted({w for _, w in got}))
    for rows in range(1, prefill_batch + 1):
        for longest in {1, -(-max_prompt_len // 2), max_prompt_len}:
            r, w = shape_for(got, rows, longest)
            assert r >= rows and w >= longest


@pytest.fixture(autouse=True)
def _clean_rings():
    obs.counters.reset()
    yield
    obs.counters.reset()


def make_engine(params, **kw):
    kw.setdefault("page_len", PAGE_LEN)
    kw.setdefault("n_pages", 32)
    kw.setdefault("max_slots", SLOTS)
    kw.setdefault("pages_per_seq", PAGES_PER_SEQ)
    kw.setdefault("prefill_batch", 2)
    kw.setdefault("max_prompt_len", MAX_PROMPT)
    kw.setdefault("clock", "virtual")
    kw.setdefault("trace_label", None)
    return ServeEngine(params, CFG, **kw)


def serve(eng, prompts, max_new=2):
    """One batch: submit, then step until drained."""
    reqs = [eng.make_request(p, max_new) for p in prompts]
    for r in reqs:
        assert eng.submit(r) is None
    while eng.queue or any(s is not None for s in eng.slots):
        eng.step()
    eng.step()  # the flush
    return [r.tokens for r in reqs]


def scanned():
    return [int(v) for _, v in obs.counters.window(
        "serve.prefill.scanned_positions", 0.0, time.perf_counter()
    )]


def watch_shapes(eng, name="_prefill"):
    """The shapes of the prompts handed to the engine's prefill program
    from here on."""
    seen = []
    inner = getattr(eng, name)
    setattr(eng, name, lambda p, pool, prompts, *a: (
        seen.append(prompts.shape), inner(p, pool, prompts, *a))[1])
    return seen


@pytest.mark.parametrize("rows,longest,shape", [
    (1, 1, (1, 32)), (1, 32, (1, 32)), (1, 33, (1, 64)), (1, 64, (1, 64)),
    (2, 9, (2, 64)), (2, 64, (2, 64)), (3, 17, (4, 64)), (4, 32, (4, 64)),
    (4, 64, (4, 64)),
])
def test_a_pass_rides_the_cheapest_shape_that_holds_it(
        params, rows, longest, shape):
    eng = make_engine(params, prefill_batch=4)  # (1, 32) (1, 64) (2, 64) (4, 64)
    assert shape_for(
        pass_shapes(eng.prefill_batch, MAX_PROMPT), rows, longest) == shape
    seen = watch_shapes(eng)
    serve(eng, [tokens_of(3, longest)]
          + [tokens_of(4 + i, min(longest, 9)) for i in range(rows - 1)])
    assert seen == [shape]
    assert scanned() == [shape[0] * shape[1]]
    assert eng.admitted == rows and eng.pool_ok_failures == 0


@pytest.mark.parametrize("n", [1, 21, 32])
def test_a_lone_request_leaves_at_the_small_shape_what_it_leaves_at_the_full(
        params, n):
    """The same request through the ladder's cheapest shape and through its
    full one: the same pages in the same table, the same K and V in them,
    the same first token and the same greedy stream after it.  Only
    padding rows went."""
    shapes = pass_shapes(SLOTS, MAX_PROMPT)
    prompt = tokens_of(30 + n, n)
    prefill = jax.jit(make_prefill(CFG, max_prompt_len=MAX_PROMPT,
                                   sentinel=False))

    def one_pass(rows, width):
        packed = np.zeros((rows, width), np.int32)
        packed[0, :n] = prompt
        lens = np.zeros((rows,), np.int32)
        lens[0] = n
        slot_ids = np.full((rows,), -1, np.int32)
        slot_ids[0] = 2
        pool, out, _key = prefill(
            params, fresh_pool(), jnp.asarray(packed), jnp.asarray(lens),
            jnp.zeros((rows,), jnp.int32), jnp.asarray(slot_ids),
            jax.random.PRNGKey(0),
        )
        assert int(out[-1]) == 1  # the pool flag rides last
        # the first tokens, each row's table entries, the flag
        assert out.shape == (rows + rows * MAX_PROMPT // PAGE_LEN + 1,)
        return pool, int(out[0])

    small, full = shape_for(shapes, 1, n), shapes[-1]
    assert small == (1, 32) and full == (SLOTS, MAX_PROMPT)
    got, first = one_pass(*small)
    ref, ref_first = one_pass(*full)
    assert first == ref_first
    for key in ("page_table", "refcount", "free", "seq_len", "active"):
        np.testing.assert_array_equal(got[key], ref[key], err_msg=key)
    for key in ("k", "v"):
        np.testing.assert_allclose(
            got[key][:-1], ref[key][:-1], rtol=KV_RTOL, atol=KV_ATOL,
            err_msg=key,
        )
    tick = jax.jit(make_decode_tick(CFG, sentinel=False))
    for _ in range(6):
        got, a, _ = tick(params, got, jax.random.PRNGKey(0))
        ref, b, _ = tick(params, ref, jax.random.PRNGKey(0))
        assert int(a[2]) == int(b[2])


def test_radix_hits_ride_a_narrower_pass_than_their_cold_twin(params):
    shared = tokens_of(5, 40)  # two full pages of 16 are cacheable
    batch = [shared + tokens_of(6, 6), shared + tokens_of(7, 9)]
    # a quarter of prefill_batch is two rows: (2, 32) (2, 64) (4, 64) (8, 64)
    cold = make_engine(params, prefill_batch=8)
    hot = make_engine(params, prefill_batch=8, prefix_cache=True)
    serve(hot, [shared + tokens_of(8, 3)])  # seeds the cache
    assert scanned() == [2 * 64]  # alone: two rows of the full width, not 8
    obs.counters.reset()
    want = serve(cold, batch)
    assert scanned() == [2 * 64]  # 49 positions: the full width
    obs.counters.reset()
    assert serve(hot, batch) == want  # the same greedy streams
    assert scanned() == [2 * 32]  # 32 matched: suffixes of 14 and 17
    assert hot.prefix.hits == 2
    assert hot.prefill_tokens_saved == hot.prefix.hit_tokens == 2 * 32
    assert hot.pool_ok_failures == cold.pool_ok_failures == 0


@pytest.mark.parametrize("kw", [
    dict(prefix_cache=True), dict(spec_k=2),
    dict(prefix_cache=True, clock="wall"),
], ids=["prefix_cache", "drafter", "run_ahead"])
def test_nothing_compiles_after_warmup(params, kw):
    """Every shape of the ladder (the drafter's passes too), a radix hit
    with a COW'd partial page and the decode tick or speculative round run
    on programs ``warmup()`` already compiled; on the wall clock too,
    where a tick goes to the device behind a pass, on its key and its
    pool."""
    eng = make_engine(params, prefill_batch=4, **kw)
    eng.warmup()
    shapes = pass_shapes(eng.prefill_batch, MAX_PROMPT)
    seen = watch_shapes(eng)
    seen_draft = watch_shapes(eng, "_draft_prefill") if eng.spec_k else None
    compiled = []

    def listener(event, _seconds, **_):
        if event == COMPILE_EVENT:
            compiled.append(event)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        for rows, width in shapes:
            serve(eng, [tokens_of(10 * rows + width + i, width - i)
                        for i in range(rows)])
        if eng.prefix is not None:
            shared = tokens_of(9, 21)  # a full page and a partial one
            serve(eng, [shared])
            serve(eng, [shared + tokens_of(11, 4)])
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert sorted(set(seen)) == sorted(shapes) and len(shapes) == 4
    assert sorted(set(scanned())) == sorted({r * w for r, w in shapes})
    if eng.prefix is not None:
        assert eng.prefix.hits == 1 and eng._prefills == 6
    if eng.spec_k:
        assert sorted(set(seen_draft)) == sorted(shapes)
        assert eng._spec_rounds > 0
    assert compiled == []
