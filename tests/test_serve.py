"""The serving engine (``ddl25spring_tpu/serve``): paged-KV
equivalence pins, continuous batching, admission control, and the
report/gate tooling.

The load-bearing pins:

- **paged == dense, bitwise** — greedy fp32 decode through the page
  pool reproduces ``models/decode.generate`` token for token, including
  a sequence spanning a page boundary and one admitted mid-batch (the
  whole correctness contract of ``kv_pages``).
- **continuous beats static** — on a seeded capacity-bound trace, slots
  refilling mid-flight deliver strictly more tokens by the fixed budget
  than drain-the-whole-batch admission (the reason ``serve/`` exists).
- **compile signatures** — serve-decode/serve-prefill pin all-reduce-
  ONLY collectives over the model axis, riding the session's
  lower-once strategy cache (``tests/conftest.py``) like every
  training strategy.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl25spring_tpu.models import decode as dm, llama
from ddl25spring_tpu.serve import kv_pages
from ddl25spring_tpu.serve.engine import (
    REJECT_BAD_REQUEST,
    REJECT_POOL_EXHAUSTED,
    REJECT_QUEUE_FULL,
    REJECT_TOKEN_BUDGET,
    REJECT_TOO_LONG,
    ServeEngine,
)
from ddl25spring_tpu.serve.traffic import (
    TrafficSpec,
    synth_trace,
    trace_tokens,
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
    return ServeEngine(params, CFG, **kw)


def drain(eng, max_steps: int = 500):
    steps = 0
    while eng.queue or any(s is not None for s in eng.slots):
        eng.step()
        steps += 1
        assert steps < max_steps, "engine failed to drain"


# ------------------------------------------------- equivalence pins


def test_paged_reproduces_dense_across_a_page_boundary(params):
    """fp32 greedy decode through the page-table cache == the dense
    cache, token for token — with prompt 4 + 9 generated crossing the
    page_len=4 boundary twice (pages 4..7 and 8..12)."""
    prompt = [5, 9, 11, 3]
    max_new = 9
    dense = dense_greedy(params, prompt, max_new)

    eng = make_engine(params)
    eng.warmup()  # also pins: warmup leaves no state behind
    assert eng.generated_tokens == 0 and eng.admitted == 0
    assert not eng.ttft_s and not eng.done
    req = eng.make_request(prompt, max_new)
    assert eng.submit(req) is None
    drain(eng)
    assert req.tokens == dense
    assert eng.pool_ok_failures == 0


def test_mid_batch_admission_is_token_exact(params):
    """A request admitted into a slot WHILE another decodes produces
    exactly its own dense generation — the cross-sequence isolation of
    the shared page pool (and the continuous-batching admission path)."""
    a_prompt, a_new = [5, 9, 11, 3], 9
    b_prompt, b_new = [7, 2, 8], 6
    dense_a = dense_greedy(params, a_prompt, a_new)
    dense_b = dense_greedy(params, b_prompt, b_new)

    eng = make_engine(params)
    ra = eng.make_request(a_prompt, a_new)
    assert eng.submit(ra) is None
    eng.step()  # prefill A, first decode tick
    eng.step()  # A decoding
    assert eng.slots[0] is ra and len(ra.tokens) >= 2
    rb = eng.make_request(b_prompt, b_new)
    assert eng.submit(rb) is None
    eng.step()  # admits B mid-flight while A stays resident
    assert rb.admitted_t is not None and ra.done_t is None
    drain(eng)
    assert ra.tokens == dense_a
    assert rb.tokens == dense_b
    assert eng.pool_ok_failures == 0


def test_eos_stops_a_sequence_and_frees_its_slot(params):
    """EOS mid-generation completes the request at the EOS token and
    releases its slot + pages — the capacity-return event continuous
    batching admits into."""
    prompt = [5, 9, 11, 3]
    dense = dense_greedy(params, prompt, 9)
    eos = dense[3]  # 4th generated token
    eng = make_engine(params, eos_id=eos)
    req = eng.make_request(prompt, 9)
    eng.submit(req)
    drain(eng)
    assert req.tokens == dense[:4]
    assert req.tokens[-1] == eos
    # every page returned: the device free mask is full again
    eng.step()  # flush the release mask
    assert int(jnp.sum(eng.pool["free"])) == eng.n_pages
    assert not any(eng.pool["active"].tolist())


def test_pages_freed_on_completion_and_host_mirror(params):
    eng = make_engine(params)
    req = eng.make_request([5, 9, 11, 3], 5)
    eng.submit(req)
    eng.step()
    assert eng._host_pages_used() > 0
    drain(eng)
    eng.step()  # flush release
    assert eng._host_pages_used() == 0
    assert int(jnp.sum(~eng.pool["free"])) == 0
    # 4 prompt + 4 appended generated tokens = 8 written positions ->
    # 2 pages of 4 at peak (the final sampled token is never written
    # back: its KV would only feed a token past the stop)
    assert eng.peak_pages == 2
    assert eng.metrics()["page_pool_peak_pages"] == 2


# ------------------------------------------------- continuous batching


def test_continuous_beats_static_on_the_seeded_trace(params):
    """THE acceptance pin: same trace, same engine knobs, virtual
    clock — admission into mid-flight freed slots delivers strictly
    more tokens by the fixed budget than static drain-then-refill."""
    from ddl25spring_tpu.serve.driver import ab_compare

    spec = TrafficSpec(
        seed=3, duration_s=0.2, rate_rps=120.0, profile="flat",
        vocab_size=CFG.vocab_size,
    )
    trace = synth_trace(spec)
    assert len(trace) >= 10
    knobs = dict(
        page_len=4, n_pages=16, max_slots=2, prefill_batch=2,
        max_prompt_len=8, max_queue=64, token_budget=None, eos_id=None,
    )
    ab = ab_compare(params, CFG, trace, knobs)
    assert ab["continuous_tokens_at_budget"] > ab["static_tokens_at_budget"]
    assert ab["advantage_tokens"] > 0
    # both drained the identical workload in full
    assert (ab["continuous"]["generated_tokens"]
            == ab["static"]["generated_tokens"])
    # and continuous took strictly fewer virtual seconds to do it
    assert (ab["continuous"]["drain_wall_s"]
            < ab["static"]["drain_wall_s"])


def test_ab_compare_equalizes_prefill_width(params):
    """The A/B must isolate admission policy: with prefill_batch <
    max_slots the static arm could never fill the batch (it only
    admits into an all-idle engine), so ab_compare forces
    ``prefill_batch=max_slots`` on BOTH arms.  Four simultaneous
    arrivals at width 2 -> static runs exactly 2 full-width prefills."""
    from ddl25spring_tpu.serve.driver import ab_compare

    trace = [
        {"t": 0.0, "prompt": [1 + i, 2 + i], "max_new": 3}
        for i in range(4)
    ]
    knobs = dict(
        page_len=4, n_pages=16, max_slots=2, prefill_batch=1,
        max_prompt_len=8, max_queue=64, token_budget=None, eos_id=None,
    )
    ab = ab_compare(params, CFG, trace, knobs)
    assert ab["static"]["prefills"] == 2
    assert ab["static"]["completed"] == 4
    assert ab["advantage_tokens"] >= 0


def test_token_timeline_readout(params):
    eng = make_engine(params)
    req = eng.make_request([5, 9], 4)
    eng.submit(req)
    drain(eng)
    assert eng.tokens_at(0.0) == 0
    assert eng.tokens_at(float("inf")) == eng.generated_tokens == 4
    counts = [n for _, n in eng.token_log]
    assert counts == sorted(counts)


# ------------------------------------------------- admission control


def test_rejection_reasons(params):
    eng = make_engine(params, max_queue=1, token_budget=16)
    # over the prefill program's STATIC prompt capacity: malformed for
    # this build (no compiled program can run it) — bad_request at the
    # door, NOT the policy-capacity too_long it was conflated with
    # before PR 11 (too_long should mean "well-formed but over the
    # context budget", so the admission counters stay truthful)
    r = eng.make_request(list(range(1, 10)), 2)
    assert eng.submit(r) == REJECT_BAD_REQUEST
    # too long: prompt + new over pages_per_seq * page_len
    r = eng.make_request([1, 2, 3], 30)
    assert eng.submit(r) == REJECT_TOO_LONG
    # worst-case pages over the whole pool
    small = make_engine(params, n_pages=2, pages_per_seq=4)
    r = small.make_request([1, 2, 3, 4], 8)  # 12 positions -> 3 pages > 2
    assert small.submit(r) == REJECT_POOL_EXHAUSTED
    # queue full
    assert eng.submit(eng.make_request([1], 2)) is None
    assert eng.submit(eng.make_request([1], 2)) == REJECT_QUEUE_FULL
    # token budget (fresh engine: queue holds 3+2 of 16, next 12+2 over)
    eng2 = make_engine(params, token_budget=16)
    assert eng2.submit(eng2.make_request([1, 2, 3], 2)) is None
    assert (eng2.submit(eng2.make_request([1, 2, 3, 4], 10))
            == REJECT_TOKEN_BUDGET)
    # malformed: an empty prompt would decode from the zero-initialized
    # logits buffer (a token the model never produced); non-positive
    # max_new would still emit one token the caller never asked for
    assert eng2.submit(eng2.make_request([], 3)) == REJECT_BAD_REQUEST
    assert eng2.submit(eng2.make_request([1, 2], 0)) == REJECT_BAD_REQUEST
    counts = eng.metrics()["rejected_by_reason"]
    assert counts[REJECT_TOO_LONG] == 1
    assert counts[REJECT_BAD_REQUEST] == 1
    assert counts[REJECT_QUEUE_FULL] == 1
    assert eng2.metrics()["rejected_by_reason"][REJECT_BAD_REQUEST] == 2


def test_head_of_line_backpressure_until_pages_free(params):
    """A request whose worst-case pages exceed the UNRESERVED pool
    waits at the head of the queue (no admission) until a completion
    frees capacity — then admits, and the device-side ok flag never
    fired (host accounting covered the pool exactly)."""
    eng = make_engine(params, n_pages=3, max_slots=2, prefill_batch=2)
    ra = eng.make_request([1, 2, 3, 4], 8)   # 12 pos -> 3 pages
    rb = eng.make_request([5, 6, 7, 8], 8)   # 3 more pages: must wait
    assert eng.submit(ra) is None
    assert eng.submit(rb) is None
    eng.step()
    assert ra.admitted_t is not None and rb.admitted_t is None
    drain(eng)
    assert rb.admitted_t is not None and rb.admitted_t > ra.done_t - 1e-9
    assert len(ra.tokens) == 8 and len(rb.tokens) == 8
    assert eng.pool_ok_failures == 0


def test_static_admission_waits_for_the_batch_to_drain(params):
    eng = make_engine(params, admission="static", prefill_batch=1)
    ra = eng.make_request([1, 2], 6)
    rb = eng.make_request([3, 4], 2)
    eng.submit(ra)
    eng.submit(rb)
    eng.step()
    assert ra.admitted_t is not None and rb.admitted_t is None
    # a free slot exists the whole time, but static admission refuses
    # to use it until EVERY slot is idle
    for _ in range(3):
        eng.step()
        if ra.done_t is None:
            assert rb.admitted_t is None
    drain(eng)
    assert rb.tokens and rb.admitted_t >= ra.done_t - 1e-9


# ------------------------------------------------- kv_pages units


def test_resolve_heads_validates_explicit_zero():
    assert kv_pages.resolve_heads(CFG, None) == CFG.num_heads
    assert kv_pages.resolve_heads(CFG, 1) == 1
    with pytest.raises(ValueError, match="num_heads=0"):
        kv_pages.resolve_heads(CFG, 0)
    with pytest.raises(ValueError, match="num_heads=-2"):
        kv_pages.resolve_heads(CFG, -2)


def test_init_kv_cache_rejects_zero_heads():
    """The ISSUE-10 satellite fix: the old ``num_heads or
    cfg.num_heads`` idiom treated an explicit 0 as unset and silently
    built a full-head cache."""
    with pytest.raises(ValueError, match="num_heads=0"):
        dm.init_kv_cache(CFG, batch=1, max_len=8, num_heads=0)
    k, v = dm.init_kv_cache(CFG, batch=1, max_len=8, num_heads=1)
    assert k.shape == (CFG.n_layers, 1, 8, 1, CFG.head_dim)


def test_page_pool_reserve_write_release_roundtrip():
    pool = kv_pages.init_page_pool(
        CFG, n_pages=4, page_len=2, max_slots=2, pages_per_seq=2,
    )
    slots = jnp.arange(2, dtype=jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    pool, ok = kv_pages.reserve_pages(
        pool, slots, pos, jnp.asarray([True, True])
    )
    assert bool(ok)
    assert int(kv_pages.used_pages(pool)) == 2
    table = np.asarray(pool["page_table"])
    assert (table[:, 0] >= 0).all() and (table[:, 1] == -1).all()
    assert table[0, 0] != table[1, 0]  # distinct pages
    # masked writes land in the trash page, never a live one
    pages, offs = kv_pages.write_page_ids(
        pool, slots, pos, jnp.asarray([True, False])
    )
    assert int(pages[1]) == 4  # the trash row (n_pages)
    pool = kv_pages.release_slots(pool, jnp.asarray([True, False]))
    assert int(kv_pages.used_pages(pool)) == 1
    assert (np.asarray(pool["page_table"])[0] == -1).all()


def test_reserve_pages_refuses_past_table_position_atomically():
    """A needed row whose position falls past the page table must fail
    the WHOLE call with nothing allocated: consuming the page from the
    free mask while the table write drop-routes would leak it forever
    (in no table, so release_slots could never return it)."""
    pool = kv_pages.init_page_pool(
        CFG, n_pages=4, page_len=4, max_slots=2, pages_per_seq=2,
    )
    pool2, ok = kv_pages.reserve_pages(
        pool,
        jnp.asarray([0]),
        jnp.asarray([2 * 4]),  # entry 2 >= pages_per_seq
        jnp.asarray([True]),
    )
    assert not bool(ok)
    assert int(jnp.sum(pool2["free"])) == 4  # nothing consumed
    assert int(kv_pages.used_pages(pool2)) == 0
    assert (pool2["page_table"] == pool["page_table"]).all()


def test_reserve_pages_refuses_overcommit_atomically():
    pool = kv_pages.init_page_pool(
        CFG, n_pages=1, page_len=2, max_slots=2, pages_per_seq=2,
    )
    pool, ok = kv_pages.reserve_pages(
        pool,
        jnp.arange(2, dtype=jnp.int32),
        jnp.zeros((2,), jnp.int32),
        jnp.asarray([True, True]),
    )
    assert not bool(ok)
    # NOTHING allocated: the flag is all-or-nothing
    assert int(kv_pages.used_pages(pool)) == 0


def test_init_page_pool_validates_geometry():
    with pytest.raises(ValueError, match="n_pages=0"):
        kv_pages.init_page_pool(
            CFG, n_pages=0, page_len=2, max_slots=1, pages_per_seq=1,
        )


def test_engine_rejects_explicit_zero_pages_per_seq(params):
    """``pages_per_seq=0`` must fail loudly in the pool, not silently
    fall back to the ctx_size-derived default (the same falsy-zero
    class as the ``init_kv_cache`` ``num_heads=0`` fix)."""
    with pytest.raises(ValueError, match="pages_per_seq=0"):
        make_engine(params, pages_per_seq=0)


def test_engine_rejects_zero_prefill_batch(params):
    """``prefill_batch=0`` admits nothing and never advances the
    virtual clock — run() would spin to max_steps with admitted=0.
    It must fail at construction like the other geometry knobs."""
    with pytest.raises(ValueError, match="prefill_batch=0"):
        make_engine(params, prefill_batch=0)


def test_prefill_completed_request_skips_the_decode_tick(params):
    """A request that completes DURING prefill (max_new=1) must have
    its device slot released before the same step's decode tick: the
    tick would otherwise write KV for a dead sequence and could lazily
    allocate a page neither admission nor the host peak mirror sees."""
    prompt = [5, 9, 11, 3]
    dense_b = dense_greedy(params, [7, 2], 1)
    eng = make_engine(params, prefill_batch=2)
    ra = eng.make_request(prompt, 6)
    rb = eng.make_request([7, 2], 1)
    assert eng.submit(ra) is None and eng.submit(rb) is None
    eng.step()  # prefill admits both; rb completes at its first token
    assert rb.done_t is not None and rb.tokens == dense_b
    assert ra.done_t is None
    # rb's slot (1) is inactive on device and its pages are back in
    # the pool BEFORE the decode tick that ran for ra in this step
    assert not bool(eng.pool["active"][1])
    assert int(jnp.sum(~eng.pool["free"])) == eng._host_pages_used()
    drain(eng)
    eng.step()  # flush ra's release
    assert int(jnp.sum(~eng.pool["free"])) == 0
    assert eng.pool_ok_failures == 0


# ------------------------------------------------- traffic


def test_trace_is_seed_deterministic():
    spec = TrafficSpec(seed=7, duration_s=1.0, rate_rps=10.0)
    a, b = synth_trace(spec), synth_trace(spec)
    assert a == b and len(a) > 0
    c = synth_trace(TrafficSpec(seed=8, duration_s=1.0, rate_rps=10.0))
    assert a != c
    assert all(0.0 <= r["t"] < 1.0 for r in a)
    assert trace_tokens(a) == sum(
        len(r["prompt"]) + r["max_new"] for r in a
    )


def test_ramp_and_spike_profiles_shape_the_rate():
    ramp = TrafficSpec(profile="ramp", rate_rps=10.0, duration_s=10.0)
    assert ramp.rate_at(0.0) == pytest.approx(1.0)
    assert ramp.rate_at(10.0) == pytest.approx(10.0)
    spike = TrafficSpec(profile="spike", rate_rps=10.0, duration_s=9.0)
    assert spike.rate_at(1.0) == pytest.approx(3.0)
    assert spike.rate_at(4.5) == pytest.approx(10.0)
    assert spike.rate_at(8.0) == pytest.approx(3.0)
    with pytest.raises(ValueError, match="profile"):
        TrafficSpec(profile="bogus").rate_at(1.0)
    assert synth_trace(TrafficSpec(rate_rps=0.0)) == []


# ------------------------------------------------- compile signatures


@pytest.mark.parametrize("name,ar_count", [
    ("serve-decode", 2 * 2),          # 2 psums/block x 2 layers
    # a prefill is ONE pass over all its positions: the same 2 psums a
    # block whatever its width (the serial scan ran them x 8 positions)
    ("serve-prefill", 2 * 2),
])
def test_serve_signature_pins(strategy_report, name, ar_count):
    """TP serving traffic is the row-parallel all-reduce ONLY: exact
    count over the model axis, every other collective forbidden, HBM
    inside the registered budget — pinned through the same registry
    gates as every training strategy (lower-once session cache)."""
    r = strategy_report(name)
    assert r["signature_violations"] == []
    assert [f for f in r["findings"] if not f["waived"]] == []
    totals = r["collectives"]["totals"]
    assert set(totals) == {"all-reduce"}
    assert totals["all-reduce"]["count"] == ar_count
    assert r["sched"]["hazards"] == []
    assert r["lowered"] in ("decode_step", "prefill_step")


# ------------------------------------------------- driver + tooling


@pytest.fixture(scope="module")
def smoke_record(params, tmp_path_factory):
    """One tiny end-to-end driver run shared by the contract tests
    (compiles ride the per-engine jit caches; keep it single)."""
    from ddl25spring_tpu.serve import driver

    out = tmp_path_factory.mktemp("serve_run")
    led = str(out / "ledger.jsonl")
    rec = driver.run_serve_bench(
        smoke=True, obs_dir=str(out), duration_s=0.5, rate_rps=40.0,
        profile="ramp", seed=0, ledger_path=led,
    )
    return rec, out, led


SERVE_CONTRACT_KEYS = (
    "tokens_per_sec_per_chip", "ttft_s_p50", "ttft_s_p95",
    "tok_latency_s_p50", "tok_latency_s_p95", "admitted", "rejected",
    "completed", "page_pool_peak_occupancy", "page_pool_peak_pages",
)


def test_driver_emits_the_telemetry_serve_contract(smoke_record):
    from ddl25spring_tpu.serve import driver

    rec, out, led = smoke_record
    cell = driver.serve_cell(rec)
    for k in SERVE_CONTRACT_KEYS:
        assert cell.get(k) is not None, k
    assert cell["ab"]["advantage_tokens"] > 0
    assert json.loads(json.dumps(cell))  # BENCH-line serializable
    # artifacts: serve.json + one ledger row
    doc = json.loads((out / "serve.json").read_text())
    assert doc["record"] == "serve" and doc["ramp"]["admitted"] > 0
    rows = [json.loads(line)
            for line in open(led) if line.strip()]
    # PR 20: the driver also appends a record:"goodput" ledger row —
    # exactly one serve row and one goodput row per run
    by_rec = {}
    for r in rows:
        by_rec.setdefault(r["record"], []).append(r)
    assert sorted(by_rec) == ["goodput", "serve"]
    assert len(by_rec["serve"]) == 1 and len(by_rec["goodput"]) == 1
    serve_row = by_rec["serve"][0]
    assert serve_row["ab"]["advantage_tokens"] > 0
    assert by_rec["goodput"][0]["key"]["scope"] == "serve"
    # raw sample lists stay OUT of the ledger (stdlib tool, 1 line/run)
    assert "ttft_s" not in serve_row and "tick_wall_s" not in serve_row


def test_serve_report_renders_and_checks(smoke_record, capsys):
    import tools.serve_report as serve_report

    rec, out, led = smoke_record
    # run report + single-row ledger: passes with "no baseline yet"
    assert serve_report.main(
        [str(out), "--ledger", led, "--check", "--check-ab"]
    ) == 0
    cap = capsys.readouterr()
    assert "TTFT histogram" in cap.out
    assert "no baseline yet" in cap.err

    # a regressed latest row trips the gate
    row = json.loads((out / "serve.json").read_text())
    good = serve_report.read_ledger(led)[0]
    bad = dict(good)
    bad["tokens_per_sec_per_chip"] = (
        good["tokens_per_sec_per_chip"] * 0.1
    )
    bad["ttft_s_p95"] = good["ttft_s_p95"] * 10
    led2 = str(out / "regressed.jsonl")
    with open(led2, "w") as f:
        for r in (good, good, bad):
            f.write(json.dumps(r) + "\n")
    assert serve_report.main(
        ["--ledger-only", "--ledger", led2, "--check"]
    ) == 1
    cap = capsys.readouterr()
    assert "tokens_per_sec_per_chip" in cap.err
    assert "ttft_s_p95" in cap.err

    # hosts never gate each other: the regressed row on another host
    other = dict(bad, host="elsewhere/64cpu/tpu")
    led3 = str(out / "otherhost.jsonl")
    with open(led3, "w") as f:
        for r in (good, good, other):
            f.write(json.dumps(r) + "\n")
    assert serve_report.main(
        ["--ledger-only", "--ledger", led3, "--check"]
    ) == 0

    # --check-ab trips when continuous failed to beat static
    tied = dict(good)
    tied["ab"] = dict(good["ab"], advantage_tokens=0)
    led4 = str(out / "tied.jsonl")
    with open(led4, "w") as f:
        f.write(json.dumps(tied) + "\n")
    assert serve_report.main(
        ["--ledger-only", "--ledger", led4, "--check", "--check-ab"]
    ) == 1
    # --check-ab alone implies --check: the verdict must gate, not
    # print-and-exit-0
    assert serve_report.main(
        ["--ledger-only", "--ledger", led4, "--check-ab"]
    ) == 1
    assert row["record"] == "serve"  # sanity on the artifact we mutated


def test_check_ab_is_scoped_to_the_run_under_test(smoke_record):
    """A historical row recorded with --no-serve-ab on an UNRELATED
    key must not wedge ``--check-ab`` for the run under test forever;
    the run's OWN group still gates strictly, and ledger-only mode
    (no run dir to scope to) keeps the strict behavior."""
    import tools.serve_report as serve_report

    rec, out, led = smoke_record
    good = serve_report.read_ledger(led)[0]
    stale = {k: v for k, v in good.items() if k != "ab"}
    stale["key"] = dict(good["key"], profile="spike")  # foreign group
    # a foreign key may also hold a documented TIE (unloaded engine)
    tied = dict(good, key=dict(good["key"], rate_rps=0.5))
    tied["ab"] = dict(good["ab"], advantage_tokens=0)
    led2 = str(out / "stale_foreign_ab.jsonl")
    with open(led2, "w") as f:
        for r in (stale, tied, good):
            f.write(json.dumps(r) + "\n")
    assert serve_report.main(
        [str(out), "--ledger", led2, "--check", "--check-ab"]
    ) == 0
    # ledger-only mode has no run to scope to: still strict
    assert serve_report.main(
        ["--ledger-only", "--ledger", led2, "--check", "--check-ab"]
    ) == 1
    # the run's own group missing its ab cell DOES gate
    own = {k: v for k, v in good.items() if k != "ab"}
    led3 = str(out / "own_missing_ab.jsonl")
    with open(led3, "w") as f:
        for r in (stale, own):
            f.write(json.dumps(r) + "\n")
    assert serve_report.main(
        [str(out), "--ledger", led3, "--check", "--check-ab"]
    ) == 1


def test_serve_report_missing_inputs(tmp_path):
    import tools.serve_report as serve_report

    assert serve_report.main(
        [str(tmp_path), "--ledger", str(tmp_path / "none.jsonl")]
    ) == 2  # no serve.json
    assert serve_report.main(
        ["--ledger-only", "--ledger", str(tmp_path / "none.jsonl"),
         "--check"]
    ) == 2  # --check with no ledger


def test_obs_report_renders_the_serving_section(smoke_record):
    from ddl25spring_tpu.obs.report import format_report, summarize_run

    rec, out, led = smoke_record
    s = summarize_run(str(out))
    assert s["serve"]["ramp"]["admitted"] == rec["ramp"]["admitted"]
    text = format_report(s)
    assert "serving (serve.json" in text
    assert "tokens/sec/chip" in text
    assert "A/B continuous" in text
