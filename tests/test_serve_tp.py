"""TP-sharded serving + ZeRO-3 weight streaming (PR 18): the sharded
engine against the dense oracle, the per-chip residency shrink, and
the layout/signature contracts.

The load-bearing pins:

- **tp=2 == dense oracle, bitwise** — the whole paged engine under a
  2-chip ``model`` mesh (KV head dim split, Megatron params) emits the
  IDENTICAL token streams as ``models/decode.generate``, including the
  prefix-cache adopt/COW path and the speculative draft/verify loop.
- **residency divides, the wire does not** — ``mem_budget_bytes()``
  per chip strictly shrinks at tp=2 (global accounting unchanged), the
  ``-tp`` describes compile under a 64 KiB budget one chip cannot meet,
  and the all-reduce payload is byte-exact UNCHANGED by tp.
- **tp unset changes nothing** — the tp=1 engine holds the very same
  ``_PROGRAM_CACHE`` executables as before PR 18 (identity, hence
  byte-identical HLO), and ``DDL25_SERVE_TP`` defaults to 1.
- **the sharing ops are layout-oblivious** — adopt_prefix / ref_pages /
  unref_pages / truncate_to preserve the head-dim split on k/v and the
  replicated accounting, exactly as ``_tp_pool_specs`` declares.
"""

from __future__ import annotations

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl25spring_tpu.models import decode as dm, llama
from ddl25spring_tpu.serve import kv_pages
from ddl25spring_tpu.serve.engine import (
    KV_POOL_HEAD_DIM,
    ServeEngine,
    _compiled_programs,
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


def assert_tp_pool_layout(pool, tp: int = 2):
    """The H013 placement the engine committed: k/v split exactly on
    :data:`KV_POOL_HEAD_DIM` over ``model``, every accounting buffer
    replicated (the host scheduler reads them obliviously)."""
    for name in ("k", "v"):
        spec = pool[name].sharding.spec
        assert len(spec) > KV_POOL_HEAD_DIM and (
            spec[KV_POOL_HEAD_DIM] == "model"
        ), (name, spec)
        assert len(pool[name].sharding.device_set) == tp
    for name in ("page_table", "seq_len", "active", "free", "refcount"):
        assert pool[name].sharding.is_fully_replicated, name


# ------------------------------------------- bitwise oracle equivalence


def test_tp2_matches_dense_oracle_bitwise(params):
    """fp32 greedy decode through the head-split pool on a 2-chip model
    mesh == the dense single-chip cache, token for token — a page-
    boundary-crossing request plus one admitted mid-batch (the whole
    PR-18 correctness contract at once)."""
    a_prompt, a_new = [5, 9, 11, 3], 9
    b_prompt, b_new = [7, 2, 8], 6
    dense_a = dense_greedy(params, a_prompt, a_new)
    dense_b = dense_greedy(params, b_prompt, b_new)

    eng = make_engine(params, tp=2)
    assert eng.tp == 2 and eng.mesh is not None
    assert_tp_pool_layout(eng.pool)
    ra = eng.make_request(a_prompt, a_new)
    assert eng.submit(ra) is None
    eng.step()
    eng.step()
    rb = eng.make_request(b_prompt, b_new)
    assert eng.submit(rb) is None
    eng.step()  # admits B mid-flight while A stays resident
    drain(eng)
    assert ra.tokens == dense_a
    assert rb.tokens == dense_b
    assert eng.pool_ok_failures == 0
    assert_tp_pool_layout(eng.pool)
    m = eng.metrics()
    assert m["tp"] == 2 and m["weight_stream"] is False
    assert m["n_chips"] == 2
    # static residency telemetry: each chip holds strictly less than
    # the global pool/params (the quantity mem_report trends)
    pool_total = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(eng.pool)
    )
    param_total = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(params)
    )
    assert 0 < m["pool_bytes_per_chip"] < pool_total
    assert 0 < m["param_bytes_per_chip"] < param_total


def test_tp2_weight_stream_matches_dense_oracle(params):
    """ZeRO-3 weight streaming inside the decode scan (double-buffered
    per-layer gather, TP slice, row-parallel block) is bit-identical to
    the resident-weights build — same dense-oracle token streams."""
    prompt, max_new = [5, 9, 11, 3], 9
    dense = dense_greedy(params, prompt, max_new)
    eng = make_engine(params, tp=2, weight_stream=True)
    assert eng.weight_stream is True
    req = eng.make_request(prompt, max_new)
    assert eng.submit(req) is None
    drain(eng)
    assert req.tokens == dense
    assert eng.pool_ok_failures == 0
    assert_tp_pool_layout(eng.pool)
    # the [L, n, k] row layout holds params/n per chip: strictly less
    # resident than even the Megatron split keeps
    resident = make_engine(params, tp=2)
    m_ws, m_tp = eng.metrics(), resident.metrics()
    assert m_ws["weight_stream"] is True
    assert m_ws["param_bytes_per_chip"] < m_tp["param_bytes_per_chip"]


def test_tp2_prefix_cache_hit_stays_bitwise(params):
    """The radix adopt/ref sharing path on the SHARDED pool: a repeated
    2-full-page prompt hits the cache (prefill work actually skipped)
    and still reproduces the dense oracle bitwise — adopt_prefix and
    ref_pages never disturb the head split they share pages under."""
    prompt = [5, 9, 11, 3, 7, 2, 8, 6]  # 2 full pages: a clean radix hit
    dense = dense_greedy(params, prompt, 6)
    eng = make_engine(params, tp=2, prefix_cache=True)
    for _ in range(2):
        r = eng.make_request(prompt, 6)
        assert eng.submit(r) is None
        drain(eng)
        assert r.tokens == dense
    assert eng.prefix.hits >= 1
    assert eng.prefill_tokens_saved > 0
    assert eng.pool_ok_failures == 0
    assert_tp_pool_layout(eng.pool)


def test_tp2_speculative_stays_bitwise(params):
    """The draft/verify loop on sharded pools: the tp=2 speculative
    engine (drafter sharded too, truncate_to rolling both pools back)
    emits the dense oracle's exact tokens with real acceptances."""
    prompt, max_new = [5, 9, 11, 3], 9
    dense = dense_greedy(params, prompt, max_new)
    eng = make_engine(params, tp=2, spec_k=2)
    req = eng.make_request(prompt, max_new)
    assert eng.submit(req) is None
    drain(eng)
    assert req.tokens == dense
    assert eng.draft_tokens_accepted > 0
    assert eng.pool_ok_failures == 0
    assert_tp_pool_layout(eng.pool)
    assert_tp_pool_layout(eng.draft_pool)


# ------------------------------------------------- layout obliviousness


def test_sharing_ops_preserve_the_head_split(params):
    """adopt_prefix / ref_pages / unref_pages / truncate_to run on the
    placed pool without re-laying it out: k/v keep the head-dim split,
    accounting stays replicated (layout-oblivious by construction —
    they only touch refcount/table state or copy whole head rows)."""
    eng = make_engine(params, tp=2)
    pool = eng.pool
    slots = jnp.arange(eng.max_slots, dtype=jnp.int32)
    pool, ok = kv_pages.reserve_pages(
        pool, slots[:1], jnp.zeros((1,), jnp.int32),
        jnp.asarray([True]),
    )
    assert bool(ok)
    assert_tp_pool_layout(pool)
    page0 = int(np.asarray(pool["page_table"])[0, 0])
    pool = kv_pages.ref_pages(pool, jnp.asarray([page0]))
    assert_tp_pool_layout(pool)
    # adopt by reference into slot 1 + a COW copy of the same page
    adopt = jnp.full((1, eng.pages_per_seq), -1, jnp.int32)
    pool, ok = kv_pages.adopt_prefix(
        pool, slots[1:2], adopt.at[0, 0].set(page0),
        jnp.asarray([page0]),
    )
    assert bool(ok)
    assert_tp_pool_layout(pool)
    pool = kv_pages.truncate_to(
        pool, jnp.zeros((eng.max_slots,), jnp.int32),
        jnp.asarray([True] * eng.max_slots),
    )
    assert_tp_pool_layout(pool)
    pool = kv_pages.unref_pages(pool, jnp.asarray([page0]))
    assert_tp_pool_layout(pool)


# ------------------------------------------------- tp=1 is untouched


def test_tp_unset_keeps_the_exact_single_device_build(params, monkeypatch):
    """The no-regression half of the tentpole: with ``DDL25_SERVE_TP``
    unset the driver knobs resolve to tp=1, and a tp=1 engine holds the
    IDENTICAL ``_PROGRAM_CACHE`` executables the pre-PR-18 build
    compiled — object identity, hence byte-identical decode HLO."""
    from ddl25spring_tpu.serve import driver

    monkeypatch.delenv("DDL25_SERVE_TP", raising=False)
    monkeypatch.delenv("DDL25_SERVE_WEIGHT_STREAM", raising=False)
    knobs = driver.engine_knobs(smoke=True)
    assert knobs["tp"] == 1 and knobs["weight_stream"] is False

    eng = make_engine(params)
    assert eng.tp == 1 and eng.mesh is None
    tick, prefill, release = _compiled_programs(
        CFG, max_prompt_len=8, temperature=0.0, sentinel=None,
        donate=True,
    )
    assert eng._tick is tick
    assert eng._prefill is prefill
    assert eng._release is release

    monkeypatch.setenv("DDL25_SERVE_TP", "2")
    monkeypatch.setenv("DDL25_SERVE_WEIGHT_STREAM", "1")
    knobs = driver.engine_knobs(smoke=True)
    assert knobs["tp"] == 2 and knobs["weight_stream"] is True


def test_tp_constructor_validation(params):
    with pytest.raises(ValueError, match="tp=0"):
        make_engine(params, tp=0)
    with pytest.raises(ValueError, match="requires tp > 1"):
        make_engine(params, tp=1, weight_stream=True)
    with pytest.raises(ValueError, match="spec_k"):
        make_engine(params, tp=2, weight_stream=True, spec_k=2)
    with pytest.raises(ValueError, match="not divisible"):
        make_engine(params, tp=4)  # 2 heads over 4 chips
    with pytest.raises(ValueError, match="devices"):
        make_engine(params, tp=16)  # conftest fakes only 8


# ------------------------------------------------- residency shrink


def test_tp_mem_budget_divides_per_chip_only(params):
    """``mem_budget_bytes()`` (per-chip, the default) strictly shrinks
    at tp=2 and again under weight streaming, while ``per_chip=False``
    — the GLOBAL logical accounting memscope bands against — is
    identical across all three builds (sharding moves bytes, it never
    creates or destroys them)."""
    dense = make_engine(params)
    tp2 = make_engine(params, tp=2)
    ws = make_engine(params, tp=2, weight_stream=True)
    assert tp2.mem_budget_bytes() < dense.mem_budget_bytes()
    assert ws.mem_budget_bytes() < dense.mem_budget_bytes()
    assert ws.mem_budget_bytes() < tp2.mem_budget_bytes()
    g = dense.mem_budget_bytes(per_chip=False)
    assert tp2.mem_budget_bytes(per_chip=False) == g
    assert ws.mem_budget_bytes(per_chip=False) == g


# ------------------------------------------------- compile signatures


@pytest.mark.parametrize("name,ar_count,ar_bytes,kinds", [
    # per-chip variants: same program as serve-decode/serve-prefill,
    # tighter screws — 64 KiB budget + byte-exact all-reduce payload
    ("serve-decode-tp", 2 * 2, 1024, {"all-reduce"}),
    # one pass: 4 all-reduces of [2 x 8 positions, dmodel 16] fp32
    ("serve-prefill-tp", 2 * 2, 4096, {"all-reduce"}),
    # streaming decode adds EXACTLY n_layers x n_buckets = 2 gathers
    ("serve-decode-zero3stream", 2 * 2, 1024, {"all-reduce", "all-gather"}),
])
def test_tp_signature_pins(strategy_report, name, ar_count, ar_bytes, kinds):
    """The PR-18 signatures: all-reduce count UNCHANGED from the dense
    pins (tp divides KV bytes and FLOPs, never the collective count),
    payload byte-exact (positions x dmodel x fp32 partial sums), and
    only the streaming entry may gather — count-pinned, not waived."""
    r = strategy_report(name)
    assert r["signature_violations"] == []
    assert [f for f in r["findings"] if not f["waived"]] == []
    totals = r["collectives"]["totals"]
    assert set(totals) == kinds
    assert totals["all-reduce"]["count"] == ar_count
    assert totals["all-reduce"]["result_bytes"] == ar_bytes
    if "all-gather" in kinds:
        n_layers, n_buckets = 2, r["meta"]["stream_buckets"]
        assert totals["all-gather"]["count"] == n_layers * n_buckets
    assert r["sched"]["hazards"] == []
    assert r["lowered"] in ("decode_step", "prefill_step")
    assert r["meta"]["kv_sharded_dim"] == KV_POOL_HEAD_DIM


@pytest.mark.parametrize("name", [
    "serve-decode-tp", "serve-prefill-tp", "serve-decode-zero3stream",
])
def test_tp_describe_budgets_shrink(strategy_report, name):
    """THE perf gate: the same program compiled on one chip vs two —
    compile-time peak HBM strictly shrinks, the tp=2 peak fits a budget
    the one-chip build measurably cannot (64 KiB vs ~75 KiB measured on
    jax 0.9.0's CPU backend, streamed or resident), and the declared per-chip pool/param
    residency divides (shard_shape math, deterministic)."""
    from ddl25spring_tpu.obs import xla_analytics as xa

    r2 = strategy_report(name)  # default mesh (2,)
    r1 = cached_lowering(
        ("tp-shrink", name),
        lambda: xa.compile_strategy(name, mesh_sizes=(1,)),
    )
    assert r1["signature_violations"] == []
    peak1 = r1["memory"]["peak_hbm_bytes"]
    peak2 = r2["memory"]["peak_hbm_bytes"]
    assert peak2 < peak1, (peak2, peak1)
    budget = r2["expected"]["memory"]["max_peak_hbm_bytes"]
    assert peak2 <= budget < peak1, (peak2, budget, peak1)
    # per-chip residency: pure shape math, pinned exact (the 16 bytes of
    # the slots' last tokens, replicated, on each chip)
    assert r1["meta"]["pool_bytes_per_chip"] == 17588
    assert r2["meta"]["pool_bytes_per_chip"] == 8884
    assert r1["meta"]["param_bytes_per_chip"] == 41280
    assert r2["meta"]["param_bytes_per_chip"] == (
        24768 if name == "serve-decode-zero3stream" else 24896
    )


def test_tp_entries_share_the_dense_programs_wire(strategy_report):
    """serve-decode-tp IS serve-decode compiled at (2,) — identical
    collective totals (the -tp registry entry changes the budget and
    the meta, never the program), so the per-chip shrink comes with the
    wire traffic pinned unchanged."""
    for dense, tp in (
        ("serve-decode", "serve-decode-tp"),
        ("serve-prefill", "serve-prefill-tp"),
    ):
        assert (strategy_report(dense)["collectives"]["totals"]
                == strategy_report(tp)["collectives"]["totals"])


def test_stream_rows_contract_catches_replicated_blocks(strategy_report):
    """The H013 stream-rows walk (analysis/shard_flow.py): green on the
    real compiled streaming program, and a report whose params['blocks']
    leaves lost their dim-1 row split raises findings (the check is not
    vacuous)."""
    from ddl25spring_tpu.analysis import shard_flow

    r = strategy_report("serve-decode-zero3stream")
    name = "serve-decode-zero3stream"
    assert shard_flow.stream_rows_findings({name: r}) == []
    bad = copy.deepcopy(r)
    broke = 0
    for p in bad["entry_params"]:
        if "blocks" in (p.get("arg") or ""):
            p["sharding"] = None
            broke += 1
    assert broke > 0
    findings = shard_flow.stream_rows_findings({name: bad})
    assert len(findings) == broke
    assert all(f.rule == "H013" for f in findings)


# ------------------------------------------------- driver + tooling


def test_driver_tp_ab_gates_green(params):
    """driver.tp_ab_compare on a seeded trace: bitwise token equality
    over every compared request, a strict per-chip residency shrink —
    and tools/serve_report.check_tp passes the cell (then trips on each
    falsified verdict, so the gate is not vacuous)."""
    from ddl25spring_tpu.serve import driver
    from ddl25spring_tpu.serve.traffic import TrafficSpec, synth_trace
    from tools import serve_report

    knobs = driver.engine_knobs(smoke=True)
    knobs["tp"] = 2
    spec = TrafficSpec(
        seed=0, duration_s=2.0, rate_rps=6.0, profile="ramp",
        vocab_size=CFG.vocab_size,
    )
    trace = synth_trace(spec)
    assert len(trace) >= 4
    tab = driver.tp_ab_compare(params, CFG, trace, knobs)
    assert tab["tp"] == 2
    assert tab["tokens_match"] is True
    assert tab["compared_requests"] > 0
    assert tab["budget_shrunk"] is True
    assert (tab["sharded"]["mem_budget_bytes_per_chip"]
            < tab["dense"]["mem_budget_bytes_per_chip"])
    # both arms drained the identical workload
    assert (tab["sharded"]["generated_tokens"]
            == tab["dense"]["generated_tokens"])

    rec = {"tp_ab": tab}
    assert serve_report.check_tp([rec]) == []
    # each verdict gates independently
    assert serve_report.check_tp([{}])  # no cell at all
    shallow = dict(tab, budget_shrunk=False)
    assert any("budget_shrunk" in f
               for f in serve_report.check_tp([{"tp_ab": shallow}]))
    mism = dict(tab, tokens_match=False)
    assert any("token-for-token" in f
               for f in serve_report.check_tp([{"tp_ab": mism}]))
    vac = dict(tab, compared_requests=0)
    assert any("token-for-token" in f
               for f in serve_report.check_tp([{"tp_ab": vac}]))
    grew = dict(
        tab,
        sharded=dict(tab["sharded"], mem_budget_bytes_per_chip=10**9),
    )
    assert any("did not shrink" in f
               for f in serve_report.check_tp([{"tp_ab": grew}]))


def test_obs_report_renders_the_tp_lines():
    """The Serving section prints per-chip pool/param bytes, the tp
    line, and the tp A/B verdict — from the raw serve.json shape (the
    arms nested under sharded/dense)."""
    from ddl25spring_tpu.obs.report import format_report

    summary = {
        "run_dir": "/tmp/x",
        "serve": {
            "key": {"model": "tiny", "tp": 2},
            "requests": {"submitted": 2, "admitted": 2, "rejected": 0,
                         "rejected_by_reason": {}, "completed": 2},
            "ramp": {
                "admitted": 2, "rejected": 0, "completed": 2,
                "tokens_per_sec_per_chip": 10.0,
                "page_pool_peak_pages": 4, "page_pool_pages": 16,
                "page_pool_peak_occupancy": 0.25,
                "pool_bytes_per_chip": 8868,
                "param_bytes_per_chip": 24896,
                "tp": 2, "weight_stream": False,
                "queue_depth_max": 1, "pool_ok_failures": 0,
            },
            "tp_ab": {
                "tp": 2, "budget_s": 1.0, "tokens_match": True,
                "tp_tokens_at_budget": 8, "dense_tokens_at_budget": 8,
                "budget_shrunk": True, "compared_requests": 2,
                "sharded": {"mem_budget_bytes_per_chip": 33722},
                "dense": {"mem_budget_bytes_per_chip": 58810},
            },
        },
    }
    text = format_report(summary)
    assert "8.7 KiB/chip" in text
    assert "tp 2" in text and "params 24.3 KiB/chip" in text
    assert "tp A/B (tp=2)" in text
    assert "shrunk True" in text
    assert "32.9 vs 57.4 KiB" in text
