"""The serving scheduler measured where the work happens: after N
``ServeEngine.step`` calls the process-global rings (``obs.counters``)
hold N ``serve.step`` spans with their children inside them, and the
counts sampled at each pass equal the engine's own state — with telemetry
OFF, no profiler, on the tiny engine of ``tests/test_serve.py`` (whose
compiled programs these share)."""

from __future__ import annotations

import contextlib
import time

import jax
import numpy as np
import pytest

from ddl25spring_tpu import obs
from ddl25spring_tpu.models import llama
from ddl25spring_tpu.serve.engine import ServeEngine, pass_shapes
from ddl25spring_tpu.utils.config import LlamaConfig

CFG = LlamaConfig(
    vocab_size=64, dmodel=16, num_heads=2, n_layers=2, ctx_size=32,
    dtype="float32",
)
CHILDREN = ("serve.release", "serve.admit", "serve.prefill",
            "serve.decode_tick", "serve.emit")


@pytest.fixture(scope="module")
def params():
    return llama.init_llama_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(autouse=True)
def _clean_rings():
    obs.enable(False)
    obs.counters.reset()
    yield
    obs.counters.reset()


def make_engine(params, **kw):
    kw.setdefault("page_len", 4)
    kw.setdefault("n_pages", 16)
    kw.setdefault("max_slots", 2)
    kw.setdefault("pages_per_seq", 4)
    kw.setdefault("prefill_batch", 1)
    kw.setdefault("max_prompt_len", 8)
    kw.setdefault("clock", "virtual")
    kw.setdefault("trace_label", None)
    return ServeEngine(params, CFG, **kw)


def everything(name):
    return obs.counters.window(name, 0.0, time.perf_counter())


@contextlib.contextmanager
def span_stats():
    """Telemetry on under a fresh Chrome recorder, which keeps each
    span's stats; yields ``stats(name)``, the stats of the spans of that
    name so far, in order."""
    rec = obs.SpanRecorder()
    old = obs.set_recorder(rec)
    try:
        with obs.scoped(True):
            yield lambda name: [
                e.get("args", {}) for e in rec.to_chrome_trace()["traceEvents"]
                if e["name"] == name
            ]
    finally:
        obs.set_recorder(old)


def run_steps(eng, prompts, max_new=4, before_tick=None):
    """Submit ``prompts``, step until drained (plus the flush step);
    ``before_tick(eng)`` runs right before every decode pass."""
    if before_tick is not None:
        inner = eng._run_decode_tick

        def tick():
            before_tick(eng)
            inner()

        eng._run_decode_tick = tick
    for p in prompts:
        assert eng.submit(eng.make_request(p, max_new)) is None
    steps = 0
    while eng.queue or any(s is not None for s in eng.slots):
        eng.step()
        steps += 1
        assert steps < 200
    eng.step()  # the flush of the last completions
    return steps + 1


PROMPTS = [[5, 9, 11, 3], [7, 2, 2, 8, 1, 4], [3, 3], [9, 8, 7, 6, 5]]


def test_n_steps_leave_n_step_spans_with_their_children_inside(params):
    eng = make_engine(params)
    n = run_steps(eng, PROMPTS)
    steps = everything("serve.step")
    assert len(steps) == n
    assert steps == sorted(steps)  # written in order, none overlapping
    assert all(a[0] + a[1] <= b[0] for a, b in zip(steps, steps[1:]))
    for child in CHILDREN:
        spans = everything(child)
        assert spans, child
        for t, d in spans:  # each lies inside exactly one step
            assert sum(s <= t and t + d <= s + sd for s, sd in steps) == 1, child
    assert len(everything("serve.admit")) == n          # one a step
    assert len(everything("serve.prefill")) == eng._prefills == len(PROMPTS)
    assert len(everything("serve.decode_tick")) == eng._ticks
    # a token loop follows every pass, prefill or tick
    assert len(everything("serve.emit")) == eng._prefills + eng._ticks
    # the harness's view and the program's agree on the tick's wall: the
    # engine's two stamps stand around the span and inside the step, so the
    # three lengths nest whatever the machine does between two of them
    ticks = everything("serve.decode_tick")
    assert len(ticks) == len(eng.tick_wall_s)
    for (t, d), wall in zip(ticks, eng.tick_wall_s):
        step = next(sd for s, sd in steps if s <= t and t + d <= s + sd)
        assert 0 < d <= wall <= step


def test_tick_counts_equal_the_engines_own_state_at_each_tick(params):
    seen = []

    def before_tick(eng):
        pool = jax.device_get(
            {k: eng.pool[k] for k in ("active", "seq_len", "free")}
        )
        seen.append({
            "active": sum(r is not None for r in eng.slots),
            "queue": len(eng.queue),
            # the device's own view: a pass attends to seq_len + 1 positions
            # of each active slot, its own write included
            "live": int(((pool["seq_len"] + 1) * pool["active"]).sum()),
            "device_active": int(pool["active"].sum()),
            "pages": int((~pool["free"]).sum()),
        })

    eng = make_engine(params)
    # telemetry on is also what makes the engine count ``pages_used``
    with span_stats() as of:
        run_steps(eng, PROMPTS, before_tick=before_tick)
        stats = of("serve.decode_tick")
    assert len(seen) == len(stats) == eng._ticks > 4

    def values(name):
        return [int(v) for _, v in everything(name)]

    # the two counts a reader windows: rings, one stamp a tick
    assert values("serve.active_slots") == [s["active"] for s in seen]
    assert values("serve.active_slots") == [s["device_active"] for s in seen]
    assert values("serve.kv_live_positions") == [s["live"] for s in seen]
    assert ([t for t, _ in everything("serve.active_slots")]
            == [t for t, _ in everything("serve.kv_live_positions")])
    assert max(s["active"] for s in seen) == eng.max_slots
    # every count rides on the tick's span
    assert [a["active"] for a in stats] == [s["active"] for s in seen]
    assert [a["queue"] for a in stats] == [s["queue"] for s in seen]
    assert [a["kv_live_positions"] for a in stats] == [s["live"] for s in seen]
    assert [a["pages_used"] for a in stats] == [s["pages"] for s in seen]
    gathered = eng.max_slots * eng.pages_per_seq * eng.page_len
    assert {a["kv_gathered_positions"] for a in stats} == {gathered}
    # and only what a reader windows has a ring
    for name in ("serve.queue_depth", "serve.pages_used",
                 "serve.kv_gathered_positions", "serve.prefill.rows"):
        assert not everything(name), name


def test_pages_are_counted_for_a_tick_only_where_a_trace_shows_them(params):
    assert not obs.spans.watched()
    with obs.scoped(True):
        assert obs.spans.watched()
    eng = make_engine(params)
    assert "pages_used" not in eng._tick_counts()
    with obs.scoped(True):
        assert eng._tick_counts()["pages_used"] == eng._host_pages_used() == 0


def test_prefill_counts_are_the_admitted_prompts_less_matched_prefixes(params):
    eng = make_engine(params, prefix_cache=True, prefill_batch=2, n_pages=32,
                      max_prompt_len=8)
    shared = [5, 9, 11, 3, 7, 2]  # one full page of 4 is cacheable
    prompts = [shared + [1, 2], shared + [3, 4], shared + [5], [8, 8, 8]]
    with span_stats() as of:
        run_steps(eng, prompts[:1])
        run_steps(eng, prompts[1:])
        stats = of("serve.prefill")
    assert eng.prefix.hits >= 2
    tokens = [int(v) for _, v in everything("serve.prefill.prompt_tokens")]
    scanned = [int(v) for _, v in everything("serve.prefill.scanned_positions")]
    assert len(tokens) == len(scanned) == len(stats) == eng._prefills
    # the rings hold what the spans carry
    assert tokens == [a["prompt_tokens"] for a in stats]
    assert scanned == [a["scanned_positions"] for a in stats]
    rows = [a["rows"] for a in stats]
    assert sum(rows) == eng.admitted == len(prompts)
    assert sum(tokens) == sum(map(len, prompts)) - eng.prefix.hit_tokens
    # a pass computes the positions of its shape, the ladder's cheapest that
    # holds its rows and its longest unmatched suffix, and says so
    shapes = pass_shapes(eng.prefill_batch, eng.max_prompt_len)
    ran = [(a["pass_rows"], a["width"]) for a in stats]
    assert all(shape in shapes for shape in ran)
    assert scanned == [r * w for r, w in ran]
    assert all(a["rows"] <= a["pass_rows"] for a in stats)
    # cold [6+2] alone rides one row of the full width; the hits' suffixes
    # (4 and 3 after the cached page of 4) two rows, which the ladder has
    # at the full width only; the cold [8, 8, 8], admitted alone behind
    # them, one row of half
    assert shapes == ((1, 4), (1, 8), (2, 8))
    assert ran == [(1, 8), (2, 8), (1, 4)] and rows == [1, 2, 1]
    # and what a hit skips is its matched positions, row for row
    assert eng.prefill_tokens_saved == eng.prefix.hit_tokens > 0


def test_a_labelled_engine_keys_its_names_and_an_unlabelled_one_does_not(params):
    ramp = make_engine(params, trace_label="ramp")
    bare = make_engine(params, trace_label=None)
    n_ramp = run_steps(ramp, PROMPTS[:2])
    n_bare = run_steps(bare, PROMPTS[:3])
    assert len(everything("serve.step@ramp")) == n_ramp
    assert len(everything("serve.step")) == n_bare
    assert len(everything("serve.active_slots@ramp")) == ramp._ticks
    assert len(everything("serve.active_slots")) == bare._ticks
    assert len(everything("serve.prefill.prompt_tokens@ramp")) == ramp._prefills


def test_a_speculative_round_is_spanned_and_counted_like_a_tick(params):
    eng = make_engine(params, spec_k=2, n_pages=32)
    n = run_steps(eng, PROMPTS[:2], max_new=6)
    steps = everything("serve.step")
    assert len(steps) == n
    rounds = everything("serve.draft")
    assert len(rounds) == len(everything("serve.verify")) == eng._spec_rounds > 0
    assert len(everything("serve.active_slots")) == eng._spec_rounds
    assert not everything("serve.decode_tick")
    for t, d in rounds + everything("serve.verify") + everything("serve.draft_prefill"):
        assert sum(s <= t and t + d <= s + sd for s, sd in steps) == 1


def test_admit_counts_its_evictions_as_accounting_programs(params):
    """``account_ops``, a late stat of the spans around the accounting
    dispatches: on ``serve.admit`` one program an eviction (two in a step
    that admits two requests against a full cache), none where nothing is
    evicted; on ``serve.emit`` the cache's claim after a prompt pass."""
    eng = make_engine(params, prefix_cache=True, n_pages=8, prefill_batch=2)
    evictions = []  # a step's evictions, in the order of the admit spans
    inner = eng._evict_for

    def evict_for(shortfall, protect):
        got = inner(shortfall, protect)
        evictions[-1] += bool(got)
        return got

    eng._evict_for = evict_for
    inner_step = eng.step

    def step():
        evictions.append(0)
        return inner_step()

    eng.step = step
    rng = np.random.default_rng(0)

    def prompt():
        return rng.integers(1, 64, 8).tolist()

    with span_stats() as of:
        for _ in range(4):  # distinct prompts, one at a time: the cache fills
            run_steps(eng, [prompt()], max_new=2)
        assert eng.n_pages - eng.prefix.held_pages < 3
        # two requests at once, 3 pages each, where fewer are uncommitted
        run_steps(eng, [prompt(), prompt()], max_new=4)
        admits = [a["account_ops"] for a in of("serve.admit")]
        emits = [a.get("account_ops") for a in of("serve.emit")]
        releases = [a["account_ops"] for a in of("serve.release")]
    assert admits == evictions
    assert 2 in admits and 0 in admits
    assert set(releases) == {1}
    # every prompt pass claims its pages for the cache; the token loop after
    # a tick dispatches nothing and carries no such stat
    assert emits.count(1) == eng._prefills and emits.count(None) == eng._ticks
    emits = [n for n in emits if n]
    assert eng._account_ops == sum(admits + emits + releases)


def test_release_and_rollback_count_one_program_a_pool(params):
    eng = make_engine(params, spec_k=2, n_pages=32)
    with span_stats() as of:
        run_steps(eng, PROMPTS[:2], max_new=6)
        releases = [a["account_ops"] for a in of("serve.release")]
        emits = [a["account_ops"] for a in of("serve.emit")]
        admits = [a["account_ops"] for a in of("serve.admit")]
    assert releases and set(releases) == {2}  # the target's pool, the drafter's
    # a round rolls both pools back; no cache, so a prompt pass claims nothing
    assert sorted(emits) == [0] * eng._prefills + [2] * eng._spec_rounds
    assert set(admits) == {0}


def test_spans_change_no_token(params):
    """Greedy streams with the rings filling equal the dense oracle's, as
    the existing bitwise pins demand; here: the same streams from two
    engines, one of them stepped under an open profiler session."""
    import glob
    import tempfile

    from ddl25spring_tpu.models import decode as dm
    import jax.numpy as jnp

    def serve(profile: bool):
        eng = make_engine(params)
        d = tempfile.mkdtemp()
        if profile:
            jax.profiler.start_trace(d)
        try:
            run_steps(eng, PROMPTS)
        finally:
            if profile:
                jax.profiler.stop_trace()
        found = glob.glob(d + "/**/*.xplane.pb", recursive=True)
        return {r.rid: r.tokens for r in eng.done}, found

    plain, _ = serve(False)
    traced, found = serve(True)
    assert plain == traced
    oracle = dm.generate(params, jnp.asarray([PROMPTS[0]], jnp.int32), CFG,
                         max_new_tokens=4, temperature=0.0)
    assert plain[0] == [int(t) for t in np.asarray(oracle)[0]]
    # and the session saw the scheduler's spans, with no DDL25_OBS
    names = {
        e.name
        for plane in jax.profiler.ProfileData.from_file(found[0]).planes
        if plane.name == "/host:CPU"
        for line in plane.lines for e in line.events
    }
    assert {"serve.step", *CHILDREN} <= names
