"""The ``mistral4`` family at small sizes on the CPU, in float32: the MLA
block's two associations, the expert layer that drops nothing, the share of
a four-chip deployment, and the whole model through the paged engine, each
against the ONE plain reference (``benchmark/reference_mistral4.py``, loaded
with its family file through ``benchmark.run.load_module``)."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench_run
from ddl25spring_tpu.models import mistral4 as m4
from ddl25spring_tpu.serve import kv_pages
from ddl25spring_tpu.serve.engine import ServeEngine

BENCH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark")
FAMILY = bench_run.load_module(BENCH, "families", "mistral4")
REF = FAMILY.reference
PUBLISHED = bench_run.load_json(
    os.path.join(BENCH, "configs", "mistral-small-4-ep4.json")
)
PAGE = 4


def tiny_config(held=4, offset=0, router=8, layers=6, **more):
    """The published configuration with every width shrunk: same keys, same
    structure (YaRN, top-k of a wider router, a share of the experts), and
    the depth the cell serves, through which rounding compounds."""
    config = dict(
        PUBLISHED, hidden_size=32, num_attention_heads=4, num_key_value_heads=4,
        q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=4, qk_rope_head_dim=4,
        qk_head_dim=8, v_head_dim=8, head_dim=8, moe_intermediate_size=16,
        n_routed_experts=held, num_experts_per_tok=2, num_hidden_layers=layers,
        vocab_size=64, run={"dtype": "float32"},
        published=dict(PUBLISHED["published"], n_routed_experts=router),
        deployment=dict(PUBLISHED["deployment"], expert_offset=offset),
    )
    config.update(more)
    return config


@pytest.fixture(scope="module")
def model():
    cfg = FAMILY.build(tiny_config())
    return cfg, FAMILY.init_params(cfg, 3)


def layer_of(params, li):
    return jax.tree.map(lambda a: a[li], params["blocks"])


def empty_planes(cfg, pages):
    return kv_pages.planes(kv_pages.init_page_pool(
        cfg, n_pages=pages, page_len=PAGE, max_slots=1, pages_per_seq=pages))


def test_published_widths_build_and_refusals_hold():
    cfg = FAMILY.build(PUBLISHED)
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.q_lora_rank,
            cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.moe_intermediate_size) == (
                4096, 32, 1024, 256, 64, 64, 128, 2048)
    assert (cfg.n_routed_experts, cfg.n_held, cfg.num_experts_per_tok,
            cfg.vocab_size) == (128, 32, 4, 32768)
    assert cfg.n_layers >= 4
    model = cfg.paged_model()
    assert dict(model.planes) == {"ckv": (256,), "kpe": (64,)}  # 320 a position
    with pytest.raises(ValueError, match="n_group"):
        FAMILY.build(dict(PUBLISHED, n_group=2))
    rope = PUBLISHED["rope_parameters"]
    with pytest.raises(ValueError, match="cos and sin unscaled"):
        FAMILY.build(dict(PUBLISHED, rope_parameters=dict(rope, mscale=0.5)))
    with pytest.raises(ValueError, match="YaRN"):
        FAMILY.build(dict(PUBLISHED, rope_parameters=dict(rope, factor=1.0)))
    with pytest.raises(NotImplementedError, match="served only"):
        FAMILY.train_flops_per_token(cfg)
    np.testing.assert_allclose(
        m4.yarn_inv_freq(cfg), REF.yarn_inv_freq(FAMILY._w(cfg)), rtol=1e-6)


def test_mla_associations_agree_with_the_reference_and_each_other(model):
    """One sequence through the latent planes: all positions in one pass
    (keys and values projected after the gather), and one position at a
    time (the absorbed association), against the reference's attention."""
    cfg, params = model
    S, pages = 12, 3
    p = layer_of(params, 1)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, S, cfg.hidden_size))
    rows = jnp.arange(pages)[None, :]
    pos = jnp.arange(S)[None, :]

    @jax.jit  # traced twice: once a width (T = S, then T = 1)
    def attend(x, planes, at):
        cos, sin = m4.rope_tables(at, cfg)
        return m4.mla_attention(
            p, x, planes, 1, rows, at // PAGE, at % PAGE, at, cos, sin, cfg)

    batch, planes = attend(x, empty_planes(cfg, pages), pos)
    assert {k: v.shape[3:] for k, v in planes.items()} == {
        "ckv": (cfg.kv_lora_rank,), "kpe": (cfg.qk_rope_head_dim,)}
    planes, steps = empty_planes(cfg, pages), []
    for t in range(S):
        out, planes = attend(x[:, t:t + 1], planes, pos[:, t:t + 1])
        steps.append(out)
    absorbed = jnp.concatenate(steps, axis=1)
    with jax.default_matmul_precision("highest"):
        ref = REF.attention(p, x[0], FAMILY._w(cfg))
    np.testing.assert_allclose(batch[0], ref, atol=2e-5)
    np.testing.assert_allclose(absorbed[0], ref, atol=2e-5)
    np.testing.assert_allclose(absorbed, batch, atol=2e-5)


def test_expert_layer_matches_the_reference_under_uniform_routing(model):
    cfg, params = model
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 9, cfg.hidden_size))
    live = jnp.ones((2, 9), bool)
    got, load = m4.moe_ffn(layer_of(params, 0), x, live, params["experts"], 0, cfg)
    with jax.default_matmul_precision("highest"):
        ref, _gap = REF.experts(
            layer_of(params, 0), params["experts"], 0, x.reshape(18, -1),
            FAMILY._w(cfg), FAMILY._held(cfg))
    np.testing.assert_allclose(got.reshape(18, -1), ref, atol=2e-5)
    # 18 rows x top-2 over 8 experts of which 4 are held: about half land here
    assert 0 < int(load.sum()) < 36 and load.shape == (cfg.n_held,)
    # rows that are not live count nothing and cost nothing
    _, none = m4.moe_ffn(layer_of(params, 0), x, ~live, params["experts"], 0, cfg)
    assert int(none.sum()) == 0


@pytest.mark.parametrize("case", ["all_on_one_held_expert", "none_held"])
def test_expert_layer_drops_nothing_at_any_imbalance(model, case):
    """No capacity: every assignment on ONE held expert is legal and exact;
    none held gives exactly zero, and no NaN from the unvisited rows."""
    cfg, params = model
    N, k, e = 40, cfg.num_experts_per_tok, 2
    h2 = jax.random.normal(jax.random.PRNGKey(4), (N, cfg.hidden_size))
    weights = jax.random.uniform(jax.random.PRNGKey(5), (N, k))
    chosen = e if case == "all_on_one_held_expert" else cfg.n_held + 1
    y, load = m4.routed_experts(
        h2, jnp.full((N, k), chosen, jnp.int32), weights, jnp.ones(N, bool),
        params["experts"], 1, cfg)
    assert np.isfinite(np.asarray(y)).all()
    if case == "none_held":
        assert int(load.sum()) == 0 and float(jnp.abs(y).max()) == 0.0
        return
    assert load.tolist() == [0, 0, N * k, 0]
    with jax.default_matmul_precision("highest"):
        one = REF.swiglu(h2, *(params["experts"][n][1, e]
                               for n in ("w_gate", "w_up", "w_down")))
    np.testing.assert_allclose(y, weights.sum(-1, keepdims=True) * one, atol=2e-5)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """What each of four chips computes for its 2 of 8 experts, the shared
    expert counted once, is the uncut reference's whole layer."""
    whole = FAMILY.build(tiny_config(held=8))
    params = FAMILY.init_params(whole, 11)
    p, w = layer_of(params, 0), FAMILY._w(whole)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 16, whole.hidden_size))
    with jax.default_matmul_precision("highest"):
        uncut, _ = REF.experts(p, params["experts"], 0, x[0], w, (0, 8))
        h2 = REF.rms_norm(x[0], p["ln2"], w["rms_norm_eps"])
        shared = REF.swiglu(h2, p["ws_gate"], p["ws_up"], p["ws_down"])
    routed = jnp.zeros_like(uncut)
    for chip in range(4):
        cfg = dataclasses.replace(whole, experts_held=2, expert_offset=2 * chip)
        stacks = {n: a[:, 2 * chip:2 * chip + 2] for n, a in params["experts"].items()}
        out, load = jax.jit(lambda stacks, cfg=cfg: m4.moe_ffn(
            p, x, jnp.ones((1, 16), bool), stacks, 0, cfg))(stacks)
        assert 0 < int(load.sum()) <= 2 * 16
        routed = routed + (out[0] - x[0] - shared)
    np.testing.assert_allclose(x[0] + routed + shared, uncut, atol=3e-5)


@pytest.fixture(scope="module")
def served(model):
    """Prefill then 32 decode steps through the latent pages of the real
    engine, with a radix hit that ends inside a page (copy-on-write)."""
    cfg, params = model
    eng = ServeEngine(
        params, cfg, page_len=PAGE, n_pages=96, max_slots=4, pages_per_seq=12,
        prefill_batch=2, max_prompt_len=12, clock="virtual", prefix_cache=True,
        logit_probe=16)
    eng.warmup()
    prefix = [5, 9, 3, 7, 11, 2]  # 6 tokens: one full page and half of one
    first = eng.make_request(prefix, 33)  # leaves a node for the partial page
    assert eng.submit(first) is None
    while first.done_t is None:
        eng.step()
    for tail in ([17], [19, 23], [29, 31, 37]):
        assert eng.submit(eng.make_request(prefix + tail, 33)) is None
    while not eng.drained:
        eng.step()
    # each hit: the full page by reference, the partial one copied on write
    assert eng.prefix.hits == 3 and eng.prefix.hit_tokens == 3 * len(prefix)
    assert eng.mem_leak_check()["ok"] and eng.pool_ok_failures == 0
    assert set(kv_pages.planes(eng.pool)) == {"ckv", "kpe"}
    return eng, [(r.prompt, r.tokens) for r in eng.done]


def test_prefill_then_decode_through_latent_pages_matches_the_reference(model, served):
    cfg, params = model
    eng, done = served
    assert all(len(tokens) == 33 for _, tokens in done)
    out = FAMILY.check_served(cfg, params, done, pad_to=eng.max_seq_len)
    assert out["ok"], out
    assert out["tokens_checked"] == 4 * 33 and out["probe_ids"] == 16
    assert out["logit_rel_err"] < 1e-4 and out["logit_rel_err_near_tie"] < 1e-4
    assert out["worst_margin"] == 0.0  # float32: the reference's own argmax
    for key in ("near_tie_share", "logit_rel_err_p50", "margin_mean"):
        assert f"{key} " in out["eps"]  # every reading beside its limit


def test_engine_counts_the_held_experts_load(served):
    from ddl25spring_tpu.obs.counters import counters

    rings = {name: counters.window(f"serve.moe.{name}", 0.0, float("inf"))
             for name in ("assignments_here", "experts_hit", "load_max")}
    n = len(rings["assignments_here"])
    assert n > 32 and all(len(v) == n for v in rings.values())
    for (_, here), (_, hit), (_, top) in zip(*rings.values()):
        # at most 2 of a position's choices, 6 layers, 4 rows x 12 positions
        assert 0 <= top <= here <= 2 * 6 * 48 and hit <= 6 * 4
    # what no reader windows is a stat of the pass's span and has no ring
    assert not counters.window("serve.moe.assignments", 0.0, float("inf"))


def to_8_bits(params):
    """``params`` with the experts' weights (routed and shared) at e4m3's 3
    mantissa bits."""
    def low(a):
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=3)
    out = dict(params, experts=jax.tree.map(low, params["experts"]))
    out["blocks"] = {k: low(v) if k.startswith("ws_") else v
                     for k, v in params["blocks"].items()}
    return out


def serve_whole(cfg, params):
    eng = ServeEngine(
        params, cfg, page_len=PAGE, n_pages=64, max_slots=4, pages_per_seq=12,
        prefill_batch=4, max_prompt_len=12, clock="virtual", logit_probe=16)
    rng = np.random.default_rng(0)
    for n in (9, 10, 11, 12):
        assert eng.submit(eng.make_request(rng.integers(1, 64, n).tolist(), 36)) is None
    while not eng.drained:
        eng.step()
    return eng.max_seq_len, [(r.prompt, r.tokens) for r in eng.done]


@pytest.fixture(scope="module")
def whole_served():
    """A chip that holds ALL 8 experts of an 8-wide router, so that the
    experts are as large a part of its result as they can be."""
    cfg = FAMILY.build(tiny_config(held=8))
    params = FAMILY.init_params(cfg, 5)
    return cfg, params, *serve_whole(cfg, params)


@pytest.mark.parametrize("control", [
    "none", "8-bit experts", "8-bit experts in the engine", "a dropped layer"])
def test_the_tolerances_refuse_lower_precision_and_missing_work(whole_served, control):
    """The family's limits at a small size, on the logits the ENGINE's own
    passes kept: against a reference whose expert weights (routed and
    shared) were rounded to 8 bits the engine reads as not correct, by the
    limit made for that, and so does an engine that serves the rounded
    weights against the reference with the true ones; against a reference
    that skips a layer's attention, too; against its own weights it is
    correct."""
    cfg, params, pad_to, done = whole_served
    other = None
    if control == "8-bit experts":
        other = to_8_bits(params)
    elif control == "8-bit experts in the engine":
        pad_to, done = serve_whole(cfg, to_8_bits(params))
    elif control == "a dropped layer":
        other = dict(params, blocks=dict(
            params["blocks"], wo=params["blocks"]["wo"].at[1].set(0.0)))
    out = FAMILY.check_served(cfg, params, done, pad_to=pad_to,
                              reference_params=other)
    assert out["ok"] == (control == "none"), out
    if control.startswith("8-bit experts"):
        assert out["logit_rel_err_p50"] > out["limits"]["logit_rel_err_p50"]


def test_a_request_without_probed_rows_is_refused(model):
    cfg, params = model
    with pytest.raises(ValueError, match="logit_probe"):
        FAMILY.check_served(cfg, params, [([1, 2, 3], [4, 5])], pad_to=16)


@pytest.mark.parametrize("sizes", [[3, 0, 130, 7], [0, 0, 0, 0], [0, 256, 0, 0]])
def test_moe_gmm_kernel_against_ragged_dot(sizes):
    """The kernel itself (interpret mode off the TPU): groups that share a
    row tile, empty groups, one group with every row, rows of no group."""
    from ddl25spring_tpu.ops.moe_gmm import moe_gmm

    lhs = jax.random.normal(jax.random.PRNGKey(0), (256, 64))
    rhs = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 64, 32))
    sizes = jnp.asarray(sizes, jnp.int32)
    out = moe_gmm(lhs, rhs, sizes, 1)
    ref = jax.lax.ragged_dot(lhs, rhs[1], sizes)
    in_group = np.arange(256)[:, None] < int(sizes.sum())
    np.testing.assert_allclose(out, np.where(in_group, ref, 0.0), atol=1e-4)


def test_moe_gmm_counts_what_the_algorithm_needs():
    flops, nbytes = FAMILY.moe_gmm_flops_bytes(64, 28)
    assert flops == 2 * 3 * 4096 * 2048 * 64
    assert nbytes == 2 * (3 * 4096 * 2048 * 28 + 2 * 4096 * 64)
