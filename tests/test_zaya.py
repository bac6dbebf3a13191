"""The ``zaya`` family at small sizes on the CPU, in float32: compressed
convolutional attention piece by piece and whole, in a prompt pass and token
by token; the float32 router with its carry across layers; the expert layer
at top-1 that drops nothing and whose shares add up; the tied table; the
whole model through the paged engine's pages AND slot state with ragged
rows; each against the ONE plain reference (``benchmark/reference_zaya.py``,
explicit shifts, loaded with its family file through
``benchmark.run.load_module``)."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench_run
from ddl25spring_tpu.models import zaya as zy
from ddl25spring_tpu.models.routed_experts import routed_experts
from ddl25spring_tpu.serve import kv_pages
from ddl25spring_tpu.serve.engine import (
    ServeEngine,
    make_decode_tick,
    make_prefill,
)
from ddl25spring_tpu.serve.paged_model import paged_model

BENCH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark")
FAMILY = bench_run.load_module(BENCH, "families", "zaya")
REF = FAMILY.reference
PUBLISHED = bench_run.load_json(os.path.join(BENCH, "configs", "zaya1-8b-pp2.json"))
PAGE = 4


def tiny_config(experts=4, held=None, offset=0, layers=3, **more):
    """The published configuration with every width shrunk: same keys, same
    structure (4 query heads a KV head, two KV heads so that the value
    halves are heads, rotary on half a head, both kernels of two taps, a
    narrow router state), three layers deep so that the carry crosses two."""
    config = dict(
        PUBLISHED, hidden_size=32, num_attention_heads=8, num_key_value_heads=2,
        head_dim=8, moe_intermediate_size=16, num_experts=experts,
        router_hidden_size=12, num_hidden_layers=layers, vocab_size=64,
        run={"dtype": "float32", "high_prec": "float32"},
        deployment=dict(PUBLISHED["deployment"], expert_offset=offset,
                        experts_held=experts if held is None else held),
    )
    config.update(more)
    return config


def seeded(cfg, seed):
    """The family's weights with every leaf it seeds at a neutral value
    DRAWN (norm scales, ``tau``, the balancing bias), so that each is held
    to the reference."""
    params = FAMILY.init_params(cfg, seed)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 16))
    blocks = dict(params["blocks"])
    for name in ("ln1", "ln2", "r_ln", "tau", "r_bias"):
        blocks[name] = blocks[name] + 0.3 * jax.random.normal(
            next(keys), blocks[name].shape)
    ln_f = params["ln_f"] + 0.3 * jax.random.normal(next(keys), params["ln_f"].shape)
    return {**params, "blocks": blocks, "ln_f": ln_f}


@pytest.fixture(scope="module", autouse=True)
def _rings_are_this_files():
    """The program's rings are global to the process: other files window
    them from time 0, so this one leaves none behind, and starts from none."""
    from ddl25spring_tpu import obs

    obs.counters.reset()
    yield
    obs.counters.reset()


@pytest.fixture(scope="module")
def model():
    cfg = FAMILY.build(tiny_config())
    return cfg, seeded(cfg, 3)


def f32(tree, *index):
    return jax.tree.map(lambda a: a[index].astype(jnp.float32), tree)


def empty_cache(cfg, slots, pages=3):
    return kv_pages.contents(kv_pages.init_page_pool(
        cfg, n_pages=pages * slots, page_len=PAGE, max_slots=slots,
        pages_per_seq=pages))


# ------------------------------------------------- configuration and seam


def test_published_widths_build_and_the_file_states_its_cut():
    cfg = FAMILY.build(PUBLISHED)
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim, cfg.rotary_dim, cfg.cca_time0, cfg.cca_time1,
            cfg.conv_channels, cfg.conv_tail, cfg.value_half,
            cfg.moe_intermediate_size, cfg.router_hidden_size) == (
                2048, 8, 2, 128, 64, 2, 2, 1280, 2, 128, 2048, 256)
    assert (cfg.num_experts, cfg.n_held, cfg.expert_offset,
            cfg.num_experts_per_tok, cfg.vocab_size) == (16, 16, 0, 1, 262272)
    assert (cfg.rope_theta, cfg.rms_norm_eps, cfg.dtype, cfg.high_prec) == (
        5e6, 1e-5, "bfloat16", "float32")
    assert cfg.n_layers == 20 >= 4
    assert PUBLISHED["reduced"] == ["num_hidden_layers"]
    assert PUBLISHED["published"] == {"num_hidden_layers": 40}
    assert PUBLISHED["tie_word_embeddings"] is True
    assert PUBLISHED["deployment"]["pipeline_stages"] == 2
    assert PUBLISHED["deployment"]["chips_sharing_each_layer"] == 1
    assert len(PUBLISHED["assumed"]) >= 10
    assert any("mixture-of-depths" in d for d in PUBLISHED["departures"])
    m = cfg.paged_model()
    # pages AND a slot of state in every layer, and a carry between them
    # a position's k and v are one row of 2 heads x 128 each: 1 kB a layer
    assert dict(m.planes) == {"k": (256,), "v": (256,)}
    assert dict(m.slot_state) == {
        "conv": ((2, 1280), "bfloat16"), "vprev": ((128,), "bfloat16")}
    assert (m.layers_of("k"), m.state_layers, m.n_units) == (20, 20, 20)
    r = jax.eval_shape(m.carry, jax.ShapeDtypeStruct((64, 1, 2048), jnp.bfloat16))
    assert (r.shape, r.dtype) == ((64, 1, 256), jnp.float32)


@pytest.mark.parametrize("key, value", [
    ("tie_word_embeddings", False), ("sliding_window", 4096),
    ("hidden_act", "gelu"), ("attention_bias", True),
    ("num_experts_per_tok", 2),
    ("layer_types", ["hybrid_sliding"] * 40),
])
def test_the_family_refuses_what_the_program_cannot_state(key, value):
    with pytest.raises(ValueError, match=key):
        FAMILY.build(dict(PUBLISHED, **{key: value}))


def test_the_family_is_served_only():
    cfg = FAMILY.build(tiny_config())
    with pytest.raises(NotImplementedError, match="served only"):
        FAMILY.train_flops_per_token(cfg)
    with pytest.raises(ValueError, match="not among the router's"):
        FAMILY.build(tiny_config(experts=4, held=4, offset=2))


def test_pool_holds_pages_and_a_slot_of_state_in_every_layer(model):
    cfg, _ = model
    pool = kv_pages.init_page_pool(
        cfg, n_pages=5, page_len=PAGE, max_slots=3, pages_per_seq=4)
    assert {k: v.shape for k, v in kv_pages.planes(pool).items()} == {
        "k": (6, 3, PAGE, 16), "v": (6, 3, PAGE, 16)}
    assert {k: v.shape for k, v in kv_pages.slot_state(pool).items()} == {
        "conv": (3, 3, 2, 80), "vprev": (3, 3, 8)}
    assert kv_pages.pool_geometry(pool)["slot_state_bytes"] == 3 * (2 * 80 + 8) * 4


# the carried leaves of a tick's scan: x, the cache's, and the carry if any
FAMILIES = {"dense": 3, "latent": 3, "hybrid": 5, "zaya": 6}


def _other_model(name):
    if name == "zaya":
        cfg = FAMILY.build(tiny_config())
        return cfg, FAMILY.init_params
    if name == "dense":
        from ddl25spring_tpu.models import llama
        from ddl25spring_tpu.utils.config import LlamaConfig

        cfg = LlamaConfig(vocab_size=64, dmodel=32, num_heads=4, n_layers=2,
                          ctx_size=32, dtype="float32")
        return cfg, lambda cfg, seed: llama.init_llama_params(
            jax.random.PRNGKey(seed), cfg)
    file, config = {"latent": ("mistral4", "mistral-small-4-ep4"),
                    "hybrid": ("qwen3next", "qwen3-next-80b-ep4")}[name]
    family = bench_run.load_module(BENCH, "families", file)
    published = bench_run.load_json(os.path.join(BENCH, "configs", f"{config}.json"))
    small = dict(
        published, hidden_size=32, num_attention_heads=4, head_dim=8,
        moe_intermediate_size=16, vocab_size=64, run={"dtype": "float32"})
    if name == "latent":
        small.update(
            num_key_value_heads=4, q_lora_rank=16, kv_lora_rank=8,
            qk_nope_head_dim=4, qk_rope_head_dim=4, qk_head_dim=8, v_head_dim=8,
            n_routed_experts=4, num_experts_per_tok=2, num_hidden_layers=2,
            published=dict(published["published"], n_routed_experts=8))
    else:
        small.update(
            num_key_value_heads=2, linear_num_key_heads=2, linear_num_value_heads=4,
            linear_key_head_dim=8, linear_value_head_dim=8,
            shared_expert_intermediate_size=16, num_experts=4,
            num_experts_per_tok=3, num_hidden_layers=4,
            published=dict(published["published"], num_experts=16))
    return family.build(small), family.init_params


@pytest.mark.parametrize("name", list(FAMILIES))
def test_the_carry_leaves_the_other_models_scans_as_they_were(name):
    """Only the model that declares a carry has one: the dense, latent and
    hybrid models' ``PagedModel`` say ``None``, and the scan of their tick
    carries ``x`` and the cache's leaves and nothing else."""
    cfg, init = _other_model(name)
    m = paged_model(cfg)
    assert (m.carry is None) == (name != "zaya")
    params = jax.eval_shape(lambda: init(cfg, 0))
    pool = jax.eval_shape(lambda: kv_pages.init_page_pool(
        cfg, n_pages=4, page_len=PAGE, max_slots=2, pages_per_seq=2))
    jaxpr = jax.make_jaxpr(make_decode_tick(cfg, sentinel=False))(
        params, pool, jax.random.PRNGKey(0))
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"
             and e.params["length"] == m.n_units]
    assert len(scans) == 1
    assert scans[0].params["num_carry"] == FAMILIES[name]
    assert FAMILIES[name] == 1 + len(kv_pages.contents(pool)) + (name == "zaya")


def test_a_custom_walk_over_the_blocks_refuses_a_model_with_a_carry():
    """``layer_stack``'s walk (the streamed weights' hook) hands ``(x,
    cache)`` on and nothing else: a model that declares a carry is refused
    there by name, not walked without it."""
    cfg = FAMILY.build(tiny_config())
    params = jax.eval_shape(lambda: FAMILY.init_params(cfg, 0))
    pool = jax.eval_shape(lambda: kv_pages.init_page_pool(
        cfg, n_pages=4, page_len=PAGE, max_slots=2, pages_per_seq=2))
    tick = make_decode_tick(
        cfg, sentinel=False, layer_stack=lambda params, run_layer, x, cache: (x, cache))
    with pytest.raises(NotImplementedError, match="layer_stack.*declares a carry"):
        jax.eval_shape(tick, params, pool, jax.random.PRNGKey(0))


# --------------------------------------------------------------------- CCA


def test_the_references_convolutions_are_explicit_causal_shifts():
    """Position 0 sees itself only; the grouped convolution's padding is
    zeros, not the bias a depthwise convolution of zeros would give."""
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(5, 6)), jnp.float32)
    w0, b0 = jnp.asarray(rng.normal(size=(2, 6))), jnp.asarray(rng.normal(size=6))
    w1 = jnp.asarray(rng.normal(size=(2, 3, 2, 2)), jnp.float32)
    b1 = jnp.asarray(rng.normal(size=6), jnp.float32)
    np.testing.assert_array_equal(REF.shift(u, 1)[0], 0.0)
    np.testing.assert_array_equal(REF.shift(u, 2)[2:], u[:-2])
    c1 = REF.conv_depthwise(u, w0, b0)
    np.testing.assert_allclose(c1[0], u[0] * w0[1] + b0, atol=1e-6)
    np.testing.assert_allclose(c1[3], u[3] * w0[1] + u[2] * w0[0] + b0, atol=1e-6)
    c2 = REF.conv_grouped(c1, w1, b1)
    head = lambda c, j: jnp.einsum("hd,hde->he", c.reshape(3, 2), w1[j]).reshape(6)
    np.testing.assert_allclose(c2[0], head(c1[0], 1) + b1, atol=1e-5)
    np.testing.assert_allclose(c2[4], head(c1[4], 1) + head(c1[3], 0) + b1, atol=1e-5)


def test_the_references_q_k_mean_at_four_query_heads_a_kv_head():
    rng = np.random.default_rng(1)
    qt, kt = rng.normal(size=(3, 8, 4)), rng.normal(size=(3, 2, 4))
    mq, mk = REF.qk_mean(jnp.asarray(qt), jnp.asarray(kt))
    for i in range(8):
        np.testing.assert_allclose(mq[:, i], (qt[:, i] + kt[:, i // 4]) / 2, atol=1e-6)
    for j in range(2):
        np.testing.assert_allclose(
            mk[:, j], np.mean([(qt[:, i] + kt[:, j]) / 2 for i in range(4 * j, 4 * j + 4)],
                              axis=0), atol=1e-6)


@pytest.fixture(scope="module")
def cca_run(model):
    """Layer 1's CCA on two rows of 12 positions: all in one pass, and one
    position at a time from the empty state; and the reference's pieces."""
    cfg, params = model
    S, layer = 12, 1
    p = jax.tree.map(lambda a: a[layer], params["blocks"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, S, cfg.hidden_size))
    rows = jnp.arange(6).reshape(2, 3)
    pos = jnp.broadcast_to(jnp.arange(S), (2, S))
    slots = jnp.arange(2, dtype=jnp.int32)

    @jax.jit  # traced twice: once a width (T = S, then T = 1)
    def run(x, cache, at):
        cos, sin = zy.rope_tables(at, cfg)
        pages = rows[jnp.arange(2)[:, None], at // PAGE]
        return zy.cca(p, x, cache, layer, slots, rows, pages, at % PAGE, at,
                      jnp.ones(at.shape, bool), cos, sin, cfg)

    batch, cache = run(x, empty_cache(cfg, 2), pos)
    stepped, steps = empty_cache(cfg, 2), []
    for t in range(S):
        out, stepped = run(x[:, t:t + 1], stepped, pos[:, t:t + 1])
        steps.append(out)
    w = FAMILY._w(cfg)
    pf = f32(params["blocks"], layer)
    with jax.default_matmul_precision("highest"):
        ref = [REF.cca(pf, REF.norm(x[b], pf["ln1"], w["rms_norm_eps"]), w)
               for b in range(2)]
    return dict(cfg=cfg, p=p, layer=layer, batch=batch, cache=cache,
                steps=jnp.concatenate(steps, axis=1), stepped=stepped, ref=ref)


def _view(cache, name, layer, b):
    """Row ``b``'s 12 positions of plane ``name``, in order, by KV head."""
    return cache[name][3 * b:3 * b + 3, layer].reshape(12, 2, 8)


@pytest.mark.parametrize("piece", [
    "k: q-k mean, normalisation, temperature, partial rotary",
    "v: the current half and the previous token's half",
    "the whole CCA in one pass", "the whole CCA a position at a time",
    "the state after the last position",
])
def test_cca_matches_the_reference_piece_by_piece(cca_run, piece):
    r = cca_run
    for b in range(2):
        out, parts = r["ref"][b]
        for cache, got in ((r["cache"], r["batch"]), (r["stepped"], r["steps"])):
            if piece.startswith("k:"):
                np.testing.assert_allclose(
                    _view(cache, "k", r["layer"], b), parts["k"], atol=2e-5)
            elif piece.startswith("v:"):
                v = _view(cache, "v", r["layer"], b)
                np.testing.assert_allclose(v, parts["v"], atol=2e-5)
                # h_{-1} = 0: position 0 has no previous token's half
                assert float(jnp.abs(v[0, 1]).max()) == 0.0 < float(jnp.abs(v[1, 1]).max())
                np.testing.assert_allclose(v[1:, 1].reshape(11, -1),
                                           parts["v_next"][:-1], atol=2e-5)
            elif piece.startswith("the state"):
                np.testing.assert_allclose(
                    cache["conv"][b, r["layer"]], parts["u"][-2:], atol=2e-5)
                np.testing.assert_allclose(
                    cache["vprev"][b, r["layer"]], parts["v_next"][-1], atol=2e-5)
                assert float(jnp.abs(cache["conv"][:, 0]).max()) == 0.0  # layer 0's
        if piece == "the whole CCA in one pass":
            np.testing.assert_allclose(r["batch"][b], out, atol=5e-5)
        if piece == "the whole CCA a position at a time":
            np.testing.assert_allclose(r["steps"][b], out, atol=5e-5)


@pytest.mark.parametrize("form", ["a prompt pass from position 0", "a tick"])
def test_both_convolutions_match_the_reference_with_their_causal_padding(
        cca_run, form):
    """``cca_convs`` alone: over a row behind zeros, and for one position
    behind the two rows before it; at position 0 the grouped convolution
    sees ZEROS before it, whatever the depthwise bias."""
    cfg, p = cca_run["cfg"], cca_run["p"]
    assert float(jnp.abs(p["conv_dw_b"]).min()) > 0  # the case is not vacuous
    u = cca_run["ref"][0][1]["u"]  # [12, C]
    want = cca_run["ref"][0][1]["c2"]
    padded = jnp.pad(u, ((2, 0), (0, 0)))[None]
    if form == "a prompt pass from position 0":
        got = zy.cca_convs(p, padded, jnp.zeros((1,), jnp.int32), cfg)[0]
    else:
        got = jnp.concatenate([
            zy.cca_convs(p, padded[:, t:t + 3], jnp.full((1,), t, jnp.int32), cfg)[0]
            for t in range(12)])
    np.testing.assert_allclose(got, want, atol=3e-5)


# ------------------------------------------------------------- the router


@pytest.mark.parametrize("served", ["float32", "bfloat16"])
def test_router_carries_its_state_across_layers_in_float32(model, served):
    """Three layers' routers in a row on the model's side (``zaya_route``
    with the carry handed on) against the reference's: the same experts,
    weights and state; layer 0 starts from zeros; and the router computes
    in float32 whatever the served type (its input is the residual stream
    AS SERVED, upcast once)."""
    cfg, params = model
    cfg = dataclasses.replace(cfg, dtype=served)
    xs = jax.random.normal(jax.random.PRNGKey(7), (3, 2, 5, cfg.hidden_size)
                           ).astype(served)
    w = FAMILY._w(cfg)
    r = paged_model(cfg).carry(xs[0])
    assert r.dtype == jnp.float32 and float(jnp.abs(r).max()) == 0.0
    r_ref = jnp.zeros((10, cfg.router_hidden_size))
    for li in range(3):
        p = jax.tree.map(lambda a: a[li], params["blocks"])
        experts, weights, r = zy.zaya_route(
            p, zy.rms_norm(xs[li].astype(jnp.float32), p["ln2"], cfg.rms_norm_eps), r, cfg)
        assert (r.dtype, weights.dtype, experts.shape) == (
            jnp.float32, jnp.float32, (10, 1))
        pf = f32(params["blocks"], li)
        with jax.default_matmul_precision("highest"):
            h = REF.norm(xs[li].astype(jnp.float32).reshape(10, -1), pf["ln2"],
                         w["rms_norm_eps"])
            r_before = r_ref
            chosen, weight, gap, r_ref = REF.router(pf, h, r_ref, w)
            alone = REF.router(pf, h, jnp.zeros_like(r_ref), w)[3]
        np.testing.assert_allclose(r.reshape(10, -1), r_ref, atol=2e-5)
        np.testing.assert_allclose(weights[:, 0], weight, atol=2e-6)
        sure = np.asarray(gap) > 1e-4
        np.testing.assert_array_equal(np.asarray(experts[:, 0])[sure],
                                      np.asarray(chosen)[sure])
        # the carry reaches layer li from layer li - 1, and only from li > 0
        moved = float(jnp.abs(r_ref - alone).max())
        assert (moved == 0.0) if li == 0 else (moved > 1e-2)
        np.testing.assert_allclose(r_ref - alone, pf["r_gamma"] * r_before, atol=1e-5)


def test_the_chosen_weight_is_the_probability_not_renormalised(model):
    cfg, params = model
    p = jax.tree.map(lambda a: a[0], params["blocks"])
    h = jax.random.normal(jax.random.PRNGKey(8), (1, 9, cfg.hidden_size))
    _, weights, _ = zy.zaya_route(p, h, jnp.zeros((1, 9, cfg.router_hidden_size)), cfg)
    assert 0.0 < float(weights.min()) and float(weights.max()) < 1.0
    # the balancing bias moves the CHOICE and not the weight
    biased = dict(p, r_bias=p["r_bias"].at[2].add(10.0))
    experts, w2, _ = zy.zaya_route(biased, h, jnp.zeros((1, 9, cfg.router_hidden_size)), cfg)
    assert experts[:, 0].tolist() == [2] * 9 and float(w2.max()) < 1.0


# ------------------------------------------------------------ the experts


def test_expert_layer_matches_the_reference_at_top_1(model):
    cfg, params = model
    li = 2
    p = jax.tree.map(lambda a: a[li], params["blocks"])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 9, cfg.hidden_size))
    r0 = jax.random.normal(jax.random.PRNGKey(3), (2, 9, cfg.router_hidden_size))
    live = jnp.ones((2, 9), bool)
    run = jax.jit(lambda live: zy.moe(p, x, r0, live, params["experts"], li, cfg))
    got, load, r = run(live)
    pf, w = f32(params["blocks"], li), FAMILY._w(cfg)
    with jax.default_matmul_precision("highest"):
        h = REF.norm(x.reshape(18, -1), pf["ln2"], w["rms_norm_eps"])
        chosen, weight, _, r_ref = REF.router(pf, h, r0.reshape(18, -1), w)
        ref = REF.experts(params["experts"], li, h, chosen, weight, (0, 4))
    np.testing.assert_allclose(got.reshape(18, -1), ref, atol=3e-5)
    np.testing.assert_allclose(r.reshape(18, -1), r_ref, atol=2e-5)
    # every live position takes exactly one expert: nothing dropped
    assert int(load.sum()) == 18 and load.shape == (4,)
    assert load.tolist() == np.bincount(np.asarray(chosen), minlength=4).tolist()
    _, none, _ = run(~live)
    assert int(none.sum()) == 0


@pytest.mark.parametrize("case", ["all_on_one_held_expert", "none_held"])
def test_expert_layer_drops_nothing_when_every_token_picks_one_expert(case):
    """No capacity at top-1: all N tokens on ONE expert is legal and exact;
    none held gives exactly zero."""
    cfg = FAMILY.build(tiny_config())
    params = FAMILY.init_params(cfg, 4)
    N, e, li = 40, 2, 1
    h2 = jax.random.normal(jax.random.PRNGKey(4), (N, cfg.hidden_size))
    weights = jax.random.uniform(jax.random.PRNGKey(5), (N, 1))
    chosen = e if case == "all_on_one_held_expert" else cfg.n_held + 1
    y, load = jax.jit(lambda e: routed_experts(
        h2, e, weights, jnp.ones(N, bool), params["experts"], li, cfg)
    )(jnp.full((N, 1), chosen, jnp.int32))
    if case == "none_held":
        assert int(load.sum()) == 0 and float(jnp.abs(y).max()) == 0.0
        return
    assert load.tolist() == [0, 0, N, 0]
    with jax.default_matmul_precision("highest"):
        one = REF.swiglu(h2, *(params["experts"][n][li, e]
                               for n in ("w_gate", "w_up", "w_down")))
    np.testing.assert_allclose(y, weights * one, atol=3e-5)


def test_the_two_shares_of_eight_experts_add_up_to_the_uncut_layer():
    """What each of two chips computes for its 8 of 16 experts is, summed,
    the uncut reference's expert step (there is no shared expert to count
    once; the router is computed alike on both)."""
    whole = FAMILY.build(tiny_config(experts=16))
    params = seeded(whole, 11)
    li = 1
    p = jax.tree.map(lambda a: a[li], params["blocks"])
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 32, whole.hidden_size))
    r0 = jnp.zeros((1, 32, whole.router_hidden_size))
    pf, w = f32(params["blocks"], li), FAMILY._w(whole)
    with jax.default_matmul_precision("highest"):
        h = REF.norm(x[0], pf["ln2"], w["rms_norm_eps"])
        chosen, weight, _, _ = REF.router(pf, h, r0[0], w)
        uncut = REF.experts(params["experts"], li, h, chosen, weight, (0, 16))
    total, loads = jnp.zeros_like(uncut), []
    for chip in range(2):
        cfg = dataclasses.replace(whole, experts_held=8, expert_offset=8 * chip)
        stacks = {n: a[:, 8 * chip:8 * chip + 8] for n, a in params["experts"].items()}
        out, load, _ = jax.jit(lambda stacks, cfg=cfg: zy.moe(
            p, x, r0, jnp.ones((1, 32), bool), stacks, li, cfg))(stacks)
        total, loads = total + out[0], loads + [int(load.sum())]
    assert sum(loads) == 32 and min(loads) > 0
    np.testing.assert_allclose(total, uncut, atol=5e-5)


# ---------------------------------------------------------- the tied table


def test_the_tied_table_is_one_array_used_twice(model):
    cfg, params = model
    m = paged_model(cfg)
    assert "unembed" not in params
    assert m.resident(params)["embed"] is params["embed"]
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 3, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        want = REF.logits(params, REF.norm(x, params["ln_f"], cfg.rms_norm_eps))
        got = m.unembed(params, x)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=2e-5)
    tokens = jnp.asarray([[5, 9]])
    np.testing.assert_array_equal(m.embed(params, tokens), params["embed"][tokens])
    # the bill counts the table once: the held weights are the tree's leaves
    bill = engine(cfg, params).memory_bill()
    assert sum(bill["weights"].values()) == sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(params))


# -------------------------------------------------------- through the engine


def engine(cfg, params, **more):
    kw = dict(page_len=PAGE, n_pages=96, max_slots=4, pages_per_seq=12,
              prefill_batch=2, max_prompt_len=12, clock="virtual",
              logit_probe=16, prefix_cache=False)
    return ServeEngine(params, cfg, **{**kw, **more})


def drain(eng, requests):
    for prompt, max_new in requests:
        assert eng.submit(eng.make_request(prompt, max_new)) is None
    while not eng.drained:
        eng.step()
    assert eng.mem_leak_check()["ok"] and eng.pool_ok_failures == 0
    return [(r.prompt, r.tokens) for r in eng.done]


@pytest.fixture(scope="module")
def served(model):
    """Six requests of ragged lengths (one of a single token, one of exactly
    the pass's width) through an engine of four slots: the last two are
    seated in slots that earlier requests released."""
    from ddl25spring_tpu.obs.counters import counters

    cfg, params = model
    eng = engine(cfg, params)
    eng.warmup()
    counters.reset()  # the rings below hold this engine's passes alone
    rng = np.random.default_rng(0)
    done = drain(eng, [(rng.integers(1, 64, n).tolist(), new) for n, new in
                       ((9, 20), (5, 33), (12, 9), (1, 33), (10, 24), (7, 30))])
    rings = {name: counters.window(f"serve.{name}", 0.0, float("inf"))
             for name in ("moe.assignments_here", "moe.experts_hit",
                          "moe.load_max", "active_slots", "kv_live_positions")}
    return eng, done, rings


def test_prefill_then_decode_through_pages_and_slot_state_matches_the_reference(
        model, served):
    cfg, params = model
    eng, done, _ = served
    assert sorted(len(p) for p, _ in done) == [1, 5, 7, 9, 10, 12]
    out = FAMILY.check_served(cfg, params, done, pad_to=eng.max_seq_len)
    assert out["ok"], out
    assert out["tokens_checked"] == 149 and out["probe_ids"] == 16
    # float32 against float32, LOGITS: a mis-seated state or a stale tail
    # reads 0.1 and stays
    assert out["logit_rel_err"] < 1e-4 and out["logit_rel_err_near_tie"] < 1e-4
    assert out["logit_rel_err_p50"] < 1e-5
    assert out["worst_margin"] == 0.0  # float32: the reference's own argmax
    assert set(kv_pages.planes(eng.pool)) == {"k", "v"}
    assert set(kv_pages.slot_state(eng.pool)) == {"conv", "vprev"}


def test_a_readmitted_slot_starts_from_the_new_prompts_state(model, served):
    """Six requests through four slots: two ran in a slot another request
    had left its state in, and each matched the reference on its own; and a
    request served alone in a fresh engine gives the same logits as it did
    in the slot it inherited."""
    cfg, params = model
    _, done, _ = served
    prompt, tokens = done[-1]
    alone = drain(engine(cfg, params), [(prompt, len(tokens))])[0][1]
    assert list(alone) == list(tokens)
    np.testing.assert_allclose(np.asarray(alone.probe), np.asarray(tokens.probe),
                               atol=1e-5)


@pytest.mark.parametrize("lens", [(5, 12), (1, 7), (12, 2)], ids=str)
def test_a_ragged_pass_seats_each_rows_state_at_its_last_live_position(model, lens):
    """Rows of several lengths in ONE pass (a single token; exactly the
    pass's width): every layer's seated state is the reference's last two
    rows of ``u`` and last ``h W_v2`` of the row's last LIVE token (zeros
    before its first), not of the padding; a padding row seats nothing."""
    cfg, params = model
    rng = np.random.default_rng(sum(lens))
    rows, width = 3, 12
    packed = np.zeros((rows, width), np.int32)
    for b, n in enumerate(lens):
        packed[b, :n] = rng.integers(1, 64, n)
    slot_ids = np.asarray([2, 0, -1], np.int32)  # the third row is padding
    pool = kv_pages.init_page_pool(
        cfg, n_pages=12, page_len=PAGE, max_slots=3, pages_per_seq=4)
    dirty = jax.tree.map(lambda a: a + 7.0, kv_pages.slot_state(pool))
    pool = {**pool, kv_pages.SLOT_STATE: dirty}  # what a released slot leaves
    prefill = jax.jit(make_prefill(cfg, max_prompt_len=12, sentinel=False))
    pool, out, _ = prefill(
        params, pool, jnp.asarray(packed), jnp.asarray([*lens, 0], jnp.int32),
        jnp.zeros((rows,), jnp.int32), jnp.asarray(slot_ids), jax.random.PRNGKey(0))
    assert int(out[-1]) == 1  # the pool flag rides last
    state, w = kv_pages.slot_state(pool), FAMILY._w(cfg)
    for b, n in enumerate(lens):
        _, _, kept = REF.forward(params, packed[b, :n], w, keep=True)
        for li, layer in enumerate(kept):
            tail = jnp.pad(layer["u"], ((2, 0), (0, 0)))[-2:]
            np.testing.assert_allclose(
                state["conv"][slot_ids[b], li], tail, atol=3e-5)
            np.testing.assert_allclose(
                state["vprev"][slot_ids[b], li], layer["v_next"][-1], atol=3e-5)
    np.testing.assert_array_equal(state["conv"][1], dirty["conv"][1])  # unseated


def test_engine_fills_the_expert_rings_and_bills_the_state(served):
    eng, done, rings = served
    here, hit, top = (rings[f"moe.{k}"] for k in
                      ("assignments_here", "experts_hit", "load_max"))
    ticks = rings["active_slots"]
    assert len(here) == len(hit) == len(top) > len(ticks) > 0  # ticks and passes
    L, E = 3, 4
    for (_, a), (_, h), (_, m) in zip(here, hit, top):
        assert 0 < h <= L * E and 0 < m <= a and a % L == 0  # top-1: live x L
    assert len(rings["kv_live_positions"]) == len(ticks)
    bill = eng.memory_bill()
    state = kv_pages.pool_geometry(eng.pool)["slot_state_bytes"] * eng.max_slots
    assert bill["bytes_state"] == state == 4 * 3 * (2 * 80 + 8) * 4
    assert bill["total"] == sum(bill["weights"].values()) + bill["pool"]


def test_prefill_span_carries_state_rows_and_the_pool_span_its_bytes(model):
    from ddl25spring_tpu import obs

    cfg, params = model
    rec = obs.SpanRecorder()
    old = obs.set_recorder(rec)
    try:
        with obs.scoped(True):
            drain(engine(cfg, params), [([3, 4, 5, 6, 7], 2), ([8, 9], 2)])
    finally:
        obs.set_recorder(old)

    def stats(name):
        return [e.get("args", {}) for e in rec.to_chrome_trace()["traceEvents"]
                if e["name"] == name]

    (late,) = stats("serve.prefill")
    assert late["state_rows"] == late["rows"] == 2
    assert late["experts_hit"] > 0 and late["assignments"] == 3 * 7
    pool = stats("serve.pool")
    assert pool and pool[0]["bytes_state"] > 0 and pool[0]["bytes_planes"] > 0


@pytest.mark.parametrize("feature, kw", [
    ("prefix cache", dict(prefix_cache=True)),
    ("drafter", dict(spec_k=2, logit_probe=0)),
    ("tp_axis", dict(tp=2, logit_probe=0)),
    ("hand-off", None),
])
def test_engine_refuses_what_cannot_carry_the_state(model, feature, kw):
    cfg, params = model
    if kw is None:
        with pytest.raises(NotImplementedError, match="hand-off.*PERF.md section 7"):
            engine(cfg, params).begin_drain()
        return
    with pytest.raises(NotImplementedError, match=f"{feature}.*PERF.md section 7"):
        engine(cfg, params, **kw)


# ----------------------------------------------------------- the tolerances


def to_8_bits(params):
    """``params`` with the experts' weights at e4m3's 3 mantissa bits."""
    def low(a):
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=3)
    return dict(params, experts=jax.tree.map(low, params["experts"]))


def serve_whole(cfg, params):
    eng = engine(cfg, params, prefill_batch=4)
    rng = np.random.default_rng(0)
    done = drain(eng, [(rng.integers(1, 64, n).tolist(), 36) for n in (9, 10, 11, 12)])
    return eng.max_seq_len, done


@pytest.fixture(scope="module")
def whole_served():
    cfg = FAMILY.build(tiny_config(layers=4))
    params = FAMILY.init_params(cfg, 5)
    return cfg, params, *serve_whole(cfg, params)


@pytest.mark.parametrize("control", [
    "none", "8-bit experts", "8-bit experts in the engine",
    "router and q/k norm in bfloat16",
    "router and q/k norm in bfloat16 in the engine", "a dropped CCA"])
def test_the_tolerances_refuse_lower_precision_and_missing_work(whole_served, control):
    """The family's check at a small size, on the logits the ENGINE's own
    passes kept: against its own weights it is correct; against a reference
    that skips a layer's CCA it is not, by the limits on the error itself;
    8-bit experts (in the reference or served by the engine) and a router
    and q/k normalisation in bfloat16 in the reference are NOT correct by
    ``nearer_*_share``: the kept logits lie as near or nearer to the model
    stated at the lower precision as to the reference, at every position
    that is a near-tie in neither pass.  An ENGINE whose router runs in
    bfloat16 turns other near-tie choices than the reference's bfloat16
    router: it is seen (a thousand times the sound error, half its
    positions nearer the lower statement where the sound run has none) and
    not refused by the limit made for the chip (PERF.md section 6, PR 36)."""
    cfg, params, pad_to, done = whole_served
    other, kw = None, {}
    if control == "8-bit experts":
        other = to_8_bits(params)
    elif control == "8-bit experts in the engine":
        pad_to, done = serve_whole(cfg, to_8_bits(params))
    elif control == "router and q/k norm in bfloat16":
        kw = dict(high_prec="bfloat16")
    elif control == "router and q/k norm in bfloat16 in the engine":
        pad_to, done = serve_whole(
            dataclasses.replace(cfg, high_prec="bfloat16"), params)
    elif control == "a dropped CCA":
        kw = dict(skip_attention=(1,))
    out = FAMILY.check_served(cfg, params, done, pad_to=pad_to,
                              reference_params=other, **kw)
    limits = out["limits"]
    if control == "none":
        assert out["ok"] and out["logit_rel_err_p50"] < 1e-5, out
        assert out["nearer_8_bit_experts_share"] == out["nearer_bf16_router_share"] == 0
    elif control == "a dropped CCA":
        assert not out["ok"] and out["logit_rel_err_p50"] > 0.5, out
        assert out["margin_mean"] > 10 * limits["margin_mean"], out
    elif control.startswith("8-bit"):
        assert not out["ok"] and out["nearer_8_bit_experts_share"] == 1.0, out
        assert 1e-3 < out["logit_rel_err_p50"] < limits["logit_rel_err_p50"], out
    elif control == "router and q/k norm in bfloat16":
        assert not out["ok"] and out["nearer_bf16_router_share"] == 1.0, out
        assert out["nearer_8_bit_experts_share"] < limits["nearer_8_bit_experts_share"]
    else:
        assert out["nearer_bf16_router_share"] > 0.3 and out["logit_rel_err_p50"] > 1e-3, out


def test_the_reference_states_the_lower_precision_without_a_second_copy(model):
    """``expert_bits=3`` reads the experts' weights at e4m3's 3 mantissa
    bits: the same logits as the reference on weights rounded beforehand,
    and nothing more is rounded when they already are."""
    cfg, params = model
    tokens = np.arange(1, 12) % cfg.vocab_size
    w, held = dataclasses.asdict(cfg), (cfg.expert_offset, cfg.n_held)
    low = REF.forward(params, tokens, w, held=held, expert_bits=3)[0]
    rounded = REF.forward(to_8_bits(params), tokens, w, held=held)[0]
    again = REF.forward(to_8_bits(params), tokens, w, held=held, expert_bits=3)[0]
    np.testing.assert_array_equal(np.asarray(low), np.asarray(rounded))
    np.testing.assert_array_equal(np.asarray(low), np.asarray(again))
    assert np.abs(np.asarray(low - REF.forward(params, tokens, w, held=held)[0])).max() > 1e-3


def test_a_request_without_probed_rows_is_refused(model):
    cfg, params = model
    with pytest.raises(ValueError, match="logit_probe"):
        FAMILY.check_served(cfg, params, [([1, 2, 3], [4, 5])], pad_to=16)


def test_the_reference_takes_the_head_in_blocks_of_the_table(model):
    """``head`` over blocks of rows that do not divide the table gives what
    the whole product gives: the kept columns, the maximum, the mean and
    the targets' logits."""
    cfg, params = model
    h = jax.random.normal(jax.random.PRNGKey(12), (5, cfg.hidden_size))
    cols, targets = np.asarray([0, 7, 33, 63]), np.asarray([63, 0, 10, 11, 40])
    kept, top, mean, at = REF.head(params["embed"], h, cols, targets, block=24)
    full = np.asarray(REF.logits(params, h))
    np.testing.assert_allclose(kept, full[:, cols], atol=1e-5)
    np.testing.assert_allclose(top, full.max(-1), atol=1e-5)
    np.testing.assert_allclose(mean, full.mean(-1), atol=1e-5)
    np.testing.assert_allclose(at, full[np.arange(5), targets], atol=1e-5)


def test_the_counts_are_of_what_the_algorithm_needs():
    flops, nbytes = FAMILY.moe_gmm_flops_bytes(64, 15)
    assert flops == 2 * 3 * 2048 * 2048 * 64
    # every hit expert's three matrices once, each row in and out, bfloat16
    assert nbytes == 2 * (3 * 2048 * 2048 * 15 + 2 * 2048 * 64)
    assert FAMILY.moe_gmm_flops_bytes(0, 0) == (0.0, 0.0)
    # the signature readers/slice_roofline.py calls
    assert FAMILY.moe_gmm_flops_bytes(4.0, 2.0, hidden=8, width=4) == (
        2.0 * 3 * 8 * 4 * 4, 2 * (3.0 * 8 * 4 * 2 + 2.0 * 8 * 4))
