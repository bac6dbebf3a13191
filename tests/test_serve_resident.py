"""Dense serving holds its weights once, in the type its passes compute in
(PR 30): ``PagedModel.resident`` and the engine's one call of it.

The dense block casts every matrix to ``cfg.dtype`` where it uses it and
multiplies the norm scales in float32, so rounding the matrices once, when
the engine is built, serves the same bits.  On the 15M preset at
``dtype="bfloat16"``:

- an engine given float32 masters holds matrices in bfloat16, norm scales
  in float32 and no leaf of the masters that it had to cast;
- the decode tick's and every prefill shape's tokens, logits and pages are
  BITWISE what the same programs give on the masters (the parent's
  arithmetic);
- ``resident`` is idempotent and returns the same arrays, so several
  engines on one resident tree share it;
- the lowered tick and passes take no float32 matrix and convert none;
- tensor-parallel, streamed, speculating and prefix-caching engines serve
  the tokens of an engine that keeps its masters (the parent's), and the
  drafter's leaves are views of the resident ones;
- a model whose weights arrive in the served type (``Mistral4Config``)
  offers the identity;
- ``memory_bill()`` bills what is held, by type.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench_run
from ddl25spring_tpu import obs
from ddl25spring_tpu.models import llama
from ddl25spring_tpu.models.llama_paged import BLOCK_MATRICES
from ddl25spring_tpu.serve import kv_pages
from ddl25spring_tpu.serve.engine import (
    ServeEngine,
    make_decode_tick,
    make_prefill,
    pass_shapes,
)
from ddl25spring_tpu.serve.paged_model import paged_model
from ddl25spring_tpu.utils.config import LlamaConfig

CFG = LlamaConfig(dtype="bfloat16")  # the 15M preset, as a server runs it
BF16, F32 = jnp.dtype("bfloat16"), jnp.dtype("float32")
PAGE_LEN, PAGES_PER_SEQ, SLOTS, N_PAGES = 16, 8, 4, 24
MAX_PROMPT = 64
SHAPES = pass_shapes(SLOTS, MAX_PROMPT)  # (1, 32) (1, 64) (2, 64) (4, 64)
PROGRAMS = ["tick"] + [f"prefill{r}x{w}" for r, w in SHAPES]


@dataclasses.dataclass(frozen=True)
class MastersConfig(LlamaConfig):
    """The parent's engine: the same block, the weights kept as given and
    cast at each use."""

    def paged_model(self):
        return dataclasses.replace(
            super().paged_model(), resident=lambda params: params
        )


MASTERS_CFG = MastersConfig(dtype="bfloat16")


@pytest.fixture(scope="module")
def masters():
    return llama.init_llama_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def resident(masters):
    return paged_model(CFG).resident(masters)


def is_matrix(path) -> bool:
    return path[-1].key in BLOCK_MATRICES + ("embed", "unembed")


def fresh_pool():
    return kv_pages.init_page_pool(
        CFG, n_pages=N_PAGES, page_len=PAGE_LEN, max_slots=SLOTS,
        pages_per_seq=PAGES_PER_SEQ,
    )


def tokens_of(seed, n):
    return np.random.default_rng(seed).integers(1, CFG.vocab_size, n).tolist()


def make_engine(params, cfg=CFG, **kw):
    kw.setdefault("page_len", PAGE_LEN)
    kw.setdefault("n_pages", N_PAGES)
    kw.setdefault("max_slots", SLOTS)
    kw.setdefault("pages_per_seq", PAGES_PER_SEQ)
    kw.setdefault("prefill_batch", 2)
    kw.setdefault("max_prompt_len", MAX_PROMPT)
    kw.setdefault("clock", "virtual")
    kw.setdefault("trace_label", None)
    return ServeEngine(params, cfg, **kw)


@contextlib.contextmanager
def span_stats():
    """Telemetry on under a fresh Chrome recorder, which keeps each span's
    stats; yields ``stats(name)``: the stats of that name's spans so far."""
    rec = obs.SpanRecorder()
    old = obs.set_recorder(rec)
    try:
        with obs.scoped(True):
            yield lambda name: [
                e["args"] for e in rec.to_chrome_trace()["traceEvents"]
                if e["name"] == name
            ]
    finally:
        obs.set_recorder(old)


# ------------------------------------------------- what the engine holds


def test_engine_holds_matrices_in_the_served_type_and_no_master(masters):
    with span_stats() as stats:
        eng = make_engine(masters)
        (cast,) = stats("serve.resident")
    held = jax.tree_util.tree_leaves_with_path(eng.params)
    given = dict(jax.tree_util.tree_leaves_with_path(masters))
    for path, leaf in held:
        if is_matrix(path):
            assert leaf.dtype == BF16, path
            assert leaf is not given[path]
            # rounded once: the bits every use's cast gave
            np.testing.assert_array_equal(
                np.asarray(leaf), np.asarray(given[path].astype(BF16))
            )
        else:  # ln1, ln2, ln_f: multiplied in float32, left alone
            assert leaf.dtype == F32 and leaf is given[path], path
    n_matrices = sum(is_matrix(p) for p, _ in held)
    assert n_matrices == 9
    nbytes = {p: x.size * x.dtype.itemsize for p, x in given.items()}
    matrices = sum(b for p, b in nbytes.items() if is_matrix(p))
    assert cast == {
        "leaves_cast": 9,
        "bytes_masters": sum(nbytes.values()),
        "bytes_resident": sum(nbytes.values()) - matrices // 2,
    }


def test_resident_is_idempotent_and_engines_share_one_tree(masters, resident):
    model = paged_model(CFG)
    again = model.resident(resident)
    assert all(jax.tree.leaves(
        jax.tree.map(lambda a, b: a is b, resident, again)
    ))
    # abstract trees pass through it too (the TP programs' templates)
    shapes = jax.eval_shape(model.resident, masters)
    assert jax.tree.map(lambda x: x.dtype, shapes) == jax.tree.map(
        lambda x: x.dtype, resident
    )
    # the driver's arms, elastic replicas: each takes the tree as it is
    with span_stats() as stats:
        a, b = make_engine(resident), make_engine(resident)
        spans = stats("serve.resident")
    assert [s["leaves_cast"] for s in spans] == [0, 0]
    assert all(s["bytes_masters"] == s["bytes_resident"] for s in spans)
    assert all(jax.tree.leaves(
        jax.tree.map(lambda x, y, z: x is y is z, resident, a.params, b.params)
    ))


def test_memory_bill_is_of_the_resident_bytes_by_type(masters):
    eng = make_engine(masters)
    bill = eng.memory_bill()
    count = {F32.name: 0, BF16.name: 0}
    for leaf in jax.tree.leaves(eng.params):
        count[leaf.dtype.name] += leaf.size * leaf.dtype.itemsize
    assert bill["weights"] == count
    norms = (2 * CFG.n_layers + 1) * CFG.dmodel
    assert bill["weights"][F32.name] == 4 * norms
    n_params = sum(x.size for x in jax.tree.leaves(masters))
    assert bill["weights"][BF16.name] == 2 * (n_params - norms)
    assert bill["total"] == eng.mem_budget_bytes() == (
        sum(bill["weights"].values()) + bill["pool"]
    )
    assert eng.metrics()["param_bytes_per_chip"] == sum(bill["weights"].values())
    # and of nothing else: an engine that keeps masters bills four bytes
    kept = make_engine(masters, MASTERS_CFG).memory_bill()
    assert kept["weights"] == {F32.name: 4 * n_params}
    assert kept["pool"] == bill["pool"]


# ------------------------------------------------------- the same bits


def jitted(name: str):
    """The body of ``name`` with every logit of its sampled rows in its
    output (``logit_probe`` = the vocabulary)."""
    if name == "tick":
        return jax.jit(make_decode_tick(CFG, logit_probe=CFG.vocab_size))
    return jax.jit(make_prefill(
        CFG, max_prompt_len=MAX_PROMPT, logit_probe=CFG.vocab_size
    ))


def args_of(name: str, params):
    """A pool and the arguments after it: for a pass, a full batch of its
    shape, prompts of different lengths, the first across a page boundary;
    for the tick, the pages a two-row pass left (written with ``params``)
    and two live rows, whose tokens the pass left in the pool."""
    if name != "tick":
        rows, width = name[len("prefill"):].split("x")
        return fresh_pool(), prompt_batch(int(rows), int(width))
    pool, packed, _key = jitted("prefill")(
        params, fresh_pool(), *prompt_batch(2, MAX_PROMPT // 2)
    )
    assert int(packed[-1]) == 1  # the pool flag rides last
    return pool, (jax.random.PRNGKey(2),)


def prompt_batch(rows: int, width: int):
    lens = np.asarray([max(1, width - 21 * r) for r in range(rows)], np.int32)
    prompts = np.zeros((rows, width), np.int32)
    for row, n in enumerate(lens):
        prompts[row, :n] = tokens_of(11 + row, n)
    return (jnp.asarray(prompts), jnp.asarray(lens),
            jnp.zeros((rows,), jnp.int32), jnp.arange(rows, dtype=jnp.int32),
            jax.random.PRNGKey(1))


def same_bits(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(
            np.asarray(x).view(np.uint8), np.asarray(y).view(np.uint8)
        )


@pytest.mark.parametrize("name", PROGRAMS)
def test_pass_on_resident_weights_is_bitwise_the_pass_on_masters(
    masters, resident, name
):
    """Tokens, every logit of every sampled row (the packed vector) and
    every page the pass wrote."""
    fn = jitted(name)
    pool, args = args_of(name, masters)  # both sides start from one pool
    want_pool, want, _key = fn(masters, pool, *args)
    got_pool, got, _key = fn(resident, pool, *args)
    assert int(want[-1]) == int(got[-1]) == 1  # the pool flag rides last
    same_bits(got, want)
    same_bits(got_pool, want_pool)
    n = SLOTS if name == "tick" else args[0].shape[0]
    live = 2 if name == "tick" else n
    probe = np.asarray(want[n: n + n * CFG.vocab_size])
    logits = probe.view(np.float32).reshape(n, CFG.vocab_size)
    assert np.isfinite(logits).all() and np.ptp(logits[0]) > 0.1
    np.testing.assert_array_equal(
        np.asarray(want[:n])[:live], logits.argmax(-1)[:live]
    )


# -------------------------------------------- what the programs take


def weight_casts(text: str, shapes: set[tuple[int, ...]]) -> list[str]:
    """``stablehlo.convert`` ops of the lowered ``text`` that turn a
    float32 array shaped like a weight (whole, or one layer of a stack)
    into bfloat16."""
    found = []
    for dims, to in re.findall(
        r"stablehlo\.convert [^\n]*\(tensor<([0-9x]+)xf32>\) -> tensor<[0-9x]+x(\w+)>",
        text,
    ):
        shape = tuple(int(d) for d in dims.split("x"))
        if to == "bf16" and shape in shapes:
            found.append(dims)
    return found


def entry_parameters(text: str) -> list[tuple[tuple[int, ...], str]]:
    sig = text[text.index("@main("):]
    sig = sig[:sig.index("{\n")]
    return [
        (tuple(int(d) for d in dims.split("x") if d), kind)
        for dims, kind in re.findall(r"%arg\d+: tensor<((?:\d+x)*)(\w+)>", sig)
    ]


@pytest.mark.parametrize("name", PROGRAMS)
def test_lowered_pass_takes_no_float32_matrix_and_casts_no_weight(
    masters, resident, name
):
    fn = jitted(name)
    pool, args = args_of(name, resident)
    shapes = set()
    for path, leaf in jax.tree_util.tree_leaves_with_path(masters):
        if is_matrix(path):
            shapes |= {leaf.shape, leaf.shape[1:]}
    text = fn.lower(resident, pool, *args).as_text()
    wide_f32 = [p for p in entry_parameters(text) if p[1] == "f32" and len(p[0]) >= 2]
    # the norm scales of the stack, [L, D], are the one float32 array of
    # rank 2 a pass takes: multiplied as they are, never converted
    assert wide_f32 == [((CFG.n_layers, CFG.dmodel), "f32")] * 2
    assert sum(k == "bf16" and len(s) >= 2 and s in shapes
               for s, k in entry_parameters(text)) == 9
    assert weight_casts(text, shapes) == []
    # the check has teeth: the same program on the masters casts all nine
    kept = fn.lower(masters, pool, *args).as_text()
    assert len(weight_casts(kept, shapes)) == 9


# ------------------------------------------------- every serving mode

MODES = {
    "plain": {},
    "prefix_cache": {"prefix_cache": True},
    "spec_k2": {"spec_k": 2, "draft_layers": 2},
    "tp2": {"tp": 2},
    "tp2_spec_k2": {"tp": 2, "spec_k": 2, "draft_layers": 2},
    "tp2_weight_stream": {"tp": 2, "weight_stream": True},
}
SHARED = tokens_of(3, 20)
REQUESTS = [(SHARED + tokens_of(4, 9), 7), (tokens_of(5, 33), 5),
            (SHARED + tokens_of(6, 3), 6)]


def serve(eng) -> list[list[int]]:
    reqs = [eng.make_request(p, n) for p, n in REQUESTS]
    for r in reqs[:2]:
        assert eng.submit(r) is None
    eng.step()
    assert eng.submit(reqs[2]) is None  # admitted while the others decode
    steps = 0
    while not eng.drained:
        eng.step()
        steps += 1
        assert steps < 200, "engine failed to drain"
    assert eng.pool_ok_failures == 0
    return [r.tokens for r in reqs]


@pytest.mark.parametrize("mode", sorted(MODES))
def test_every_mode_serves_the_tokens_of_an_engine_that_keeps_masters(
    masters, mode
):
    """Mode by mode (in bfloat16 a radix hit or a verify pass may round
    another way than the plain path: that is the mode's, and the
    parent's too)."""
    parents_tokens = serve(make_engine(masters, MASTERS_CFG, **MODES[mode]))
    eng = make_engine(masters, **MODES[mode])
    for path, leaf in jax.tree_util.tree_leaves_with_path(eng.params):
        assert leaf.dtype == (BF16 if is_matrix(path) else F32), path
    if "spec_k" in MODES[mode]:
        # the drafter is cut from the resident leaves: no float32 matrix
        # anywhere, and its unsliced leaves are the target's own arrays
        for path, leaf in jax.tree_util.tree_leaves_with_path(eng.draft_params):
            assert leaf.dtype == (BF16 if is_matrix(path) else F32), path
        if eng.tp == 1:
            assert eng.draft_params["embed"] is eng.params["embed"]
            assert eng.draft_params["unembed"] is eng.params["unembed"]
        np.testing.assert_array_equal(
            np.asarray(eng.draft_params["blocks"]["wq"]),
            np.asarray(eng.params["blocks"]["wq"][:2]),
        )
    assert serve(eng) == parents_tokens
    if eng.prefix is not None:
        assert eng.prefix.hits >= 1
    if "spec_k" in MODES[mode]:
        assert eng.draft_tokens_proposed > 0


def test_streamed_rows_are_planned_and_gathered_in_the_resident_type(masters):
    """The ZeRO-3 bucket plan counts the bytes that are streamed: bfloat16
    matrix rows in their own buckets, the float32 scales in another."""
    from ddl25spring_tpu.parallel import zero
    from ddl25spring_tpu.serve.engine import _resident_template

    template = _resident_template(CFG)
    plan = zero.stream_block_plan(template["blocks"], 2)
    for bucket in plan.buckets:
        assert len({plan.dtypes[i] for i in bucket}) == 1
    assert {jnp.dtype(d) for d in plan.dtypes} == {BF16, F32}
    eng = make_engine(masters, tp=2, weight_stream=True)
    rows = eng.params["blocks"]
    assert rows["w_up"].dtype == BF16 and rows["ln1"].dtype == F32
    assert rows["w_up"].shape[:2] == (CFG.n_layers, 2)


# ------------------------------------- a model that arrives resident


def test_a_model_served_in_its_own_type_offers_the_identity():
    bench = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark")
    family = bench_run.load_module(bench, "families", "mistral4")
    published = bench_run.load_json(
        os.path.join(bench, "configs", "mistral-small-4-ep4.json")
    )
    config = dict(
        published, hidden_size=32, num_attention_heads=4, num_key_value_heads=4,
        q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=4, qk_rope_head_dim=4,
        qk_head_dim=8, v_head_dim=8, head_dim=8, moe_intermediate_size=16,
        n_routed_experts=4, num_experts_per_tok=2, num_hidden_layers=2,
        vocab_size=64, run={"dtype": "bfloat16"},
        published=dict(published["published"], n_routed_experts=8),
        deployment=dict(published["deployment"], expert_offset=0),
    )
    cfg = family.build(config)
    params = family.init_params(cfg, 3)
    model = paged_model(cfg)
    assert model.resident(params) is params
    with span_stats() as stats:
        eng = ServeEngine(
            params, cfg, page_len=4, n_pages=8, max_slots=2, pages_per_seq=4,
            prefill_batch=1, max_prompt_len=8, clock="virtual", trace_label=None,
        )
        (cast,) = stats("serve.resident")
    assert eng.params is params
    assert cast["leaves_cast"] == 0
    assert cast["bytes_masters"] == cast["bytes_resident"]
    # the tick lowered with what the engine holds is the tick lowered with
    # what the family drew: nothing about the program moved
    pool = kv_pages.init_page_pool(
        cfg, n_pages=8, page_len=4, max_slots=2, pages_per_seq=4
    )
    tick = jax.jit(make_decode_tick(cfg))
    args = (pool, jax.random.PRNGKey(0))
    assert tick.lower(eng.params, *args).as_text() == tick.lower(
        params, *args
    ).as_text()
