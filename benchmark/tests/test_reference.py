"""The plain reference against ``models/llama.py`` at a tiny size in
float32, and the serving check's two negative controls."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference
from ddl25spring_tpu.models import llama
from ddl25spring_tpu.ops.losses import causal_lm_loss
from ddl25spring_tpu.serve import driver
from ddl25spring_tpu.utils.config import LlamaConfig

CFG = LlamaConfig(vocab_size=96, dmodel=32, num_heads=2, n_layers=3,
                  ctx_size=32, dtype="float32")
FP32_EPS = 1e-4  # float32 against float32: reduction order only


@pytest.fixture(scope="module")
def params():
    return llama.init_llama_params(jax.random.PRNGKey(3), CFG)


def test_reference_matches_the_program_in_float32(params):
    tokens = jax.random.randint(jax.random.PRNGKey(4), (3, 24), 1, CFG.vocab_size)
    with jax.default_matmul_precision("highest"):
        want = llama.llama_forward(params, tokens, CFG)
    got = reference.forward(params, tokens, num_heads=CFG.num_heads)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    loss = float(reference.loss(params, tokens, num_heads=CFG.num_heads))
    assert abs(loss - float(causal_lm_loss(want, tokens))) < 1e-5


def test_reference_reads_a_pipeline_split(params):
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 16), 1, CFG.vocab_size)
    staged = llama.split_blocks_for_stages(params, 3)
    a = reference.loss(reference.flat_blocks(staged), tokens, num_heads=2)
    b = reference.loss(params, tokens, num_heads=2)
    assert float(a) == float(b)


def test_train_check_fails_a_dropped_layer(params):
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 24), 1, CFG.vocab_size)
    good = reference.check_train_loss(
        float(causal_lm_loss(llama.llama_forward(params, tokens, CFG), tokens)),
        params, tokens, num_heads=2, rtol=1e-5,
    )
    assert good["ok"] and good["rel"] < 1e-5
    short = dict(params, blocks=jax.tree.map(lambda a: a[:2], params["blocks"]))
    bad_loss = float(causal_lm_loss(llama.llama_forward(
        short, tokens, LlamaConfig(**{**CFG.__dict__, "n_layers": 2})), tokens))
    # in float32 the check is tight enough to see a dropped layer; at the
    # bfloat16 tolerance of the chip it is not, and reference.py says so
    bad = reference.check_train_loss(bad_loss, params, tokens, num_heads=2, rtol=1e-5)
    assert not bad["ok"] and bad["rel"] > 1e-5, bad


def serve(params, corrupt_after: int | None = None):
    """One request through the paged engine; optionally zero the KV page
    that holds its first positions after ``corrupt_after`` steps."""
    knobs = {**driver.engine_knobs(), "max_slots": 2, "prefill_batch": 2,
             "max_prompt_len": 16, "page_len": 4, "pages_per_seq": 8,
             "n_pages": 24, "max_queue": 8}
    eng = driver._build_engine(params, CFG, knobs, clock="wall",
                               temperature=0.0, trace_label=None)
    prompt = [int(t) for t in np.random.default_rng(0).integers(1, 96, 12)]
    req = eng.make_request(prompt, 14)
    assert eng.submit(req) is None
    steps = 0
    while req.done_t is None:
        eng.step()
        steps += 1
        if steps == corrupt_after:
            page = int(eng.pool["page_table"][eng.slots.index(req)][0])
            eng.pool = dict(
                eng.pool, k=eng.pool["k"].at[page].set(0.0),
                v=eng.pool["v"].at[page].set(0.0),
            )
    assert eng.mem_leak_check()["ok"]
    return [(req.prompt, [int(t) for t in req.tokens])], eng.max_seq_len


def test_served_tokens_pass_and_both_negative_controls_fail(params):
    done, pad_to = serve(params)
    ok = reference.check_served(params, done, num_heads=2, pad_to=pad_to, eps=FP32_EPS)
    assert ok["ok"] and ok["tokens_checked"] == 14, ok
    # a dropped layer in what is compared: the served tokens are not the
    # reference's any more
    dropped = reference.check_served(params, done, num_heads=2, pad_to=pad_to,
                                     eps=FP32_EPS, skip_layers=(1,))
    assert not dropped["ok"], dropped
    # a wrong page under the decode: zero one KV page mid-request
    wrong, _ = serve(params, corrupt_after=2)
    zeroed = reference.check_served(params, wrong, num_heads=2, pad_to=pad_to,
                                    eps=FP32_EPS)
    assert not zeroed["ok"], zeroed


def test_nothing_checked_is_not_correct(params):
    assert not reference.check_served(params, [], num_heads=2, pad_to=32)["ok"]
