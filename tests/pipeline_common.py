"""What the pipeline test files share: the tiny configs, the serial
oracles, and the params fixture.  ``tests/test_pipeline*.py`` are one suite
split by schedule so that ``--dist loadfile`` can spread them over workers
(one file held a whole worker for most of the run's limit)."""

import jax
import jax.numpy as jnp
import pytest

from ddl25spring_tpu.models import llama
from ddl25spring_tpu.ops.losses import causal_lm_loss
from ddl25spring_tpu.utils.config import LlamaConfig

CFG = LlamaConfig(
    vocab_size=64, dmodel=32, num_heads=2, n_layers=4, ctx_size=16, dtype="float32"
)


def serial_loss(params, tokens):
    return causal_lm_loss(llama.llama_forward(params, tokens, CFG), tokens)


@pytest.fixture(scope="module")
def params_and_tokens():
    params = llama.init_llama_params(jax.random.PRNGKey(0), CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (6, 16), 0, 64)
    return params, tokens


MOE_CFG = LlamaConfig(
    vocab_size=64, dmodel=32, num_heads=2, n_layers=4, ctx_size=16,
    dtype="float32", n_experts=4, capacity_factor=2.0,
)

# 4-head variant for TP tests (heads must divide the model axis)
CFG4H = LlamaConfig(
    vocab_size=64, dmodel=32, num_heads=4, n_layers=4, ctx_size=16,
    dtype="float32",
)


def serial_moe_loss(params, tokens, M):
    """Per-microbatch oracle: the pipeline's MoE dispatch groups are the
    ``[mb*L]`` token groups each stage sees, so the reference composite
    loss is the mean over microbatches of ``ce + w * aux`` from
    ``llama_forward_with_aux`` — routing (and any capacity drops) is then
    IDENTICAL on both sides, so equality is exact, not just ample-capacity."""
    B, L = tokens.shape
    mbs = tokens.reshape(M, B // M, L)

    def per_mb(mb):
        logits, aux = llama.llama_forward_with_aux(params, mb, MOE_CFG)
        return causal_lm_loss(logits, mb) + MOE_CFG.moe_aux_weight * aux

    return jnp.mean(jax.vmap(per_mb)(mbs))
