"""Pipeline-parallel correctness — sequence parallelism inside the pipe.

Split from ``tests/test_pipeline.py`` (same oracle: the partitioned program
must match the unpartitioned model, loss AND gradients); the shared configs
and serial oracles live in ``tests/pipeline_common.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ddl25spring_tpu.models import llama
from ddl25spring_tpu.ops.losses import causal_lm_loss
from ddl25spring_tpu.parallel.pipeline import (
    make_1f1b_value_and_grad,
    make_pipeline_loss,
    make_pipeline_train_step,
    shard_staged_params,
)
from ddl25spring_tpu.utils.config import LlamaConfig
from ddl25spring_tpu.utils.mesh import make_mesh
from pipeline_common import (  # noqa: F401 — the fixture is used by name
    CFG,
    CFG4H,
    MOE_CFG,
)


# ------------------------------------------------------- SP inside the pipe


@pytest.mark.parametrize("mode,dp,flash", [
    ("ring", 1, False),
    ("ring", 2, True),
    ("ulysses", 1, False),
    ("ulysses", 2, False),
])
def test_pipeline_sp_equals_serial(mode, dp, flash, devices8):
    """Sequence parallelism INSIDE pipeline stages (round-5 closure of
    the SP x PP hole): tokens shard their length dim over a seq axis,
    every stage runs ring/Ulysses attention at global positions, targets
    come from one pre-scan boundary ppermute, and loss + grads equal the
    serial model on the (data, stage, seq) mesh."""
    import dataclasses

    cfg = dataclasses.replace(CFG, use_flash=flash)
    S, sq, M = 2, 2, 2
    params = llama.init_llama_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)

    def serial(p):
        return causal_lm_loss(llama.llama_forward(p, tokens, cfg), tokens)

    names = (
        {"data": dp, "stage": S, "seq": sq} if dp > 1
        else {"stage": S, "seq": sq}
    )
    mesh = make_mesh(devices8[: S * sq * dp], **names)
    staged = llama.split_blocks_for_stages(params, S)
    loss = make_pipeline_loss(
        cfg, mesh, M, data_axis="data" if dp > 1 else None,
        seq_axis="seq", sp_mode=mode,
    )
    l, g = jax.jit(jax.value_and_grad(loss))(staged, tokens)
    np.testing.assert_allclose(float(l), float(serial(params)), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            jax.device_get(a), jax.device_get(b), atol=2e-4, rtol=2e-3
        ),
        jax.grad(serial)(params),
        llama.merge_blocks_from_stages(g),
    )


def test_pipeline_sp_train_step_and_guards(devices8):
    """The train-step builder threads seq_axis (gpipe only); the guarded
    compositions raise instead of silently deadlocking or mis-training."""
    S, sq, M = 2, 2, 2
    mesh = make_mesh(devices8[: S * sq], stage=S, seq=sq)
    params = llama.init_llama_params(jax.random.PRNGKey(0), CFG)
    staged = shard_staged_params(
        llama.split_blocks_for_stages(params, S), mesh
    )
    tx = optax.adam(1e-2)
    step = make_pipeline_train_step(CFG, tx, mesh, M, seq_axis="seq")
    opt = tx.init(staged)
    toks = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, 64)
    losses = []
    for _ in range(5):
        staged, opt, loss = step(staged, opt, toks)
        losses.append(float(loss))
    assert losses[-1] < losses[0]

    with pytest.raises(NotImplementedError, match="residual"):
        make_pipeline_train_step(
            CFG, tx, mesh, M, seq_axis="seq", schedule="1f1b-stash"
        )
    with pytest.raises(NotImplementedError, match="dense"):
        make_1f1b_value_and_grad(MOE_CFG, mesh, M, seq_axis="seq")


@pytest.mark.parametrize("tp", [1, 2])
def test_pipeline_sp_moe_equals_sp_oracle(tp, devices8):
    """Switch-MoE under SP x PP (round 5), with and without TP inside
    the stages: per-(seq-shard, layer, microbatch) dispatch groups with
    the aux term on its OWN scan carry (the CE slot holds
    token-count-normalized sums under seq — one denominator cannot
    serve both).  The oracle is make_sp_loss itself, per microbatch on
    a seq-only mesh: identical routing groups and the identical
    sharded-MoE aux estimator, so equality is exact (TP members compute
    identical global routing, so the same oracle serves tp > 1)."""
    from ddl25spring_tpu.parallel.sp import make_sp_loss

    S, sq, M = 2, 2, 2
    cfg = (
        LlamaConfig(
            vocab_size=64, dmodel=32, num_heads=4, n_layers=4,
            ctx_size=16, dtype="float32", n_experts=4,
            capacity_factor=2.0,
        )
        if tp > 1 else MOE_CFG
    )
    params = llama.init_llama_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
    names = {"stage": S, "seq": sq}
    kw = {}
    if tp > 1:
        names["model"] = tp
        kw["tp_axis"] = "model"
    mesh = make_mesh(devices8[: S * sq * tp], **names)
    staged = llama.split_blocks_for_stages(params, S)
    loss = make_pipeline_loss(cfg, mesh, M, seq_axis="seq", **kw)
    l, g = jax.jit(jax.value_and_grad(loss))(staged, tokens)

    mesh_sq = make_mesh(devices8[:sq], seq=sq)
    sp_loss = make_sp_loss(cfg, mesh_sq, seq_axis="seq")

    def oracle(p):
        mbs = tokens.reshape(M, tokens.shape[0] // M, -1)
        return jnp.mean(
            jnp.stack([sp_loss(p, mbs[m]) for m in range(M)])
        )

    # jitted: traced eagerly, op by op through the shard_maps, this
    # oracle alone took eight minutes per case on jax 0.9.0
    l_ref, g_ref = jax.jit(jax.value_and_grad(oracle))(params)
    np.testing.assert_allclose(float(l), float(l_ref), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            jax.device_get(a), jax.device_get(b), atol=2e-4, rtol=2e-3
        ),
        jax.device_get(g_ref),
        jax.device_get(llama.merge_blocks_from_stages(g)),
    )


@pytest.mark.parametrize("mode,num_chunks,tp", [
    ("ring", 1, 1), ("ulysses", 1, 1), ("ring", 2, 1),
    ("ring", 1, 2), ("ulysses", 1, 2), ("ring", 2, 2),
])
def test_sp_1f1b_equals_serial(mode, num_chunks, tp, devices8):
    """SP under the hand-rolled 1F1B backwards (plain AND interleaved
    chunks, AND composed with TP): sequence-sharded stages with
    ring/Ulysses attention, the forward slot running unconditionally
    (masked) so the seq collectives stay uniform, blocks pcast varying
    over seq so the final psum-over-seq assembles each shard's local
    grad paths exactly once (the TP 1/t normalization then composes
    unchanged) — loss and grads equal the serial model."""
    S, sq, M, V = 2, 2, 2, num_chunks
    cfg = CFG4H if tp > 1 else CFG
    params = llama.init_llama_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)

    def serial(p):
        return causal_lm_loss(llama.llama_forward(p, tokens, cfg), tokens)

    names = {"stage": S, "seq": sq}
    kw = {}
    if tp > 1:
        names["model"] = tp
        kw["tp_axis"] = "model"
    mesh = make_mesh(devices8[: S * sq * tp], **names)
    staged = (
        llama.split_blocks_interleaved(params, S, V) if V > 1
        else llama.split_blocks_for_stages(params, S)
    )
    l, g = jax.jit(
        make_1f1b_value_and_grad(
            cfg, mesh, M, seq_axis="seq", sp_mode=mode, num_chunks=V, **kw
        )
    )(staged, tokens)
    np.testing.assert_allclose(float(l), float(serial(params)), rtol=1e-5)
    merged = (
        llama.merge_blocks_interleaved(g) if V > 1
        else llama.merge_blocks_from_stages(g)
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            jax.device_get(a), jax.device_get(b), atol=2e-4, rtol=2e-3
        ),
        jax.grad(serial)(params),
        merged,
    )


@pytest.mark.parametrize("mode", ["ring", "ulysses"])
def test_pipeline_sp_tp_equals_serial(mode, devices8):
    """The full PP x SP x TP composition on a (stage, seq, model) mesh:
    Megatron-split matmuls operate on the per-shard head subset, ring /
    Ulysses attention runs over the seq axis within each stage, and loss
    + grads equal the serial model."""
    cfg = CFG4H
    S, sq, T, M = 2, 2, 2, 2
    params = llama.init_llama_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)

    def serial(p):
        return causal_lm_loss(llama.llama_forward(p, tokens, cfg), tokens)

    mesh = make_mesh(devices8[:8], stage=S, seq=sq, model=T)
    staged = llama.split_blocks_for_stages(params, S)
    loss = make_pipeline_loss(
        cfg, mesh, M, seq_axis="seq", sp_mode=mode, tp_axis="model"
    )
    l, g = jax.jit(jax.value_and_grad(loss))(staged, tokens)
    np.testing.assert_allclose(float(l), float(serial(params)), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            jax.device_get(a), jax.device_get(b), atol=2e-4, rtol=2e-3
        ),
        jax.grad(serial)(params),
        llama.merge_blocks_from_stages(g),
    )
