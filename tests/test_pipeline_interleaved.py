"""Pipeline-parallel correctness — the interleaved (virtual-stage) schedules.

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
    make_interleaved_pipeline_loss,
    make_pipeline_train_step,
    shard_staged_params,
)
from ddl25spring_tpu.utils.config import LlamaConfig
from ddl25spring_tpu.utils.mesh import make_mesh
from pipeline_common import (  # noqa: F401 — the fixture is used by name
    CFG,
    MOE_CFG,
    params_and_tokens,
    serial_loss,
    serial_moe_loss,
)


# ---------------------------------------------------------------- interleaved


def test_interleaved_split_merge_roundtrip():
    params = llama.init_llama_params(jax.random.PRNGKey(2), CFG)
    split = llama.split_blocks_interleaved(params, 2, 2)
    leaf = jax.tree.leaves(split["blocks"])[0]
    assert leaf.shape[:3] == (2, 2, 1)  # [S, V, Lc]
    back = llama.merge_blocks_interleaved(split)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(a, b), params, back
    )
    # chunk mapping: blocks[s][v] is global chunk v*S + s
    l0 = params["blocks"]["wq"]
    np.testing.assert_array_equal(split["blocks"]["wq"][1, 0, 0], l0[1])
    np.testing.assert_array_equal(split["blocks"]["wq"][0, 1, 0], l0[2])


@pytest.mark.parametrize("mbs", [2, 4])
def test_interleaved_loss_and_grads_equal_serial(
    params_and_tokens, mbs, devices8
):
    """The virtual-stage schedule (V=2 chunks/device) must match the
    serial model exactly — the tick algebra (slot -> (chunk, microbatch)
    map, single-ring delay-1 transfers, wrap-to-chunk-v+1) is all pinned
    by this equality."""
    params, tokens = params_and_tokens
    tokens = tokens[:4]  # B=4: divisible by both M values
    S, V = 2, 2
    mesh = make_mesh(devices8[:S], stage=S)
    staged = llama.split_blocks_interleaved(params, S, V)
    loss = make_interleaved_pipeline_loss(CFG, mesh, mbs, V)
    np.testing.assert_allclose(
        float(jax.jit(loss)(staged, tokens)),
        float(serial_loss(params, tokens)),
        rtol=1e-5,
    )
    g = jax.jit(jax.grad(loss))(staged, tokens)
    g_serial = jax.grad(serial_loss)(params, tokens)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            jax.device_get(a), jax.device_get(b), atol=2e-4, rtol=2e-3
        ),
        g_serial,
        llama.merge_blocks_interleaved(g),
    )


def test_interleaved_rejects_indivisible_microbatches(devices8):
    mesh = make_mesh(devices8[:2], stage=2)
    with pytest.raises(ValueError, match="divisible"):
        make_interleaved_pipeline_loss(CFG, mesh, 3, 2)


def test_interleaved_dp_pp_train_step(params_and_tokens, devices8):
    """schedule='interleaved' on the 2-D (data, stage) mesh: one step
    equals the serial step."""
    params, tokens = params_and_tokens
    tokens = tokens[:4]
    S, V, M = 2, 2, 2
    mesh = make_mesh(devices8[:4], data=2, stage=S)
    staged = shard_staged_params(
        llama.split_blocks_interleaved(params, S, V), mesh
    )
    tx = optax.adam(1e-3)
    step = make_pipeline_train_step(
        CFG, tx, mesh, M, data_axis="data", schedule="interleaved",
        num_chunks=V,
    )
    new_params, _, loss = step(staged, tx.init(staged), tokens)

    sloss, g = jax.value_and_grad(serial_loss)(params, tokens)
    updates, _ = tx.update(g, tx.init(params), params)
    expect = optax.apply_updates(params, updates)
    np.testing.assert_allclose(float(loss), float(sloss), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            jax.device_get(a), jax.device_get(b), atol=1e-5, rtol=1e-4
        ),
        llama.merge_blocks_interleaved(jax.device_get(new_params)),
        expect,
    )


def test_interleaved_moe_equals_serial(devices8):
    """Switch-MoE rides the interleaved schedule: per-(chunk, microbatch)
    dispatch groups are the per-layer-per-microbatch groups of the serial
    oracle, so equality is exact."""
    S, V, M = 2, 2, 2
    mesh = make_mesh(devices8[:S], stage=S)
    params = llama.init_llama_params(jax.random.PRNGKey(0), MOE_CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
    staged = llama.split_blocks_interleaved(params, S, V)
    loss = make_interleaved_pipeline_loss(MOE_CFG, mesh, M, V)
    np.testing.assert_allclose(
        float(jax.jit(loss)(staged, tokens)),
        float(serial_moe_loss(params, tokens, M)),
        rtol=1e-5,
    )
    g = jax.jit(jax.grad(loss))(staged, tokens)
    g_serial = jax.grad(lambda p: serial_moe_loss(p, tokens, M))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            jax.device_get(a), jax.device_get(b), atol=2e-4, rtol=2e-3
        ),
        g_serial,
        llama.merge_blocks_interleaved(g),
    )


# ------------------------------------------------------- interleaved 1F1B


@pytest.mark.parametrize("stages,chunks,microbatches,dp,tp", [
    (2, 2, 2, 1, 1),
    (2, 3, 4, 1, 1),
    (4, 2, 4, 1, 1),
    (2, 2, 4, 2, 2),
])
def test_interleaved_1f1b_equals_serial(
    stages, chunks, microbatches, dp, tp, devices8
):
    """The production Megatron schedule — interleaved virtual stages WITH
    the memory-bounded hand-rolled 1F1B backward: loss and grads must
    equal the serial model across chunk counts, stage counts, and the
    full DP x PP x TP composition (the backward stream's reversed slot
    map and ring indexing are what this pins)."""
    S, V, M = stages, chunks, microbatches
    cfg = LlamaConfig(
        vocab_size=64, dmodel=32, num_heads=2, n_layers=S * V, ctx_size=16,
        dtype="float32",
    )
    params = llama.init_llama_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (M * dp * 2, 16), 0, 64
    )

    def serial(p):
        return causal_lm_loss(llama.llama_forward(p, tokens, cfg), tokens)

    kw = {}
    names = {"stage": S}
    if dp > 1:
        names = {"data": dp, "stage": S}
        kw["data_axis"] = "data"
    if tp > 1:
        names["model"] = tp
        kw["tp_axis"] = "model"
    mesh = make_mesh(devices8[: S * dp * tp], **names)
    staged = llama.split_blocks_interleaved(params, S, V)
    l, g = jax.jit(
        make_1f1b_value_and_grad(cfg, mesh, M, num_chunks=V, **kw)
    )(staged, tokens)
    np.testing.assert_allclose(float(l), float(serial(params)), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            jax.device_get(a), jax.device_get(b), atol=2e-4, rtol=2e-3
        ),
        jax.grad(serial)(params),
        llama.merge_blocks_interleaved(g),
    )


def test_interleaved_1f1b_moe_equals_serial(devices8):
    """Switch-MoE rides interleaved 1F1B: every (chunk, microbatch)
    backward slot banks its chunk's weighted aux term."""
    S, V, M = 2, 2, 2
    mesh = make_mesh(devices8[:S], stage=S)
    params = llama.init_llama_params(jax.random.PRNGKey(0), MOE_CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
    staged = llama.split_blocks_interleaved(params, S, V)
    l, g = jax.jit(
        make_1f1b_value_and_grad(MOE_CFG, mesh, M, num_chunks=V)
    )(staged, tokens)
    np.testing.assert_allclose(
        float(l), float(serial_moe_loss(params, tokens, M)), rtol=1e-5
    )
    g_serial = jax.grad(lambda p: serial_moe_loss(p, tokens, M))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            jax.device_get(a), jax.device_get(b), atol=2e-4, rtol=2e-3
        ),
        g_serial,
        llama.merge_blocks_interleaved(g),
    )


def test_interleaved_1f1b_bounds_activation_memory(devices8):
    """The point of composing the two schedules: at V=2 the interleaved
    scan-transpose saves every chunk-tick's residuals (O(M·V)); the
    interleaved 1F1B ring-stashes 2VS-1 chunk inputs and rematerializes —
    compiled temp memory must be several times smaller at M=8."""
    cfg = LlamaConfig(
        vocab_size=128, dmodel=32, num_heads=2, n_layers=4, ctx_size=256,
        dtype="float32",
    )
    S, V, M = 2, 2, 8
    mesh = make_mesh(devices8[:S], stage=S)
    staged = shard_staged_params(
        llama.split_blocks_interleaved(
            llama.init_llama_params(jax.random.PRNGKey(0), cfg), S, V
        ),
        mesh, chunked=True,
    )
    tx = optax.adam(1e-3)
    opt = tx.init(staged)
    tokens = jnp.zeros((M, cfg.ctx_size), jnp.int32)

    temps = {}
    for sched in ("interleaved", "interleaved-1f1b"):
        step = make_pipeline_train_step(
            cfg, tx, mesh, M, schedule=sched, num_chunks=V
        )
        stats = step.lower(staged, opt, tokens).compile().memory_analysis()
        temps[sched] = stats.temp_size_in_bytes
    assert temps["interleaved-1f1b"] * 2 < temps["interleaved"], temps


def test_interleaved_1f1b_train_step_and_guards(devices8):
    """The train-step builder dispatches the interleaved-1f1b schedule
    (loss falls over steps) and the guards hold: residual stash and EP
    are not wired for chunked stacks, num_chunks >= 2 required."""
    S, V, M = 2, 2, 2
    mesh = make_mesh(devices8[:S], stage=S)
    cfg = LlamaConfig(
        vocab_size=64, dmodel=32, num_heads=2, n_layers=S * V, ctx_size=16,
        dtype="float32",
    )
    params = llama.init_llama_params(jax.random.PRNGKey(0), cfg)
    staged = shard_staged_params(
        llama.split_blocks_interleaved(params, S, V), mesh, chunked=True
    )
    tx = optax.adam(1e-2)
    step = make_pipeline_train_step(
        cfg, tx, mesh, M, schedule="interleaved-1f1b", num_chunks=V
    )
    opt = tx.init(staged)
    toks = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, 64)
    losses = []
    for _ in range(5):
        staged, opt, loss = step(staged, opt, toks)
        losses.append(float(loss))
    assert losses[-1] < losses[0]

    with pytest.raises(NotImplementedError, match="residual"):
        make_1f1b_value_and_grad(
            cfg, mesh, M, stash="residuals", num_chunks=V
        )
    with pytest.raises(ValueError, match="num_chunks"):
        make_pipeline_train_step(
            cfg, tx, mesh, M, schedule="interleaved-1f1b", num_chunks=1
        )
    with pytest.raises(ValueError, match="divisible"):
        make_1f1b_value_and_grad(cfg, mesh, 3, num_chunks=V)
