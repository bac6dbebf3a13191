"""Pipeline-parallel correctness.

The oracle (SURVEY §4): the pipelined, microbatched, stage-sharded program
must match the unpartitioned model — loss AND gradients — under the same
params and batch.  This subsumes the reference's eyeball-the-loss-files
verification of ``s01_b1_microbatches.py`` / ``s01_b2_dp_pp.py``.
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
    make_grad_accum_step,
    make_pipeline_loss,
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


@pytest.mark.parametrize("stages,microbatches", [(2, 3), (4, 2), (2, 6)])
def test_pipeline_loss_equals_serial(params_and_tokens, stages, microbatches, devices8):
    params, tokens = params_and_tokens
    mesh = make_mesh(devices8[:stages], stage=stages)
    staged = llama.split_blocks_for_stages(params, stages)
    pipe_loss = make_pipeline_loss(CFG, mesh, microbatches)
    l_pipe = float(jax.jit(pipe_loss)(staged, tokens))
    l_serial = float(serial_loss(params, tokens))
    np.testing.assert_allclose(l_pipe, l_serial, rtol=1e-5)


def test_pipeline_grads_equal_serial(params_and_tokens, devices8):
    params, tokens = params_and_tokens
    S, M = 2, 3
    mesh = make_mesh(devices8[:S], stage=S)
    staged = llama.split_blocks_for_stages(params, S)
    pipe_loss = make_pipeline_loss(CFG, mesh, M)

    g_pipe = jax.jit(jax.grad(pipe_loss))(staged, tokens)
    g_serial = jax.grad(serial_loss)(params, tokens)

    g_pipe_merged = llama.merge_blocks_from_stages(g_pipe)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            jax.device_get(a), jax.device_get(b), atol=2e-4, rtol=2e-3
        ),
        g_serial,
        g_pipe_merged,
    )


def test_dp_pp_2d_mesh_equals_serial(params_and_tokens, devices8):
    """The flagship topology: 2 pipelines x 2 stages on a 2-D mesh
    (reference shape: ``s01_b2_dp_pp.py:22-34`` with world=6; here 2x2)."""
    params, tokens = params_and_tokens
    mesh = make_mesh(devices8[:4], data=2, stage=2)
    staged = llama.split_blocks_for_stages(params, 2)
    pipe_loss = make_pipeline_loss(CFG, mesh, 3, data_axis="data")

    l_pipe = float(jax.jit(pipe_loss)(staged, tokens))
    l_serial = float(serial_loss(params, tokens))
    np.testing.assert_allclose(l_pipe, l_serial, rtol=1e-5)

    g_pipe = llama.merge_blocks_from_stages(
        jax.jit(jax.grad(pipe_loss))(staged, tokens)
    )
    g_serial = jax.grad(serial_loss)(params, tokens)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            jax.device_get(a), jax.device_get(b), atol=2e-4, rtol=2e-3
        ),
        g_serial,
        g_pipe,
    )


def test_pipeline_train_step_loss_decreases(devices8):
    mesh = make_mesh(devices8[:2], stage=2)
    params = llama.init_llama_params(jax.random.PRNGKey(0), CFG)
    staged = shard_staged_params(
        llama.split_blocks_for_stages(params, 2), mesh
    )
    tx = optax.adam(1e-3)
    opt_state = tx.init(staged)
    step = make_pipeline_train_step(CFG, tx, mesh, num_microbatches=3)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (6, 16), 0, 64)
    losses = []
    for _ in range(15):
        staged, opt_state, loss = step(staged, opt_state, tokens)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("stages,microbatches,dp", [(2, 3, 1), (4, 2, 1), (2, 4, 2)])
def test_1f1b_equals_gpipe_and_serial(
    params_and_tokens, stages, microbatches, dp, devices8
):
    """The 1F1B schedule (hand-rolled backward, bounded activation stash)
    must produce the same loss and gradients as GPipe and the serial model
    (the reference's 1F1B chain generalized: ``intro_PP_1F1B.py:50-95``)."""
    params, tokens = params_and_tokens
    B = 2 * microbatches * dp  # divisible by M, with M-chunks divisible by dp
    tokens = jnp.tile(tokens, (-(-B // tokens.shape[0]), 1))[:B]
    devs = devices8[: stages * dp]
    data_axis = "data" if dp > 1 else None
    mesh = (
        make_mesh(devs, data=dp, stage=stages)
        if dp > 1
        else make_mesh(devs, stage=stages)
    )
    staged = llama.split_blocks_for_stages(params, stages)

    l_1f1b, g_1f1b = jax.jit(
        make_1f1b_value_and_grad(CFG, mesh, microbatches, data_axis=data_axis)
    )(staged, tokens)
    l_gpipe, g_gpipe = jax.jit(
        jax.value_and_grad(
            make_pipeline_loss(CFG, mesh, microbatches, data_axis=data_axis)
        )
    )(staged, tokens)

    np.testing.assert_allclose(float(l_1f1b), float(l_gpipe), rtol=1e-5)
    np.testing.assert_allclose(
        float(l_1f1b), float(serial_loss(params, tokens)), rtol=1e-5
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            jax.device_get(a), jax.device_get(b), atol=2e-5, rtol=2e-4
        ),
        g_gpipe,
        g_1f1b,
    )
    g_serial = jax.grad(serial_loss)(params, tokens)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            jax.device_get(a), jax.device_get(b), atol=2e-4, rtol=2e-3
        ),
        g_serial,
        llama.merge_blocks_from_stages(g_1f1b),
    )


@pytest.mark.parametrize("moe", [False, True])
def test_1f1b_residual_stash_equals_remat_and_serial(
    params_and_tokens, moe, devices8
):
    """The non-remat 1F1B (stash='residuals': pullback residuals ring-
    stashed via closure_convert, no forward recompute) must match the
    remat schedule and the serial model exactly — VERDICT r3 #5."""
    S, M = 2, 3
    cfg = MOE_CFG if moe else CFG
    mesh = make_mesh(devices8[:S], stage=S)
    params = llama.init_llama_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (6, 16), 0, 64)
    staged = llama.split_blocks_for_stages(params, S)

    l_res, g_res = jax.jit(
        make_1f1b_value_and_grad(cfg, mesh, M, stash="residuals")
    )(staged, tokens)
    l_in, g_in = jax.jit(
        make_1f1b_value_and_grad(cfg, mesh, M, stash="input")
    )(staged, tokens)

    np.testing.assert_allclose(float(l_res), float(l_in), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            jax.device_get(a), jax.device_get(b), atol=2e-5, rtol=2e-4
        ),
        g_in,
        g_res,
    )
    if moe:
        l_serial = float(serial_moe_loss(params, tokens, M))
    else:
        l_serial = float(
            causal_lm_loss(llama.llama_forward(params, tokens, cfg), tokens)
        )
    np.testing.assert_allclose(float(l_res), l_serial, rtol=1e-5)


def test_1f1b_train_step_loss_decreases(devices8):
    mesh = make_mesh(devices8[:2], stage=2)
    params = llama.init_llama_params(jax.random.PRNGKey(0), CFG)
    staged = shard_staged_params(llama.split_blocks_for_stages(params, 2), mesh)
    tx = optax.adam(1e-3)
    opt_state = tx.init(staged)
    step = make_pipeline_train_step(
        CFG, tx, mesh, num_microbatches=3, schedule="1f1b"
    )
    tokens = jax.random.randint(jax.random.PRNGKey(1), (6, 16), 0, 64)
    losses = []
    for _ in range(15):
        staged, opt_state, loss = step(staged, opt_state, tokens)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_gpipe_remat_equals_plain_and_saves_memory(devices8):
    """``remat=True`` GPipe: same loss/grads, less compiled temp memory
    (scan saves carries only, recomputes block internals)."""
    cfg = LlamaConfig(
        vocab_size=128, dmodel=32, num_heads=2, n_layers=4, ctx_size=128,
        dtype="float32",
    )
    S, M = 2, 6
    mesh = make_mesh(devices8[:S], stage=S)
    params = llama.init_llama_params(jax.random.PRNGKey(0), cfg)
    staged = llama.split_blocks_for_stages(params, S)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (M, cfg.ctx_size), 0, 128)

    vg_plain = jax.jit(jax.value_and_grad(make_pipeline_loss(cfg, mesh, M)))
    vg_remat = jax.jit(
        jax.value_and_grad(make_pipeline_loss(cfg, mesh, M, remat=True))
    )
    (l0, g0), (l1, g1) = vg_plain(staged, tokens), vg_remat(staged, tokens)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            jax.device_get(a), jax.device_get(b), atol=1e-5, rtol=1e-4
        ),
        g0, g1,
    )
    m_plain = vg_plain.lower(staged, tokens).compile().memory_analysis()
    m_remat = vg_remat.lower(staged, tokens).compile().memory_analysis()
    assert m_remat.temp_size_in_bytes < m_plain.temp_size_in_bytes, (
        m_remat.temp_size_in_bytes, m_plain.temp_size_in_bytes,
    )


def test_1f1b_bounds_activation_memory(devices8):
    """The point of 1F1B: compiled temp memory is bounded in M.  GPipe's
    scan-transpose saves every tick's residuals (O(M) activations + block
    internals); 1F1B stashes only ``2S-1`` stage inputs and rematerializes.
    At ctx 256 / M=8 the compiled temp footprint must be several times
    smaller (measured 6.9x at ctx 1024 — RESULTS.md)."""
    cfg = LlamaConfig(
        vocab_size=128, dmodel=32, num_heads=2, n_layers=4, ctx_size=256,
        dtype="float32",
    )
    S, M = 2, 8
    mesh = make_mesh(devices8[:S], stage=S)
    staged = shard_staged_params(
        llama.split_blocks_for_stages(
            llama.init_llama_params(jax.random.PRNGKey(0), cfg), S
        ),
        mesh,
    )
    tx = optax.adam(1e-3)
    opt = tx.init(staged)
    tokens = jnp.zeros((M, cfg.ctx_size), jnp.int32)

    temps = {}
    for sched in ("gpipe", "1f1b"):
        step = make_pipeline_train_step(cfg, tx, mesh, M, schedule=sched)
        stats = step.lower(staged, opt, tokens).compile().memory_analysis()
        temps[sched] = stats.temp_size_in_bytes
    assert temps["1f1b"] * 2 < temps["gpipe"], temps


def test_grad_accum_equals_full_batch():
    """Microbatch grad accumulation == full-batch step (linearity), the
    standalone capability of s01_b1 without the stage split."""
    params = llama.init_llama_params(jax.random.PRNGKey(0), CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (6, 16), 0, 64)
    tx = optax.sgd(0.1)

    def loss_fn(p, batch, key):
        return causal_lm_loss(llama.llama_forward(p, batch, CFG), batch)

    accum = make_grad_accum_step(loss_fn, tx, num_microbatches=3)
    p_a, _, l_a = accum(params, tx.init(params), tokens, jax.random.PRNGKey(2))

    g_full = jax.grad(lambda p: loss_fn(p, tokens, None))(params)
    p_f = jax.tree.map(lambda p, g: p - 0.1 * g, params, g_full)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            jax.device_get(a), jax.device_get(b), atol=1e-5, rtol=1e-4
        ),
        p_a,
        p_f,
    )


@pytest.mark.parametrize("schedule", ["gpipe", "interleaved", "interleaved-1f1b"])
def test_fused_steps_equal_sequential(schedule, devices8):
    """fuse_train_steps(step, K) on [K, B, L] stacked batches must land on
    the same params/losses as K sequential dispatches of the same step
    (dispatch-amortization must not change semantics) — the fusion wraps
    ANY schedule, so both splitters/schedules share this harness."""
    from ddl25spring_tpu.parallel.pipeline import fuse_train_steps

    S, M, K = 2, 2, 3
    mesh = make_mesh(devices8[:S], stage=S)
    params = llama.init_llama_params(jax.random.PRNGKey(5), CFG)
    chunked = schedule.startswith("interleaved")
    if chunked:
        staged = llama.split_blocks_interleaved(params, S, 2)
    else:
        staged = llama.split_blocks_for_stages(params, S)
    tx = optax.sgd(0.05)
    # num_chunks only rides the interleaved schedules — passing it with
    # gpipe now raises (the round-4 advisor's silent-fallback finding)
    step = make_pipeline_train_step(
        CFG, tx, mesh, M, schedule=schedule,
        num_chunks=2 if chunked else 1,
    )
    tokens_k = jax.random.randint(jax.random.PRNGKey(6), (K, 4, 16), 0, 64)

    p_seq, o_seq = staged, tx.init(staged)
    seq_losses = []
    for i in range(K):
        p_seq, o_seq, loss = step(p_seq, o_seq, tokens_k[i])
        seq_losses.append(float(loss))

    multi = fuse_train_steps(step, K)
    p_fused, _, losses = multi(staged, tx.init(staged), tokens_k)

    np.testing.assert_allclose(np.asarray(losses), seq_losses, rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            jax.device_get(a), jax.device_get(b), atol=1e-5, rtol=1e-4
        ),
        p_fused,
        p_seq,
    )
