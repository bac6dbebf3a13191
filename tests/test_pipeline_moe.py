"""Pipeline-parallel correctness — switch-MoE and expert parallelism in the pipe.

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
    make_pipeline_loss,
    make_pipeline_train_step,
    shard_staged_params,
)
from ddl25spring_tpu.utils.mesh import make_mesh
from pipeline_common import (  # noqa: F401 — the fixture is used by name
    MOE_CFG,
    serial_moe_loss,
)


def test_gpipe_moe_loss_and_grads_equal_serial(devices8):
    """Switch-MoE rides GPipe: aux loss accumulates through the scan carry
    (VERDICT r3 #3 — the flagship MoE-LLaMA x PP composition)."""
    S, M = 2, 3
    mesh = make_mesh(devices8[:S], stage=S)
    params = llama.init_llama_params(jax.random.PRNGKey(0), MOE_CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (6, 16), 0, 64)
    staged = llama.split_blocks_for_stages(params, S)

    pipe_loss = make_pipeline_loss(MOE_CFG, mesh, M)
    l_pipe = float(jax.jit(pipe_loss)(staged, tokens))
    l_serial = float(serial_moe_loss(params, tokens, M))
    np.testing.assert_allclose(l_pipe, l_serial, rtol=1e-5)

    g_pipe = llama.merge_blocks_from_stages(
        jax.jit(jax.grad(pipe_loss))(staged, tokens)
    )
    g_serial = jax.grad(lambda p: serial_moe_loss(p, tokens, M))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            jax.device_get(a), jax.device_get(b), atol=2e-4, rtol=2e-3
        ),
        g_serial,
        g_pipe,
    )


def test_1f1b_moe_equals_gpipe_and_serial(devices8):
    """The memory-bounded schedule carries the per-stage aux term too
    (uniform 1.0 loss-cotangent seed across stages)."""
    S, M = 2, 3
    mesh = make_mesh(devices8[:S], stage=S)
    params = llama.init_llama_params(jax.random.PRNGKey(0), MOE_CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (6, 16), 0, 64)
    staged = llama.split_blocks_for_stages(params, S)

    l_1f1b, g_1f1b = jax.jit(
        make_1f1b_value_and_grad(MOE_CFG, mesh, M)
    )(staged, tokens)
    l_gpipe, g_gpipe = jax.jit(
        jax.value_and_grad(make_pipeline_loss(MOE_CFG, mesh, M))
    )(staged, tokens)

    np.testing.assert_allclose(float(l_1f1b), float(l_gpipe), rtol=1e-5)
    np.testing.assert_allclose(
        float(l_1f1b), float(serial_moe_loss(params, tokens, M)), rtol=1e-5
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            jax.device_get(a), jax.device_get(b), atol=2e-5, rtol=2e-4
        ),
        g_gpipe,
        g_1f1b,
    )
    g_serial = jax.grad(lambda p: serial_moe_loss(p, tokens, M))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            jax.device_get(a), jax.device_get(b), atol=2e-4, rtol=2e-3
        ),
        g_serial,
        llama.merge_blocks_from_stages(g_1f1b),
    )


def test_moe_dp_pp_2d_mesh_equals_serial(devices8):
    """MoE x the flagship DP x PP topology on a 2-D mesh."""
    S, M = 2, 2
    mesh = make_mesh(devices8[:4], data=2, stage=S)
    params = llama.init_llama_params(jax.random.PRNGKey(0), MOE_CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64)
    staged = llama.split_blocks_for_stages(params, S)

    pipe_loss = make_pipeline_loss(MOE_CFG, mesh, M, data_axis="data")
    l_pipe = float(jax.jit(pipe_loss)(staged, tokens))
    # DP shards the microbatch dim: each replica sees its own [mb] rows, so
    # the oracle groups are the M*dp per-replica microbatches
    l_serial = float(serial_moe_loss(params, tokens, M * 2))
    np.testing.assert_allclose(l_pipe, l_serial, rtol=1e-5)


@pytest.mark.parametrize("cf", [2.0, 0.5])
def test_ep_dp_pp_expert_sharded_equals_dense(cf, devices8):
    """EP x DP x PP: expert stacks sharded over the data axis, capacity
    buckets moved between data rows by all_to_all each tick.  Routing and
    capacity are decided per data shard BEFORE the a2a, so loss and grads
    are EXACTLY the replicated-expert pipeline's — at ample capacity
    (cf=2.0) and under heavy drops (cf=0.5) alike — while each device
    holds only E/n experts per stage."""
    import dataclasses

    cfg = dataclasses.replace(MOE_CFG, capacity_factor=cf)
    S, M = 2, 2
    mesh = make_mesh(devices8[:4], data=2, stage=S)
    params = llama.init_llama_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64)
    staged = llama.split_blocks_for_stages(params, S)

    dense_loss = make_pipeline_loss(cfg, mesh, M, data_axis="data")
    l_dense, g_dense = jax.jit(jax.value_and_grad(dense_loss))(staged, tokens)

    sharded = shard_staged_params(staged, mesh, ep_axis="data")
    w = sharded["blocks"]["moe"]["w_gate"]
    assert w.addressable_shards[0].data.shape[2] == cfg.n_experts // 2, (
        "expert stacks not sharded over the data axis"
    )
    ep_loss = make_pipeline_loss(
        cfg, mesh, M, data_axis="data", ep_axis="data"
    )
    l_ep, g_ep = jax.jit(jax.value_and_grad(ep_loss))(sharded, tokens)

    np.testing.assert_allclose(float(l_ep), float(l_dense), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            jax.device_get(a), jax.device_get(b), atol=2e-5, rtol=2e-4
        ),
        g_dense,
        g_ep,
    )


def test_ep_pipeline_train_step_and_guards(devices8):
    """The EP x DP x PP train step runs (loss falls over steps); EP and
    TP remain mutually exclusive in the staged specs."""
    S, M = 2, 2
    mesh = make_mesh(devices8[:4], data=2, stage=S)
    params = llama.init_llama_params(jax.random.PRNGKey(0), MOE_CFG)
    staged = shard_staged_params(
        llama.split_blocks_for_stages(params, S), mesh, ep_axis="data"
    )
    tx = optax.adam(1e-2)
    step = make_pipeline_train_step(
        MOE_CFG, tx, mesh, M, data_axis="data", ep_axis="data"
    )
    opt = tx.init(staged)
    losses = []
    toks = jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, 64)
    for _ in range(5):
        staged, opt, loss = step(staged, opt, toks)
        losses.append(float(loss))
    assert losses[-1] < losses[0]

    with pytest.raises(NotImplementedError, match="exclusive"):
        make_pipeline_train_step(
            MOE_CFG, tx, mesh, M, data_axis="data", ep_axis="data",
            tp_axis="data",
        )


@pytest.mark.parametrize("schedule", ["interleaved", "interleaved-1f1b"])
def test_ep_interleaved_expert_sharded_equals_dense(schedule, devices8):
    """EP rides BOTH interleaved schedules (round-5 closure of the
    chunked-EP guard): the 5-d expert stacks shard their expert dim over
    the data axis, the per-tick a2a sits in uniform control flow (the
    interleaved tick runs its chunk unconditionally under EP), and loss
    + grads equal the dense replicated-expert run exactly — heavy drops
    included."""
    import dataclasses

    cfg = dataclasses.replace(MOE_CFG, capacity_factor=0.5)
    S, V, M, dp = 2, 2, 2, 2
    mesh = make_mesh(devices8[:4], data=dp, stage=S)
    params = llama.init_llama_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64)
    staged = llama.split_blocks_interleaved(params, S, V)

    if schedule == "interleaved":
        def vag(ep_axis, p):
            return jax.jit(jax.value_and_grad(make_interleaved_pipeline_loss(
                cfg, mesh, M, V, data_axis="data", ep_axis=ep_axis
            )))(p, tokens)
    else:
        def vag(ep_axis, p):
            return jax.jit(make_1f1b_value_and_grad(
                cfg, mesh, M, data_axis="data", num_chunks=V,
                ep_axis=ep_axis,
            ))(p, tokens)

    l_dense, g_dense = vag(None, staged)
    sharded = shard_staged_params(staged, mesh, ep_axis="data", chunked=True)
    w = sharded["blocks"]["moe"]["w_gate"]
    assert w.addressable_shards[0].data.shape[3] == cfg.n_experts // dp
    l_ep, g_ep = vag("data", sharded)

    np.testing.assert_allclose(float(l_ep), float(l_dense), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            jax.device_get(a), jax.device_get(b), atol=2e-5, rtol=2e-4
        ),
        g_dense,
        g_ep,
    )


@pytest.mark.parametrize("cf,stash", [
    (2.0, "input"), (0.5, "input"), (2.0, "residuals"),
])
def test_ep_1f1b_expert_sharded_equals_dense(cf, stash, devices8):
    """EP x DP x PP under the 1F1B schedules: the forward slot runs the
    stage body unconditionally (output masked) so the EP all_to_all sits
    in uniform control flow, and expert-slice grads take the 1/n
    normalization.  Loss and grads must equal the dense replicated-expert
    1F1B run EXACTLY — ample capacity and heavy drops alike (routing and
    capacity are per data shard, decided before the a2a)."""
    import dataclasses

    cfg = dataclasses.replace(MOE_CFG, capacity_factor=cf)
    S, M = 2, 2
    mesh = make_mesh(devices8[:4], data=2, stage=S)
    params = llama.init_llama_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64)
    staged = llama.split_blocks_for_stages(params, S)

    l_dense, g_dense = jax.jit(
        make_1f1b_value_and_grad(
            cfg, mesh, M, data_axis="data", stash=stash
        )
    )(staged, tokens)

    sharded = shard_staged_params(staged, mesh, ep_axis="data")
    l_ep, g_ep = jax.jit(
        make_1f1b_value_and_grad(
            cfg, mesh, M, data_axis="data", stash=stash, ep_axis="data"
        )
    )(sharded, tokens)

    np.testing.assert_allclose(float(l_ep), float(l_dense), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            jax.device_get(a), jax.device_get(b), atol=2e-5, rtol=2e-4
        ),
        g_dense,
        g_ep,
    )
    # and the dense 1F1B itself is pinned to GPipe elsewhere; close the
    # loop cheaply against the serial per-microbatch oracle on the loss
    def oracle(p):
        mbs = tokens.reshape(M * 2, tokens.shape[0] // (M * 2), -1)

        def per_mb(mb):
            logits, aux = llama.llama_forward_with_aux(p, mb, cfg)
            return causal_lm_loss(logits, mb) + cfg.moe_aux_weight * aux

        return jnp.mean(jax.vmap(per_mb)(mbs))

    np.testing.assert_allclose(float(l_ep), float(oracle(params)), rtol=1e-5)
