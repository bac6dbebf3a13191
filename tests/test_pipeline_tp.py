"""Pipeline-parallel correctness — tensor parallelism inside the stages (DP x PP x TP).

Split from ``tests/test_pipeline.py`` (same oracle: the partitioned program
must match the unpartitioned model, loss AND gradients); the shared configs
and serial oracles live in ``tests/pipeline_common.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
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
    CFG,
    MOE_CFG,
    params_and_tokens,
    serial_loss,
    serial_moe_loss,
)


# ---------------------------------------------------------------- DPxPPxTP


@pytest.mark.parametrize("dp", [1, 2])
def test_pipeline_tp_equals_serial(params_and_tokens, dp, devices8):
    """Full 3-D parallelism (data, stage, model): Megatron TP inside each
    pipeline stage.  Loss AND sharded-weight grads must equal the serial
    model — the pmean-over-TP transpose and the in-block psums are what
    this pins."""
    params, tokens = params_and_tokens
    S, T = 2, 2
    tokens = tokens[:4]
    if dp > 1:
        mesh = make_mesh(devices8[: dp * S * T], data=dp, stage=S, model=T)
    else:
        mesh = make_mesh(devices8[: S * T], stage=S, model=T)
    staged = llama.split_blocks_for_stages(params, S)
    loss = make_pipeline_loss(
        CFG, mesh, 2, data_axis="data" if dp > 1 else None, tp_axis="model"
    )
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
        llama.merge_blocks_from_stages(g),
    )


def test_pipeline_tp_train_step_sharded_placement(params_and_tokens, devices8):
    """The 3-D train step with actually-sharded param placement: one step
    runs, block weights are placed over (stage, model), loss is finite."""
    import optax as _optax

    params, tokens = params_and_tokens
    tokens = tokens[:4]
    mesh = make_mesh(devices8, data=2, stage=2, model=2)
    staged = shard_staged_params(
        llama.split_blocks_for_stages(params, 2), mesh, tp_axis="model"
    )
    shard = staged["blocks"]["wq"].sharding.spec
    assert shard == jax.sharding.PartitionSpec("stage", None, None, "model")
    tx = _optax.adam(1e-3)
    step = make_pipeline_train_step(
        CFG, tx, mesh, 2, data_axis="data", tp_axis="model"
    )
    new_params, _, loss = step(staged, tx.init(staged), tokens)
    sloss = float(serial_loss(params, tokens))
    np.testing.assert_allclose(float(loss), sloss, rtol=1e-5)
    # the TP placement must SURVIVE the step — a train step that silently
    # drops tp_axis would return P('stage', ...) params (regression guard:
    # the first wiring of this feature did exactly that)
    out_spec = new_params["blocks"]["wq"].sharding.spec
    assert out_spec == jax.sharding.PartitionSpec(
        "stage", None, None, "model"
    ), out_spec
    # the 1F1B schedule accepts tp_axis through the SAME train-step
    # builder (regression guard on the pass-through at the vag dispatch):
    # loss == serial and the TP placement survives the optimizer step
    step1f = make_pipeline_train_step(
        CFG, tx, mesh, 2, data_axis="data", tp_axis="model",
        schedule="1f1b",
    )
    p1f, _, loss1f = step1f(staged, tx.init(staged), tokens)
    np.testing.assert_allclose(float(loss1f), sloss, rtol=1e-5)
    assert p1f["blocks"]["wq"].sharding.spec == jax.sharding.PartitionSpec(
        "stage", None, None, "model"
    )

    # the interleaved schedule composes with TP too: 5-d chunked specs
    # (chunked=True), loss == serial, placement survives the step
    staged_il = shard_staged_params(
        llama.split_blocks_interleaved(params, 2, 2), mesh,
        tp_axis="model", chunked=True,
    )
    assert staged_il["blocks"]["wq"].sharding.spec == (
        jax.sharding.PartitionSpec("stage", None, None, None, "model")
    )
    step_il = make_pipeline_train_step(
        CFG, tx, mesh, 2, data_axis="data", tp_axis="model",
        schedule="interleaved", num_chunks=2,
    )
    p_il, _, loss_il = step_il(staged_il, tx.init(staged_il), tokens)
    np.testing.assert_allclose(float(loss_il), sloss, rtol=1e-5)
    assert p_il["blocks"]["wq"].sharding.spec == (
        jax.sharding.PartitionSpec("stage", None, None, None, "model")
    )


def test_interleaved_tp_grads_equal_serial(params_and_tokens, devices8):
    """Interleaved virtual stages x Megatron TP: grads ≡ serial through
    the chunk-indexed TP blocks (the chunked 5-d specs must shard the
    OUTPUT dim of column weights, not the input dim)."""
    params, tokens = params_and_tokens
    tokens = tokens[:4]
    mesh = make_mesh(devices8[:4], stage=2, model=2)
    staged = llama.split_blocks_interleaved(params, 2, 2)
    loss = make_interleaved_pipeline_loss(CFG, mesh, 2, 2, tp_axis="model")
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


@pytest.mark.parametrize("stash", ["input", "residuals"])
def test_1f1b_tp_equals_serial(params_and_tokens, stash, devices8):
    """TP inside the hand-rolled 1F1B backward: the cooperative vjp runs
    the in-block psum transposes across TP members, and the final 1/t
    normalization (see make_1f1b_value_and_grad) makes loss AND grads
    equal the serial model — both stash variants, on the 3-D mesh."""
    params, tokens = params_and_tokens
    tokens = tokens[:4]
    mesh = make_mesh(devices8, data=2, stage=2, model=2)
    staged = llama.split_blocks_for_stages(params, 2)
    l, g = jax.jit(
        make_1f1b_value_and_grad(
            CFG, mesh, 2, data_axis="data", stash=stash, tp_axis="model"
        )
    )(staged, tokens)
    np.testing.assert_allclose(
        float(l), float(serial_loss(params, tokens)), rtol=1e-5
    )
    g_serial = jax.grad(serial_loss)(params, tokens)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            jax.device_get(a), jax.device_get(b), atol=2e-4, rtol=2e-3
        ),
        g_serial,
        llama.merge_blocks_from_stages(g),
    )


@pytest.mark.parametrize("cf", [2.0, 0.5])
def test_pipeline_tp_moe_equals_serial(cf, devices8):
    """Switch-MoE under pipeline TP on the full (data, stage, model) mesh:
    expert stacks shard their expert dim over the tp axis
    (staged_param_specs n_experts schema), routing stays global per
    (data-shard, stage, microbatch) group via make_tp_moe_fn, and the
    block's row-parallel psum completes the partial combine — so loss and
    grads equal the serial per-microbatch oracle EXACTLY, at ample
    capacity (cf=2.0) and under heavy drops (cf=0.5) alike."""
    import dataclasses

    cfg = dataclasses.replace(MOE_CFG, capacity_factor=cf)
    S, T, dp, M = 2, 2, 2, 2
    mesh = make_mesh(devices8[: dp * S * T], data=dp, stage=S, model=T)
    params = llama.init_llama_params(jax.random.PRNGKey(0), cfg)
    # sharpen router margins: TP's psum reorders fp summation by ulps,
    # and with the near-uniform init logits a ulp can flip a near-tie
    # routing decision under tight capacity — the test pins the drop
    # MECHANISM (global capacity, identical bucketing on every shard),
    # not fp tie-breaking, so give the router decisive margins
    params["blocks"]["moe"]["router"] = (
        30.0 * params["blocks"]["moe"]["router"]
    )
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64)
    staged = llama.split_blocks_for_stages(params, S)

    sharded = shard_staged_params(staged, mesh, tp_axis="model")
    w = sharded["blocks"]["moe"]["w_gate"]
    assert w.addressable_shards[0].data.shape[2] == cfg.n_experts // T, (
        "expert stacks not sharded over the model axis"
    )

    loss = make_pipeline_loss(
        cfg, mesh, M, data_axis="data", tp_axis="model"
    )
    l_pipe, g_pipe = jax.jit(jax.value_and_grad(loss))(sharded, tokens)

    # per-microbatch oracle at THIS cf (serial_moe_loss is pinned to
    # MOE_CFG's ample capacity): dp shards the microbatch dim -> M*dp
    # per-replica dispatch groups
    def oracle(p):
        mbs = tokens.reshape(M * dp, tokens.shape[0] // (M * dp), -1)

        def per_mb(mb):
            logits, aux = llama.llama_forward_with_aux(p, mb, cfg)
            return causal_lm_loss(logits, mb) + cfg.moe_aux_weight * aux

        return jnp.mean(jax.vmap(per_mb)(mbs))

    l_serial = float(oracle(params))
    np.testing.assert_allclose(float(l_pipe), l_serial, rtol=1e-5)

    g_serial = jax.grad(oracle)(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            jax.device_get(a), jax.device_get(b), atol=2e-4, rtol=2e-3
        ),
        g_serial,
        llama.merge_blocks_from_stages(g_pipe),
    )


@pytest.mark.parametrize("stash", ["input", "residuals"])
def test_1f1b_tp_moe_equals_serial(stash, devices8):
    """MoE x TP inside the hand-rolled 1F1B backward: the router grad is
    replicated across tp (pmean re-typing) while the expert slices follow
    the 1/t matmul normalization — pinned against the serial oracle, for
    both the remat and residual-stash backward variants."""
    S, T, M = 2, 2, 2
    mesh = make_mesh(devices8[: S * T], stage=S, model=T)
    params = llama.init_llama_params(jax.random.PRNGKey(0), MOE_CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
    staged = llama.split_blocks_for_stages(params, S)

    l, g = jax.jit(
        make_1f1b_value_and_grad(
            MOE_CFG, mesh, M, tp_axis="model", stash=stash
        )
    )(staged, tokens)
    l_serial = float(serial_moe_loss(params, tokens, M))
    np.testing.assert_allclose(float(l), l_serial, rtol=1e-5)
    g_serial = jax.grad(lambda p: serial_moe_loss(p, tokens, M))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            jax.device_get(a), jax.device_get(b), atol=2e-4, rtol=2e-3
        ),
        g_serial,
        llama.merge_blocks_from_stages(g),
    )


def test_interleaved_tp_moe_equals_serial(devices8):
    """MoE x TP x the interleaved virtual-stage schedule: the chunked
    5-d expert stacks shard their expert dim over tp."""
    S, V, M, T = 2, 2, 2, 2
    mesh = make_mesh(devices8[: S * T], stage=S, model=T)
    params = llama.init_llama_params(jax.random.PRNGKey(0), MOE_CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
    staged = llama.split_blocks_interleaved(params, S, V)
    loss = make_interleaved_pipeline_loss(
        MOE_CFG, mesh, M, V, tp_axis="model"
    )
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
