"""The heterogeneous pipeline's stage-SHARDED variant and the ResNet bench
builder on it — split from ``tests/test_het_pipeline.py`` (whose fixture and
helpers it shares) so that ``--dist loadfile`` can give the two halves to
two workers: together they were one worker's nine-minute chain."""

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ddl25spring_tpu.ops.losses import cross_entropy_logits
from ddl25spring_tpu.parallel.het_pipeline import make_het_pipeline_train_step
from ddl25spring_tpu.utils.mesh import make_mesh

from test_het_pipeline import (  # noqa: F401 — fixture used by name
    _shapes,
    _stage_fns,
    setup,
)


def test_sharded_het_pipeline_equals_replicated(setup, devices8):
    """The stage-SHARDED variant (params packed [S, maxP] over the stage
    axis, each device materializing only its branch) must match the
    replicated path — loss and the params after one optimizer step."""
    from ddl25spring_tpu.parallel.het_pipeline import (
        make_sharded_het_pipeline_train_step,
        pack_stage_params,
        unpack_stage_params,
    )

    params, x, y = setup
    mesh = make_mesh(devices8[:4], data=2, stage=2)
    M, mb = 2, 2
    batch = {"x": x, "y": y}
    tx = optax.sgd(0.1)

    step_rep = make_het_pipeline_train_step(
        _stage_fns(), lambda lg, b: cross_entropy_logits(lg, b["y"]),
        *_shapes(mb), tx, mesh, M, data_axis="data",
    )
    p_rep, _, l_rep = step_rep(params, tx.init(params), batch)

    step_sh, stacked, opt_sh = make_sharded_het_pipeline_train_step(
        _stage_fns(), params,
        lambda lg, b: cross_entropy_logits(lg, b["y"]),
        *_shapes(mb), tx, mesh, M, data_axis="data",
    )
    stacked, _, l_sh = step_sh(stacked, opt_sh, batch)

    np.testing.assert_allclose(float(l_rep), float(l_sh), rtol=1e-6)
    _, metas = pack_stage_params(params)
    for i in range(2):
        p_i = unpack_stage_params(jax.device_get(stacked)[i], metas[i])
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                jax.device_get(a), jax.device_get(b), atol=1e-5, rtol=1e-5
            ),
            p_rep[i],
            p_i,
        )


def test_sharded_het_pipeline_param_memory(setup, devices8):
    """The point of sharding: per-device param bytes are max_s|p_s| (plus
    padding), not sum_s|p_s|.  Check the compiled argument footprint of the
    sharded step is strictly below the replicated step's."""
    from ddl25spring_tpu.parallel.het_pipeline import (
        make_sharded_het_pipeline_train_step,
        pack_stage_params,
    )

    params, x, y = setup
    mesh = make_mesh(devices8[:2], stage=2)
    M, mb = 2, 4
    batch = {"x": x, "y": y}
    tx = optax.sgd(0.1)

    step_rep = make_het_pipeline_train_step(
        _stage_fns(), lambda lg, b: cross_entropy_logits(lg, b["y"]),
        *_shapes(mb), tx, mesh, M,
    )
    rep_stats = step_rep.lower(
        params, tx.init(params), batch
    ).compile().memory_analysis()

    step_sh, stacked, opt_sh = make_sharded_het_pipeline_train_step(
        _stage_fns(), params,
        lambda lg, b: cross_entropy_logits(lg, b["y"]),
        *_shapes(mb), tx, mesh, M,
    )
    sh_stats = step_sh.lower(stacked, opt_sh, batch).compile().memory_analysis()

    # replicated: every device holds p0+p1 (+opt twin). sharded: [S, maxP]
    # total across devices = 2*maxP, i.e. per-device maxP < p0+p1
    assert sh_stats.argument_size_in_bytes < rep_stats.argument_size_in_bytes, (
        sh_stats.argument_size_in_bytes, rep_stats.argument_size_in_bytes,
    )


def test_build_resnet_step_s3(devices8):
    """build_resnet_step at the reference flagship topology (dp=2, S=3):
    one step runs on a (data=2, stage=3) mesh and the loss is finite."""
    from ddl25spring_tpu.benchmarks import build_resnet_step

    step, params, opt_state, meta = build_resnet_step(
        devices8[:6], dp=2, S=3, num_microbatches=2, batch=8,
        dtype=jnp.float32,
    )
    assert meta["n_chips"] == 6
    assert "stage=3" in meta["topology"]
    x = np.zeros((8, 32, 32, 3), np.uint8)
    y = np.zeros((8,), np.int32)
    _, _, loss = step(params, opt_state, (jnp.asarray(x), jnp.asarray(y)))
    assert np.isfinite(float(loss))
