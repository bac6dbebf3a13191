"""Heterogeneous-stage (ResNet) pipeline correctness.

Same oracle as the LLaMA pipeline tests: the 2-stage microbatched SPMD
program must reproduce the unpartitioned model's loss and gradients
(SURVEY §4 equivalence-testing discipline), here for the benchmark
topology — ResNet stages with *different* param structures and boundary
shapes (BASELINE.json "2-stage pipeline x 2-way DP with microbatches").
"""

import jax
import numpy as np
import optax
import pytest

from ddl25spring_tpu.models.resnet import ResNet18Stage0, ResNet18Stage1
from ddl25spring_tpu.ops.losses import cross_entropy_logits
from ddl25spring_tpu.parallel.het_pipeline import (
    make_het_pipeline_loss,
    make_het_pipeline_train_step,
)
from ddl25spring_tpu.utils.mesh import make_mesh

W = 8  # narrow net: CPU-fast, same structure
S0 = ResNet18Stage0(width=W)
S1 = ResNet18Stage1(width=W, num_classes=10)


@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 32, 32, 3))
    y = jax.random.randint(jax.random.PRNGKey(2), (8,), 0, 10)
    p0 = S0.init(key, x[:1])["params"]
    mid = S0.apply({"params": p0}, x[:1])
    p1 = S1.init(jax.random.PRNGKey(3), mid)["params"]
    return (p0, p1), x, y


def serial_loss(params, batch):
    p0, p1 = params
    logits = S1.apply({"params": p1}, S0.apply({"params": p0}, batch["x"]))
    return cross_entropy_logits(logits, batch["y"])


def _stage_fns():
    return [
        lambda p, x: S0.apply({"params": p}, x),
        lambda p, x: S1.apply({"params": p}, x),
    ]


def _shapes(mb):
    return (mb, 32, 32, 3), [(mb, 16, 16, 2 * W), (mb, 10)]


@pytest.mark.parametrize("microbatches", [2, 4])
def test_het_pipeline_loss_equals_serial(setup, microbatches, devices8):
    params, x, y = setup
    mesh = make_mesh(devices8[:2], stage=2)
    mb = x.shape[0] // microbatches
    in_shape, bounds = _shapes(mb)
    loss = make_het_pipeline_loss(
        _stage_fns(), lambda logits, b: cross_entropy_logits(logits, b["y"]),
        in_shape, bounds, mesh, microbatches,
    )
    l_pipe = float(jax.jit(loss)(params, {"x": x, "y": y}))
    l_serial = float(serial_loss(params, {"x": x, "y": y}))
    np.testing.assert_allclose(l_pipe, l_serial, rtol=1e-5)


def test_het_pipeline_grads_equal_serial(setup, devices8):
    params, x, y = setup
    mesh = make_mesh(devices8[:2], stage=2)
    M = 2
    in_shape, bounds = _shapes(x.shape[0] // M)
    loss = make_het_pipeline_loss(
        _stage_fns(), lambda logits, b: cross_entropy_logits(logits, b["y"]),
        in_shape, bounds, mesh, M,
    )
    g_pipe = jax.jit(jax.grad(loss))(params, {"x": x, "y": y})
    g_serial = jax.grad(serial_loss)(params, {"x": x, "y": y})
    for a, b in zip(jax.tree.leaves(g_pipe), jax.tree.leaves(g_serial)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_het_pipeline_dp_pp_trains(setup, devices8):
    """DPxPP: 2-way data x 2-stage pipeline on 4 devices; loss decreases."""
    params, x, y = setup
    mesh = make_mesh(devices8[:4], data=2, stage=2)
    M = 2
    mb = x.shape[0] // M // 2  # per-DP-shard microbatch
    in_shape, bounds = _shapes(mb)
    tx = optax.sgd(0.05)
    step = make_het_pipeline_train_step(
        _stage_fns(), lambda logits, b: cross_entropy_logits(logits, b["y"]),
        in_shape, bounds, tx, mesh, M, data_axis="data",
    )
    opt_state = tx.init(params)
    batch = {"x": x, "y": y}
    losses = []
    for _ in range(5):
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


# ---------------------------------------------------------- sharded params


@pytest.mark.parametrize("stages", [3, 4])
def test_het_pipeline_s3_s4_equals_serial(stages, devices8):
    """The S-generic ResNet stage split (round-5 lift of the S<=2 cap):
    the S-stage pipelined loss and grads equal the serial composition of
    the same stages — the reference's flagship 3-stage topology
    (lab/s01_b2_dp_pp.py:22-29) is now expressible on the benchmark
    workload."""
    from ddl25spring_tpu.models.resnet import make_resnet_stages

    S = stages
    mods = make_resnet_stages(S, width=W)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 32, 32, 3))
    y = jax.random.randint(jax.random.PRNGKey(2), (8,), 0, 10)
    params, shapes, h = [], [], x[:1]
    for i, sm in enumerate(mods):
        p = sm.init(jax.random.PRNGKey(i), h)["params"]
        h = sm.apply({"params": p}, h)
        params.append(p)
        shapes.append(h.shape)
    params = tuple(params)

    def serial(ps, batch):
        h = batch["x"]
        for sm, p in zip(mods, ps):
            h = sm.apply({"params": p}, h)
        return cross_entropy_logits(h, batch["y"])

    mesh = make_mesh(devices8[:S], stage=S)
    M, mb = 2, 4
    fns = [
        (lambda sm: lambda p, h: sm.apply({"params": p}, h))(sm)
        for sm in mods
    ]
    pipe = make_het_pipeline_loss(
        fns, lambda logits, b: cross_entropy_logits(logits, b["y"]),
        (mb, 32, 32, 3), [(mb,) + s[1:] for s in shapes], mesh, M,
    )
    batch = {"x": x, "y": y}
    np.testing.assert_allclose(
        float(jax.jit(pipe)(params, batch)),
        float(serial(params, batch)),
        rtol=1e-5,
    )
    g_pipe = jax.jit(jax.grad(pipe))(params, batch)
    g_serial = jax.grad(serial)(params, batch)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            jax.device_get(a), jax.device_get(b), atol=5e-4, rtol=5e-3
        ),
        g_serial,
        g_pipe,
    )
