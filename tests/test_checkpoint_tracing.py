"""Checkpoint/resume and tracing subsystems.

The key test is kill-and-resume equivalence: a run that checkpoints, "dies",
restores, and continues must land bitwise on the state of a run that never
died — the TPU-world recovery story the reference lacks (SURVEY §5)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ddl25spring_tpu.models.mnist_cnn import MnistCnn
from ddl25spring_tpu.obs.spans import span
from ddl25spring_tpu.ops.losses import nll_loss
from ddl25spring_tpu.parallel.dp import make_dp_train_step
from ddl25spring_tpu.utils.checkpoint import Checkpointer
from ddl25spring_tpu.utils.mesh import make_mesh, replicated
from ddl25spring_tpu.utils.tracing import StepTimer


@pytest.fixture()
def train_setup():
    model = MnistCnn()
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 28, 28, 1))
    y = jax.random.randint(jax.random.PRNGKey(1), (16,), 0, 10)
    params = model.init(jax.random.PRNGKey(2), x[:1])["params"]

    def loss_fn(p, batch, key):
        out = model.apply(
            {"params": p}, batch[0], train=True, rngs={"dropout": key}
        )
        return nll_loss(out, batch[1])

    tx = optax.adam(1e-3)
    return loss_fn, tx, params, (x, y)


def test_kill_and_resume_equivalence(tmp_path, train_setup, devices8):
    loss_fn, tx, params, batch = train_setup
    mesh = make_mesh(devices8[:2], data=2)
    step = make_dp_train_step(loss_fn, tx, mesh, per_shard_rng=False)
    key = jax.random.PRNGKey(3)

    # uninterrupted run: 6 steps
    p_ref, o_ref = params, tx.init(params)
    for _ in range(6):
        p_ref, o_ref, _ = step(p_ref, o_ref, batch, key)

    # interrupted run: 3 steps, checkpoint, "crash", restore, 3 more
    ckpt = Checkpointer(tmp_path / "ckpt")
    p, o = params, tx.init(params)
    for _ in range(3):
        p, o, _ = step(p, o, batch, key)
    ckpt.save(2, {"params": p, "opt_state": o})
    ckpt.close()  # saves are async; the barrier stands in for process exit

    # the template pins device placement: restored slices land mesh-placed
    # (here replicated over the data axis, as the DP step expects)
    init_state = jax.device_put(
        {"params": params, "opt_state": tx.init(params)}, replicated(mesh)
    )
    restored, next_step = Checkpointer(tmp_path / "ckpt").restore_or_init(
        init_state
    )
    assert next_step == 3
    p, o = restored["params"], restored["opt_state"]
    for _ in range(3):
        p, o, _ = step(p, o, batch, key)

    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        p,
        p_ref,
    )


def test_zero_sharded_kill_and_resume(tmp_path, train_setup, devices8):
    """Kill-and-resume with ZeRO/FSDP-SHARDED state: the checkpoint holds
    [n, k] shard layouts, and the restore template (freshly re-sharded
    init state) pins each restored leaf back onto its NamedSharding(P
    ('data')) placement — the production resume path for sharded DP."""
    from ddl25spring_tpu.parallel.zero import (
        make_zero_dp_train_step, zero_shard_params,
    )

    loss_fn, tx, params, batch = train_setup
    mesh = make_mesh(devices8[:2], data=2)
    step = make_zero_dp_train_step(
        loss_fn, tx, mesh, params, per_shard_rng=False
    )
    key = jax.random.PRNGKey(4)

    # uninterrupted: 4 steps
    s_ref = zero_shard_params(params, mesh)
    o_ref = tx.init(s_ref)
    for _ in range(4):
        s_ref, o_ref, _ = step(s_ref, o_ref, batch, key)

    # interrupted: 2 steps, save, crash, restore via fresh template, 2 more
    ckpt = Checkpointer(tmp_path / "zckpt")
    s = zero_shard_params(params, mesh)
    o = tx.init(s)
    for _ in range(2):
        s, o, _ = step(s, o, batch, key)
    ckpt.save(1, {"shards": s, "opt_state": o})
    ckpt.close()

    from ddl25spring_tpu.utils.checkpoint import with_mesh_placement

    template = {"shards": zero_shard_params(params, mesh)}
    template["opt_state"] = tx.init(template["shards"])
    # opt-state scalars (Adam count) are born single-device; the template
    # must replicate them over the mesh or the resumed jit rejects the
    # mixed placement — the exact job of with_mesh_placement
    template = with_mesh_placement(template, mesh)
    restored, next_step = Checkpointer(tmp_path / "zckpt").restore_or_init(
        template
    )
    assert next_step == 2
    s2, o2 = restored["shards"], restored["opt_state"]
    # restored leaves carry the sharded placement, not single-device
    leaf = jax.tree.leaves(s2)[0]
    assert leaf.sharding.spec == jax.tree.leaves(template["shards"])[0].sharding.spec
    for _ in range(2):
        s2, o2, _ = step(s2, o2, batch, key)

    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        s2,
        s_ref,
    )


def test_restore_or_init_fresh_start(tmp_path, train_setup):
    _, tx, params, _ = train_setup
    ckpt = Checkpointer(tmp_path / "empty")
    state, next_step = ckpt.restore_or_init({"params": params})
    assert next_step == 0
    assert state["params"] is params


def test_max_to_keep_prunes(tmp_path):
    ckpt = Checkpointer(tmp_path / "ckpt", max_to_keep=2)
    state = {"w": jnp.arange(4.0)}
    for s in range(4):
        ckpt.save(s, state)
    assert ckpt.steps() == [2, 3]  # 0 and 1 pruned
    assert ckpt.latest_step() == 3
    restored = ckpt.restore(3)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.arange(4.0))


def test_step_timer_discards_warmup():
    t = StepTimer(warmup=1)
    x = jnp.ones((8, 8))
    for _ in range(4):
        with span("matmul"):
            x = x @ x.T
        t.tick(x)
    assert len(t.times) == 2  # 3 intervals, 1 warmup discarded
    assert t.steps_per_sec() > 0
