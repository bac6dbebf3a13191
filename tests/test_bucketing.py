"""Flat-bucket collectives + universal buffer donation: the equality and
memory contracts behind the bucketed/donated defaults.

Three pin families:

- **plan/pack units**: dtype-homogeneous greedy packing under the byte
  threshold, order preservation, pack/unpack round-trip;
- **path equality**: the bucketed DP/ZeRO-1/2/3 steps land exactly where
  the per-leaf paths land — DP *bitwise* (psum is elementwise, packing
  commutes with it), ZeRO within the suite's grad tolerance — and the
  scanned-LLaMA gather-prefetch ZeRO-3 step trains identically to
  replicated DP;
- **donation**: a donated step's compile-time peak HBM sits strictly
  below the undonated build of the same program (the aliased
  params+opt-state bytes), on the fake CPU mesh via ``memory_analysis``.

Collective-count shapes (O(n_buckets) vs O(n_leaves), the prefetch
while-loop) are pinned next to the other signatures in
``tests/test_xla_analytics.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ddl25spring_tpu.parallel import bucketing
from ddl25spring_tpu.parallel.dp import _tiny_mlp_workload, make_dp_train_step
from ddl25spring_tpu.parallel.zero import (
    _llama_workload,
    make_zero3_llama_train_step,
    make_zero_dp_train_step,
    make_zero_partitioned_train_step,
    zero_shard_llama_params,
    zero_shard_params,
    zero_unshard_llama_params,
    zero_unshard_params,
)
from ddl25spring_tpu.utils.compat import compiled_memory_stats
from ddl25spring_tpu.utils.mesh import make_mesh

# ------------------------------------------------------------- plan units


def test_plan_groups_by_dtype_and_threshold():
    tree = {
        "a": jnp.zeros((256,), jnp.float32),   # 1 KiB
        "b": jnp.zeros((256,), jnp.float32),   # 1 KiB
        "c": jnp.zeros((256,), jnp.int32),     # different dtype
        "d": jnp.zeros((512,), jnp.float32),   # 2 KiB - overflows 2 KiB cap
    }
    plan = bucketing.plan_buckets(tree, bucket_bytes=2 * 1024)
    # a+b fill the first f32 bucket exactly; d overflows into its own;
    # c buckets alone (dtype-homogeneous)
    assert plan.n_buckets == 3
    kinds = {
        tuple(sorted(plan.buckets[b])): str(plan.bucket_dtype(b))
        for b in range(plan.n_buckets)
    }
    leaves = sorted(tree)  # flatten order: a, b, c, d
    assert kinds[(leaves.index("a"), leaves.index("b"))] == "float32"
    assert kinds[(leaves.index("c"),)] == "int32"
    assert kinds[(leaves.index("d"),)] == "float32"


def test_plan_single_bucket_under_threshold_and_oversize_leaf():
    small = {"a": jnp.zeros((4, 4)), "b": jnp.zeros((8,))}
    assert bucketing.plan_buckets(small).n_buckets == 1
    big = {"x": jnp.zeros((64,)), "y": jnp.zeros((2048,))}  # y alone > cap
    plan = bucketing.plan_buckets(big, bucket_bytes=1024)
    assert plan.n_buckets == 2  # an oversize leaf still lands somewhere


def test_plan_backward_order_groups_by_readiness():
    """order="backward" walks the leaves in reversed flatten order —
    bucket 0 holds the LAST leaves (the first cotangents the backward
    produces) — and still round-trips pack/unpack exactly."""
    tree = {f"l{i}": jnp.zeros((256,), jnp.float32) for i in range(4)}
    fwd = bucketing.plan_buckets(tree, bucket_bytes=2 * 1024)
    bwd = bucketing.plan_buckets(tree, bucket_bytes=2 * 1024,
                                 order="backward")
    assert fwd.buckets == ((0, 1), (2, 3))
    assert bwd.buckets == ((3, 2), (1, 0))
    vals = {f"l{i}": jnp.arange(256.0) + i for i in range(4)}
    back = bwd.unpack(bwd.pack(vals))
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        vals, back,
    )
    with pytest.raises(ValueError, match="order"):
        bucketing.plan_buckets(tree, order="sideways")


def test_bucket_bytes_env_knob(monkeypatch):
    """DDL25_BUCKET_BYTES resolves through the sanctioned env boundary:
    AUTO -> the knob (0 = per-leaf), explicit values pass through, and
    None keeps meaning per-leaf as it has since PR 3."""
    monkeypatch.delenv("DDL25_BUCKET_BYTES", raising=False)
    assert bucketing.resolve_bucket_bytes(bucketing.AUTO) == (
        bucketing.DEFAULT_BUCKET_BYTES
    )
    monkeypatch.setenv("DDL25_BUCKET_BYTES", str(1 << 20))
    assert bucketing.resolve_bucket_bytes(bucketing.AUTO) == 1 << 20
    monkeypatch.setenv("DDL25_BUCKET_BYTES", "0")
    assert bucketing.resolve_bucket_bytes(bucketing.AUTO) is None
    assert bucketing.resolve_bucket_bytes(None) is None
    assert bucketing.resolve_bucket_bytes(0) is None
    assert bucketing.resolve_bucket_bytes(2048) == 2048
    monkeypatch.setenv("DDL25_BUCKET_BYTES", "not-bytes")
    with pytest.raises(ValueError):
        bucketing.resolve_bucket_bytes(bucketing.AUTO)


def test_pack_unpack_roundtrip_mixed_dtypes():
    key = jax.random.PRNGKey(0)
    tree = {
        "w": jax.random.normal(key, (3, 5)),
        "b": jnp.arange(7, dtype=jnp.int32),
        "s": jnp.float32(3.5).reshape(()),
    }
    plan = bucketing.plan_buckets(tree, bucket_bytes=64)
    back = plan.unpack(plan.pack(tree))
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        tree, back,
    )
    assert back["b"].dtype == jnp.int32
    assert back["s"].shape == ()


def test_bucketed_pmean_matches_per_leaf_bitwise(devices8):
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    mesh = make_mesh(devices8[:4], data=4)
    tree = {
        "w": jax.random.normal(jax.random.PRNGKey(0), (4, 33, 7)),
        "b": jax.random.normal(jax.random.PRNGKey(1), (4, 11)),
    }

    @partial(shard_map, mesh=mesh, in_specs=(P("data"),), out_specs=P())
    def both(t):
        local = jax.tree.map(lambda x: x[0], t)
        per_leaf = jax.tree.map(
            lambda x: jax.lax.pmean(x, "data"), local
        )
        bucketed = bucketing.bucketed_pmean(local, "data")
        return per_leaf, bucketed

    per_leaf, bucketed = jax.jit(both)(tree)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        per_leaf, bucketed,
    )


# ---------------------------------------------------------- path equality


@pytest.fixture(scope="module")
def mlp4(devices8):
    n = 4
    mesh = make_mesh(devices8[:n], data=n)
    params, loss_fn, batch, _ = _tiny_mlp_workload(n)
    key0 = jax.random.PRNGKey(7)
    params = jax.tree.map(
        lambda x: 0.1 * jax.random.normal(key0, x.shape, x.dtype), params
    )
    batch = (
        jax.random.normal(jax.random.PRNGKey(8), batch[0].shape),
        jax.random.normal(jax.random.PRNGKey(9), batch[1].shape),
    )
    return mesh, params, loss_fn, batch


def _dp_three_steps(mlp4, **kw):
    """Final (params, loss) of three DP steps of one ``make_dp_train_step``
    variant from the shared ``mlp4`` start."""
    mesh, params, loss_fn, batch = mlp4
    tx = optax.adam(1e-2)
    step = make_dp_train_step(loss_fn, tx, mesh, per_shard_rng=False, **kw)
    p, o = params, tx.init(params)
    for _ in range(3):
        p, o, loss = step(p, o, batch, jax.random.PRNGKey(0))
    return jax.device_get(p), float(loss)


def _assert_trees(check, a, b):
    jax.tree.map(lambda x, y: check(np.asarray(x), np.asarray(y)), a, b)


# the per-leaf path lets autodiff reduce: psum of cotangents the pmean'd
# loss already scaled by 1/n.  The bucketed paths take LOCAL grads and
# pmean them — psum, then divide.  Same value, different rounding: jax
# 0.9.0's CPU backend lands them one ulp apart (1.5e-8 absolute after
# three adam steps here), where jax 0.4.37 compiled both to one program
_ULP = dict(rtol=1e-6, atol=1e-7)


def test_dp_bucketed_equals_per_leaf_bitwise(mlp4):
    """The acceptance pin: DP's bucketed gradient path trains the
    per-leaf path's trajectory — the loss bitwise, the params to the
    rounding of one reduction (:data:`_ULP`).  That packing itself
    commutes with the elementwise psum, bit for bit, is
    ``test_bucketed_pmean_matches_per_leaf_bitwise``'s pin."""
    p1, l1 = _dp_three_steps(mlp4, bucket_bytes=None)
    p2, l2 = _dp_three_steps(mlp4)
    assert l1 == l2
    _assert_trees(
        lambda a, b: np.testing.assert_allclose(a, b, **_ULP), p1, p2
    )


def test_zero3_bucketed_equals_per_leaf(mlp4):
    mesh, params, loss_fn, batch = mlp4
    tx = optax.adam(1e-2)
    key = jax.random.PRNGKey(0)
    per_leaf = make_zero_dp_train_step(
        loss_fn, tx, mesh, params, per_shard_rng=False, bucket_bytes=None
    )
    bucketed = make_zero_dp_train_step(
        loss_fn, tx, mesh, params, per_shard_rng=False
    )
    s1, s2 = zero_shard_params(params, mesh), zero_shard_params(params, mesh)
    o1, o2 = tx.init(s1), tx.init(s2)
    for _ in range(3):
        s1, o1, l1 = per_leaf(s1, o1, batch, key)
        s2, o2, l2 = bucketed(s2, o2, batch, key)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-6, rtol=2e-6
        ),
        zero_unshard_params(jax.device_get(s1), params),
        zero_unshard_params(jax.device_get(s2), params),
    )


@pytest.mark.parametrize("stage", [1, 2])
def test_zero_stage12_bucketed_equals_per_leaf(stage, mlp4):
    mesh, params, loss_fn, batch = mlp4
    tx = optax.adam(1e-2)
    key = jax.random.PRNGKey(0)
    per_leaf = make_zero_partitioned_train_step(
        loss_fn, tx, mesh, params, stage=stage, per_shard_rng=False,
        bucket_bytes=None,
    )
    bucketed = make_zero_partitioned_train_step(
        loss_fn, tx, mesh, params, stage=stage, per_shard_rng=False
    )
    p1 = p2 = params
    o1 = tx.init(zero_shard_params(params, mesh))
    o2 = tx.init(zero_shard_params(params, mesh))
    for _ in range(3):
        p1, o1, l1 = per_leaf(p1, o1, batch, key)
        p2, o2, l2 = bucketed(p2, o2, batch, key)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-6, rtol=2e-6
        ),
        jax.device_get(p1), jax.device_get(p2),
    )


@pytest.mark.parametrize("prefetch", [True, False])
def test_zero3_llama_prefetch_equals_plain_dp(prefetch, devices8):
    """The scanned-LLaMA gather-prefetch ZeRO-3 step (double-buffered
    carry, layer i+1's all-gather issued before layer i's compute — and
    the prefetch=False remat variant that re-gathers in the backward)
    trains identically to replicated DP + the same Adam chain."""
    n = 4
    mesh = make_mesh(devices8[:n], data=n)
    cfg, params, loss_fn, tokens, _ = _llama_workload(n)
    tokens = jnp.asarray(
        jax.random.randint(jax.random.PRNGKey(1), tokens.shape, 0,
                           cfg.vocab_size)
    )
    tx = optax.adam(1e-2)
    key = jax.random.PRNGKey(0)

    dp = make_dp_train_step(loss_fn, tx, mesh, per_shard_rng=False)
    zp = make_zero3_llama_train_step(
        cfg, tx, mesh, prefetch=prefetch, per_shard_rng=False
    )

    p_ref, o_ref = params, tx.init(params)
    shards = zero_shard_llama_params(params, mesh)
    o_z = tx.init(shards)
    for _ in range(3):
        p_ref, o_ref, l_ref = dp(p_ref, o_ref, tokens, key)
        shards, o_z, l_z = zp(shards, o_z, tokens, key)
        np.testing.assert_allclose(float(l_ref), float(l_z), rtol=1e-5)
    restored = zero_unshard_llama_params(jax.device_get(shards), params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5
        ),
        jax.device_get(p_ref), restored,
    )


def test_zero3_llama_prefetch_holds_sharded_state(devices8):
    """The point of the layout: block params and Adam moments live in the
    per-layer [L, n, k] layout with 1/n per device."""
    n = 4
    mesh = make_mesh(devices8[:n], data=n)
    cfg, params, _, tokens, _ = _llama_workload(n)
    tx = optax.adam(1e-2)
    zp = make_zero3_llama_train_step(
        cfg, tx, mesh, per_shard_rng=False
    )
    shards = zero_shard_llama_params(params, mesh)
    o_z = tx.init(shards)
    shards, o_z, _ = zp(shards, o_z, tokens, jax.random.PRNGKey(0))
    wq = shards["blocks"]["wq"]
    assert wq.shape[:2] == (cfg.n_layers, n)
    local = [s for s in wq.addressable_shards if s.device == devices8[0]]
    assert sum(s.data.shape[1] for s in local) == 1  # one row of each layer
    mu = o_z[0].mu["blocks"]["wq"]
    assert mu.shape == wq.shape


# ------------------------------------------------------- overlapped backward


def test_dp_overlap_equals_per_leaf_bitwise(mlp4):
    """The PR-8 acceptance pin: the backward-overlapped DP step — each
    bucket's all-reduce emitted by its custom_vjp bwd rule, buckets in
    backward-readiness order — lands BITWISE where the sync bucketed
    step lands (psum is elementwise; issue order commutes with it), and
    so within :data:`_ULP` of per-leaf sync DP."""
    p0, l0 = _dp_three_steps(mlp4, bucket_bytes=None)
    p1, l1 = _dp_three_steps(mlp4)
    p2, l2 = _dp_three_steps(mlp4, overlap=True)
    assert l0 == l1 == l2
    _assert_trees(np.testing.assert_array_equal, p1, p2)
    _assert_trees(
        lambda a, b: np.testing.assert_allclose(a, b, **_ULP), p0, p2
    )


@pytest.mark.parametrize("stage", [1, 2, 3])
def test_zero_overlap_equals_sync(stage, mlp4):
    """Every ZeRO overlap variant — stage 1's bwd-issued all-reduce,
    stage 2's bwd-issued reduce-scatter (re-seated at row i of the
    padded layout), stage 3's backward-ordered gather plan — trains
    within the suite grad tolerance of its sync twin."""
    mesh, params, loss_fn, batch = mlp4
    tx = optax.adam(1e-2)
    key = jax.random.PRNGKey(0)
    if stage == 3:
        mk = lambda ov: make_zero_dp_train_step(  # noqa: E731
            loss_fn, tx, mesh, params, per_shard_rng=False, overlap=ov
        )
        s1, s2 = (
            zero_shard_params(params, mesh), zero_shard_params(params, mesh)
        )
        a1, a2 = s1, s2
    else:
        mk = lambda ov: make_zero_partitioned_train_step(  # noqa: E731
            loss_fn, tx, mesh, params, stage=stage, per_shard_rng=False,
            overlap=ov,
        )
        a1 = a2 = params
    o1 = tx.init(zero_shard_params(params, mesh))
    o2 = tx.init(zero_shard_params(params, mesh))
    sync, overlapped = mk(False), mk(True)
    for _ in range(3):
        a1, o1, l1 = sync(a1, o1, batch, key)
        a2, o2, l2 = overlapped(a2, o2, batch, key)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    unshard = (
        (lambda t: zero_unshard_params(jax.device_get(t), params))
        if stage == 3 else jax.device_get
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-6, rtol=2e-6
        ),
        unshard(a1), unshard(a2),
    )


def test_weight_avg_bucketed_sync_equals_per_leaf(mlp4):
    """The third DP variant: weight-aggregation DP's params-pmean rides
    the flat-bucket path now (it had stayed per-leaf through PR 3) —
    bitwise-equal, same oracle as the gradient path."""
    from ddl25spring_tpu.parallel.dp import (
        make_dp_weight_avg_step,
        stack_opt_state,
    )

    mesh, params, loss_fn, batch = mlp4
    tx = optax.sgd(0.1)
    key = jax.random.PRNGKey(0)
    per_leaf = make_dp_weight_avg_step(
        loss_fn, tx, mesh, per_shard_rng=False, bucket_bytes=None
    )
    bucketed = make_dp_weight_avg_step(
        loss_fn, tx, mesh, per_shard_rng=False
    )
    o1 = stack_opt_state(tx.init(params), 4)
    o2 = stack_opt_state(tx.init(params), 4)
    p1, p2 = params, params
    for _ in range(2):
        p1, o1, l1 = per_leaf(p1, o1, batch, key)
        p2, o2, l2 = bucketed(p2, o2, batch, key)
        assert float(l1) == float(l2)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        jax.device_get(p1), jax.device_get(p2),
    )


def test_overlap_requires_bucketing(mlp4):
    mesh, params, loss_fn, _ = mlp4
    tx = optax.sgd(0.1)
    with pytest.raises(ValueError, match="bucketed path"):
        make_dp_train_step(
            loss_fn, tx, mesh, bucket_bytes=None, overlap=True
        )
    with pytest.raises(ValueError, match="bucketed path"):
        make_zero_dp_train_step(
            loss_fn, tx, mesh, params, bucket_bytes=0, overlap=True
        )


# --------------------------------------------------------------- donation


def _peak(jitted, *args):
    stats = compiled_memory_stats(jitted.lower(*args).compile())
    assert stats is not None
    return stats["peak_hbm_bytes"], stats.get("alias_size_in_bytes", 0)


def test_dp_donated_peak_hbm_strictly_below_undonated(mlp4):
    """The acceptance pin: with params+opt-state donated, the compiled
    DP step's peak HBM drops strictly below the undonated build — by at
    least the aliased bytes' worth of double-residency."""
    mesh, params, loss_fn, batch = mlp4
    tx = optax.adam(1e-2)
    opt_state = tx.init(params)
    args = (params, opt_state, batch, jax.random.PRNGKey(0))
    undonated = make_dp_train_step(
        loss_fn, tx, mesh, per_shard_rng=False, donate=False
    )
    donated = make_dp_train_step(
        loss_fn, tx, mesh, per_shard_rng=False, donate=True
    )
    peak_u, alias_u = _peak(undonated, *args)
    peak_d, alias_d = _peak(donated, *args)
    assert alias_u == 0
    tree_bytes = sum(
        np.size(l) * np.asarray(l).dtype.itemsize
        for l in jax.tree.leaves((params, opt_state))
    )
    # params + both Adam moments alias in place...
    assert alias_d >= tree_bytes
    # ...and the peak drops by most of it (XLA keeps a small live-range
    # remainder, so "strictly below by >= half the aliased bytes" is the
    # robust form of the claim)
    assert peak_u - peak_d >= alias_d // 2
    assert peak_d < peak_u


@pytest.mark.parametrize("builder", ["zero3", "zero12", "llama-prefetch"])
def test_sharded_steps_donate_their_shards(builder, mlp4, devices8):
    """Every ZeRO variant's donated build aliases a nonzero byte count
    (the per-device shard of params/opt state) and never exceeds the
    undonated build's peak."""
    mesh, params, loss_fn, batch = mlp4
    tx = optax.adam(1e-2)
    if builder == "zero3":
        mk = lambda donate: make_zero_dp_train_step(  # noqa: E731
            loss_fn, tx, mesh, params, per_shard_rng=False, donate=donate
        )
        shards = zero_shard_params(params, mesh)
        args = (shards, tx.init(shards), batch, jax.random.PRNGKey(0))
    elif builder == "zero12":
        mk = lambda donate: make_zero_partitioned_train_step(  # noqa: E731
            loss_fn, tx, mesh, params, stage=2, per_shard_rng=False,
            donate=donate,
        )
        args = (
            params, tx.init(zero_shard_params(params, mesh)), batch,
            jax.random.PRNGKey(0),
        )
    else:
        cfg, lp, _, tokens, _ = _llama_workload(4)
        mk = lambda donate: make_zero3_llama_train_step(  # noqa: E731
            cfg, tx, mesh, per_shard_rng=False, donate=donate
        )
        shards = zero_shard_llama_params(lp, mesh)
        args = (shards, tx.init(shards), tokens, jax.random.PRNGKey(0))
    peak_u, _ = _peak(mk(False), *args)
    peak_d, alias_d = _peak(mk(True), *args)
    assert alias_d > 0
    assert peak_d < peak_u


def test_donation_invalidates_inputs_and_env_default(mlp4, monkeypatch):
    """Runtime contract: a donated call consumes its input buffers (the
    caller must rebind), and the builders' donate=None default follows
    DDL25_DONATE (the conftest sets 0 so oracle tests can re-use trees)."""
    mesh, params, loss_fn, batch = mlp4
    tx = optax.sgd(0.1)
    assert bucketing.donation_default() is False  # conftest opt-out
    monkeypatch.delenv("DDL25_DONATE", raising=False)
    assert bucketing.donation_default() is True
    step = make_dp_train_step(
        loss_fn, tx, mesh, per_shard_rng=False, donate=True
    )
    p = jax.tree.map(jnp.array, params)
    o = tx.init(p)
    p2, o2, _ = step(p, o, batch, jax.random.PRNGKey(0))
    with pytest.raises(RuntimeError):
        _ = np.asarray(jax.tree.leaves(p)[0]) + 0
    # the returned trees are live and feed the next step
    p3, _, _ = step(p2, o2, batch, jax.random.PRNGKey(0))
    assert np.isfinite(np.asarray(jax.tree.leaves(p3)[0])).all()


@pytest.mark.slow
def test_resnet_dp_donation_saves_param_and_momentum_bytes(devices8):
    """The bench workload's donation claim: ResNet-18 DP's donated build
    aliases ~params+momentum in place (the 44.7 MB HBM headroom at the
    real batch; scaled-down compile here)."""
    from ddl25spring_tpu.benchmarks import build_resnet_step

    step_d, params, opt_state, _ = build_resnet_step(
        devices8[:2], 2, 1, 1, 64, donate=True
    )
    step_u, _, _, _ = build_resnet_step(
        devices8[:2], 2, 1, 1, 64, donate=False
    )
    raw = (
        jnp.zeros((64, 32, 32, 3), jnp.uint8),
        jnp.zeros((64,), jnp.int32),
    )
    peak_u, _ = _peak(step_u, params, opt_state, raw)
    peak_d, alias_d = _peak(step_d, params, opt_state, raw)
    tree_bytes = sum(
        np.size(l) * np.asarray(l).dtype.itemsize
        for l in jax.tree.leaves((params, opt_state))
    )
    assert alias_d >= tree_bytes  # fp32 params + SGD momentum
    assert peak_d < peak_u
