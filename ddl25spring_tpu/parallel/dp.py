"""Data parallelism.

The reference implements DP as per-rank processes that, after ``backward()``,
flatten every gradient into one vector, ``all_reduce(SUM)`` it over gloo,
unflatten, divide by world size, and step
(``lab/tutorial_1b/DP/gradient_aggr/intro_DP_GA.py:53-66``;
the same flatten/all_reduce/unflatten appears per stage-group in
``lab/s01_b2_dp_pp.py:205-224``).

TPU-native design: ONE jitted SPMD program over a mesh ``data`` axis.  The
global batch is sharded over the axis; ``jax.lax.pmean`` of the gradient
pytree *is* the all_reduce+divide (no flattening — XLA fuses the collective
over the tree).  The optimizer update runs on replicated params outside the
``shard_map`` so any optax transform works unchanged.

Two aggregation flavors, matching the reference's two scripts:

- gradient aggregation (``make_dp_train_step``): pmean grads, then step —
  mathematically identical to large-batch serial SGD;
- weight aggregation (``make_dp_weight_avg_step``): step locally on local
  grads, then pmean the *weights*.  The reference's version is a silent no-op
  (``intro_DP_WA.py:57`` compares a tensor to None; ``:67`` rebinds the loop
  variable) — this implements the *intent*, i.e. real periodic weight
  averaging with per-replica optimizer state.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax import lax, shard_map
from jax.lax import pcast
from jax.sharding import Mesh, PartitionSpec as P
from ddl25spring_tpu.parallel import bucketing
from ddl25spring_tpu.parallel.bucketing import donate_argnums

# loss_fn(params, batch, key) -> scalar
LossFn = Callable[[Any, Any, jax.Array], jax.Array]


def make_train_step(
    loss_fn: LossFn,
    tx: optax.GradientTransformation,
    donate: bool | None = None,
    sentinel: bool | None = None,
):
    """Single-device jitted trainstep (parity: the centralized loop of
    ``lab/tutorial_1b/primer/intro.py:23-33``).  Serves as the serial side of
    the DP-equivalence oracle (SURVEY §4).

    ``sentinel`` (None = follow the global ``DDL25_SENTINELS`` flag at
    build time): in-step numerics sentinels via
    :func:`ddl25spring_tpu.obs.sentinels.guard` — zero-cost and
    HLO-identical when disabled, like every builder here."""
    from ddl25spring_tpu.obs import sentinels

    s_on, s_policy = sentinels.resolve(sentinel)

    @partial(jax.jit, donate_argnums=donate_argnums(donate))
    def step(params, opt_state, batch, key):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch, key)
        updates, new_state = tx.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        new_params, new_state = sentinels.guard(
            "serial", (new_params, new_state), loss=loss, grads=grads,
            params=params, updates=updates,
            fallback=(params, opt_state), enabled=s_on, policy=s_policy,
        )
        return new_params, new_state, loss

    return step


def make_dp_train_step(
    loss_fn: LossFn,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    axis: str = "data",
    per_shard_rng: bool = True,
    instrument: bool | None = None,
    bucket_bytes: int | float | None = bucketing.AUTO,
    donate: bool | None = None,
    sentinel: bool | None = None,
    overlap: bool = False,
):
    """Gradient-aggregation DP trainstep over ``mesh[axis]``.

    The batch pytree is sharded on its leading dim; params/opt_state are
    replicated.  ``per_shard_rng`` folds the shard index into the dropout key
    so different shards don't reuse dropout masks (set False for bitwise
    serial-equivalence tests with deterministic losses).

    ``instrument``: telemetry counters (loss + grad-norm via
    ``jax.debug.callback``, :mod:`ddl25spring_tpu.obs`) — ``None`` follows
    the global obs flag at build time, ``True``/``False`` hard-enable/
    -disable regardless of the flag.  Disabled,
    the step lowers to HLO identical to an uninstrumented build (pinned in
    ``tests/test_obs.py``); enabled, the callbacks cost one host transfer
    per step.

    ``bucket_bytes`` (default :data:`~ddl25spring_tpu.parallel.
    bucketing.AUTO` = the ``DDL25_BUCKET_BYTES`` knob, 4 MiB unset):
    launch the gradient all-reduce per flat dtype-homogeneous
    **bucket** instead of per pytree leaf — O(n_buckets) collective
    launches instead of O(n_leaves), same bytes on the wire
    (:mod:`ddl25spring_tpu.parallel.bucketing`).  Bitwise equal to the
    per-leaf path (``None``/``0`` restores it): psum is elementwise
    across devices, so packing commutes with it — pinned in
    ``tests/test_bucketing.py`` and visible in the compile-time
    collective inventory (``tests/test_xla_analytics.py``).

    ``overlap`` (requires bucketing): issue each bucket's all-reduce
    INSIDE the backward — params route through a per-bucket identity
    ``custom_vjp`` whose bwd rule reduces that bucket's cotangents the
    moment they exist, with buckets planned in backward-readiness
    order (:func:`~ddl25spring_tpu.parallel.bucketing.overlapped_grad_
    reduce`).  Bucket k's collective then depends only on layers >= k
    and can overlap layer k-1's backward compute instead of queueing
    after the full grad tree — the graft-lint H001 restructure.  Still
    bitwise-equal to the per-leaf path (same pinned oracle).

    ``donate`` (default on, see :func:`donate_argnums`): alias the
    params/opt-state inputs to the outputs so the update runs in place —
    the step's peak HBM drops by ~the params+opt bytes (pinned donated <
    undonated in ``tests/test_bucketing.py``).  Callers re-using the
    input trees after the call must pass ``donate=False``.

    ``sentinel`` (None = follow ``DDL25_SENTINELS`` at build time):
    in-step numerics sentinels — loss / grad global-norm / non-finite
    leaf flags / update-to-param ratio computed inside the compiled
    step, policy log/halt/skip on violation
    (:mod:`ddl25spring_tpu.obs.sentinels`).  Disabled, the HLO is
    byte-identical to an unguarded build (``tests/test_health.py``).
    """
    from ddl25spring_tpu import obs
    from ddl25spring_tpu.obs import sentinels

    instr = obs.enabled() if instrument is None else bool(instrument)
    s_on, s_policy = sentinels.resolve(sentinel)
    bucket_bytes = bucketing.resolve_bucket_bytes(bucket_bytes)
    if overlap and not bucket_bytes:
        raise ValueError(
            "overlap=True needs the bucketed path; pass a bucket_bytes "
            "threshold (or leave the AUTO default)"
        )

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), P(axis), P()),
        out_specs=(P(), P()),
    )
    def loss_and_pmean_grad(params, batch, key):
        if per_shard_rng:
            key = jax.random.fold_in(key, lax.axis_index(axis))

        if overlap:
            # overlapped path: the per-bucket pmean is emitted by each
            # bucket's custom_vjp bwd rule, INSIDE the backward dataflow
            # — value_and_grad returns already-reduced grads, and bucket
            # k's all-reduce is schedulable against layer k-1's backward.
            # The params go in INVARIANT: the barrier casts them varying
            # under its own rule, so the pmean'd grads come back typed
            # invariant, as out_specs=P() requires
            def reduced_loss(p):
                p = bucketing.overlapped_grad_reduce(p, axis, bucket_bytes)
                return loss_fn(p, batch, key)

            loss, grads = jax.value_and_grad(reduced_loss)(params)
            return lax.pmean(loss, axis), grads

        if bucket_bytes:
            # bucketed path: take LOCAL grads (params cast axis-varying so
            # autodiff inserts no per-leaf psum), then complete the
            # all_reduce+divide with ONE pmean per flat bucket — the same
            # arithmetic per element, O(n_buckets) launches
            lparams = pcast(params, axis, to="varying")
            loss, grads = jax.value_and_grad(loss_fn)(lparams, batch, key)
            grads = bucketing.bucketed_pmean(grads, axis, bucket_bytes)
            return lax.pmean(loss, axis), grads

        # The pmean sits INSIDE the differentiated function: its transpose
        # scales each shard's cotangent by 1/n, and shard_map's autodiff
        # psums the cotangent of the axis-invariant ``params`` — together
        # exactly the all_reduce(SUM)+divide of intro_DP_GA.py:63-66, over
        # ICI instead of gloo.
        def global_loss(params):
            return lax.pmean(loss_fn(params, batch, key), axis)

        loss, grads = jax.value_and_grad(global_loss)(params)
        return loss, grads

    @partial(jax.jit, donate_argnums=donate_argnums(donate))
    def step(params, opt_state, batch, key):
        loss, grads = loss_and_pmean_grad(params, batch, key)
        if instr:
            obs.counters.emit("dp.loss", loss, force=True)
            gnorm_sq = sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree.leaves(grads)
            )
            obs.counters.emit("dp.grad_norm", jnp.sqrt(gnorm_sq), force=True)
        updates, new_state = tx.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        new_params, new_state = sentinels.guard(
            "dp-overlap" if overlap else "dp", (new_params, new_state),
            loss=loss, grads=grads, params=params, updates=updates,
            fallback=(params, opt_state), enabled=s_on, policy=s_policy,
        )
        return new_params, new_state, loss

    return step


def make_dp_weight_avg_step(
    loss_fn: LossFn,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    axis: str = "data",
    per_shard_rng: bool = True,
    bucket_bytes: int | float | None = bucketing.AUTO,
    donate: bool | None = None,
    sentinel: bool | None = None,
):
    """Weight-aggregation DP: local step, then average weights over ``axis``.

    Per-replica optimizer state is represented as a stacked pytree with a
    leading ``[n_replicas, ...]`` dim sharded over ``axis`` (build it with
    :func:`stack_opt_state`).  Params enter and leave replicated (averaged
    every step, i.e. sync_every=1, the reference scripts' cadence).

    ``bucket_bytes`` (default :data:`~ddl25spring_tpu.parallel.
    bucketing.AUTO`): the weight-sync pmean launches per flat bucket
    instead of per leaf — the same O(n_buckets) collapse the gradient
    path got in PR 3, now on this variant's only collective (it had
    stayed per-leaf).  Bitwise-equal (elementwise pmean commutes with
    packing); ``None``/``0`` restores per-leaf.  There is no separate
    ``overlap`` mode here: the weight pmean's operand is the *updated*
    params, which depend on the entire backward + optimizer by
    construction — nothing earlier in the step could overlap it.

    ``sentinel``: in-step numerics sentinels
    (:mod:`ddl25spring_tpu.obs.sentinels`; cross-shard facts reduced
    over ``axis`` — the grad norm aggregates every replica's local
    gradient).
    """
    from ddl25spring_tpu.obs import sentinels

    s_on, s_policy = sentinels.resolve(sentinel)
    bucket_bytes = bucketing.resolve_bucket_bytes(bucket_bytes)
    n = mesh.shape[axis]

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P()),
        out_specs=(P(), P(axis), P()),
    )
    def local_step_then_avg(params, opt_state_stacked, batch, key):
        if per_shard_rng:
            key = jax.random.fold_in(key, lax.axis_index(axis))
        opt_state = jax.tree.map(lambda x: x[0], opt_state_stacked)
        # Mark params as axis-varying so autodiff yields LOCAL grads (no
        # implicit cross-shard psum) — each replica steps on its own data,
        # as each reference rank does before the weight sync.
        local_params = pcast(params, axis, to="varying")
        opt0 = opt_state
        loss, grads = jax.value_and_grad(loss_fn)(local_params, batch, key)
        updates, opt_state = tx.update(grads, opt_state, local_params)
        stepped = optax.apply_updates(local_params, updates)
        # the *intended* all_reduce-of-weights of intro_DP_WA.py:54-67
        # (per flat bucket when bucketing — one launch per bucket)
        avg_params = (
            bucketing.bucketed_pmean(stepped, axis, bucket_bytes)
            if bucket_bytes else lax.pmean(stepped, axis)
        )
        avg_params, opt_state = sentinels.guard(
            "dp-weight-avg", (avg_params, opt_state),
            loss=lax.pmean(loss, axis), grads=grads, params=local_params,
            updates=updates, fallback=(params, opt0), axis=axis,
            enabled=s_on, policy=s_policy,
        )
        return (
            avg_params,
            jax.tree.map(lambda x: x[None], opt_state),
            lax.pmean(loss, axis),
        )

    @partial(jax.jit, donate_argnums=donate_argnums(donate))
    def step(params, opt_state_stacked, batch, key):
        return local_step_then_avg(params, opt_state_stacked, batch, key)

    return step


def stack_opt_state(opt_state, n: int):
    """Replicate an optax state into the stacked ``[n, ...]`` layout used by
    :func:`make_dp_weight_avg_step`."""
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + jnp.shape(x)), opt_state)


# the bucket threshold describe() defaults to: small enough that the
# tiny-MLP tree plans MULTIPLE buckets under BOTH packing layouts —
# the flat grad plan (raw leaf bytes: 128/2048/512 B -> 3 buckets) and
# ZeRO's per-device row plan (k-row bytes: 32/512/128 B -> 2 buckets,
# still merging {b1,w1} so the O(buckets) < O(leaves) collapse stays
# pinned) — so the compile-time reports exercise the real multi-launch
# structure.  Single-bucket programs cannot show overlap slack (the
# one collective depends on the whole backward), and the sched
# verifier's overlap-vs-sync pins need the windows to exist.
# Deliberately NOT the runtime default (4 MiB) nor the env knob:
# signatures must not drift with ambient state.
DESCRIBE_BUCKET_BYTES = 560


def _tiny_mlp_workload(n_shards: int):
    """The minimal DP workload the compile-time analytics lower: a 2-layer
    MLP regression step whose gradient tree has a known byte size (shared
    shape with :func:`ddl25spring_tpu.parallel.zero.describe` so the
    DP/ZeRO signatures compare like for like)."""
    d_in, d_h, d_out = 16, 32, 4
    params = {
        "w1": jnp.zeros((d_in, d_h), jnp.float32),
        "b1": jnp.zeros((d_h,), jnp.float32),
        "w2": jnp.zeros((d_h, d_out), jnp.float32),
    }

    def loss_fn(p, batch, key):
        del key
        x, y = batch
        h = jnp.tanh(x @ p["w1"] + p["b1"])
        return jnp.mean((h @ p["w2"] - y) ** 2)

    batch = (
        jnp.zeros((8 * n_shards, d_in), jnp.float32),
        jnp.zeros((8 * n_shards, d_out), jnp.float32),
    )
    param_bytes = sum(
        l.size * l.dtype.itemsize for l in jax.tree.leaves(params)
    )
    return params, loss_fn, batch, param_bytes


def describe(
    mesh: Mesh,
    axis: str = "data",
    bucketed: bool = True,
    overlap: bool = False,
    bucket_bytes: int | float | None = None,
):
    """Registry hook for :mod:`ddl25spring_tpu.obs.xla_analytics`: the
    lowerable DP train step + example inputs + the analytic collective
    signature.

    Plain gradient-aggregation DP's compiled signature is the tightest of
    all strategies: the ONLY cross-device traffic is the gradient
    all-reduce — total all-reduce payload == grad bytes (+ scalar loss
    reductions), every group over the data axis, and no other collective
    kind at all.  A stray all-gather here means someone broke the
    replicated-params invariant.  With bucketing (the default) the
    non-scalar all-reduce additionally collapses to ONE site per grad
    bucket, and the step is compiled donated — params+opt state aliased
    in place, pinned via ``memory`` / ``donation`` below.

    ``overlap=True`` describes the strategy ``dp-overlap``: the same
    signature (identical bytes, bucket-count launch ceiling, data-axis
    grouping, donation floor) with every bucket's all-reduce emitted by
    the backward's per-bucket ``custom_vjp`` — the restructure is a
    scheduling/dataflow change, so any signature drift here means the
    overlap machinery changed what goes on the wire, not just when.

    ``bucket_bytes`` pins an explicit threshold (the bucket-sweep
    harness); the default is :data:`DESCRIBE_BUCKET_BYTES` — a
    multi-bucket plan over the tiny tree, deliberately NOT the env
    knob, so compile-time signature pins never drift with ambient
    ``DDL25_BUCKET_BYTES``.
    """
    if overlap and not bucketed:
        raise ValueError("overlap describes the bucketed DP path only")
    n = mesh.shape[axis]
    params, loss_fn, batch, param_bytes = _tiny_mlp_workload(n)
    tx = optax.sgd(0.1)
    bb = (
        (bucket_bytes or DESCRIBE_BUCKET_BYTES) if bucketed
        else None
    )
    step = make_dp_train_step(
        loss_fn, tx, mesh, axis=axis, per_shard_rng=False, instrument=False,
        bucket_bytes=bb, donate=True, overlap=overlap,
    )
    n_buckets = (
        bucketing.plan_buckets(
            params, bb, order="backward" if overlap else "forward"
        ).n_buckets
        if bucketed else None
    )
    opt_state = tx.init(params)
    state_bytes = sum(
        jnp.size(l) * jnp.result_type(l).itemsize
        for l in jax.tree.leaves(opt_state)
    )
    expected = {
        "scalar_bytes": 64,
        "all-reduce": {
            "min_bytes": param_bytes,
            "max_bytes": param_bytes + 256,
            "axes": [axis],
        },
        "forbidden": [
            "all-gather", "reduce-scatter", "collective-permute",
            "all-to-all",
        ],
        # donated params + SGD state alias in place (grad buckets and the
        # batch still need fresh buffers, hence "at least params+state")
        "donation": {"min_saved_bytes": param_bytes + state_bytes},
        # budget pin: the tiny-MLP DP program fits comfortably under 4 MiB
        # on every jax this repo supports; 10x headroom over measured
        # (~0.4 MiB) so only a real regression trips it
        "memory": {"max_peak_hbm_bytes": 4 * 1024 * 1024},
    }
    if bucketed:
        # n_buckets grad all-reduce sites + at most 2 scalar loss pmeans
        expected["all-reduce"]["max_count"] = n_buckets + 2
    return {
        "fn": step,
        "args": (params, opt_state, batch, jax.random.PRNGKey(0)),
        "lowered": "train_step",
        "meta": {
            "param_bytes": param_bytes,
            "grad_bytes": param_bytes,
            "n_param_leaves": len(jax.tree.leaves(params)),
            **({"n_buckets": n_buckets} if bucketed else {}),
            **({"bucket_bytes": bb} if bucketed else {}),
            **({"overlap": True} if overlap else {}),
        },
        "expected": expected,
    }
