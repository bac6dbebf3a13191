"""GPipe pipeline for HETEROGENEOUS stages (e.g. ResNet-18 DP+PP).

:mod:`ddl25spring_tpu.parallel.pipeline` handles the reference's LLaMA
workload, where every pipeline stage is the same block structure and the
stage split is a reshape of stacked layer params.  Convolutional nets
(the BASELINE.json benchmark config, ResNet-18/CIFAR-10 DP+PP) break both
assumptions the homogeneous path relies on:

- per-stage params have *different* pytree structures/shapes, so they cannot
  be stacked ``[S, ...]`` and sharded over the ``stage`` axis;
- stage-boundary activations have *different* shapes (channel/spatial dims
  change at downsampling groups), so a single ``ppermute`` buffer of one
  shape cannot carry them.

Design here (same one-program SPMD GPipe schedule as the LLaMA path):

- per-stage params are passed **replicated**; each device executes only its
  own stage's compute via ``lax.switch`` on the stage index.  The memory cost
  (every chip holds all stages' params) is the price of heterogeneity and is
  irrelevant at ResNet-18 scale; the FLOPs and activation memory — the actual
  pipeline motivation — still split S ways.
- boundary activations travel in one flat ``[mb, max_boundary]`` buffer;
  each stage unflattens its input slice and flattens/zero-pads its output.
  The ``ppermute`` hop between stages is then shape-uniform.
- microbatch grad accumulation, the bubble schedule (T = M + S - 1 ticks),
  and the DP dimension are identical to the homogeneous path: losses sum in
  the scan carry and the cotangent ``psum`` over ``data`` is automatic.

Parity anchors: the reference's microbatch schedule + per-stage-group
all_reduce (``lab/s01_b1_microbatches.py:66-178``,
``lab/s01_b2_dp_pp.py:93-227``), retargeted at the conv benchmark workload.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import optax
from jax import lax, shard_map
from jax.lax import pcast

from ddl25spring_tpu.parallel.bucketing import donate_argnums
from jax.sharding import Mesh, PartitionSpec as P

Params = Any
StageFn = Callable[[Params, jax.Array], jax.Array]


def _flat_size(shape: Sequence[int]) -> int:
    return math.prod(shape[1:])  # per-example size (dim 0 is the microbatch)


def make_het_pipeline_loss(
    stage_fns: Sequence[StageFn],
    loss_fn: Callable[[jax.Array, Any], jax.Array],
    in_shape: Sequence[int],
    boundary_shapes: Sequence[Sequence[int]],
    mesh: Mesh,
    num_microbatches: int,
    inject_fn: Callable[[Any], jax.Array] | None = None,
    stage_axis: str = "stage",
    data_axis: str | None = None,
    compute_dtype: Any = jnp.float32,
    instrument: bool | None = None,
):
    """Build ``loss(params_per_stage, batch) -> scalar`` for S heterogeneous
    stages on the mesh ``stage`` axis.

    ``stage_fns[i]``: ``(params_i, x_i) -> x_{i+1}`` with ``x_0`` of shape
    ``in_shape`` and ``x_{i+1}`` of shape ``boundary_shapes[i]`` (all shapes
    include the microbatch dim; ``boundary_shapes[-1]`` is the final output
    fed to ``loss_fn(final, mb_batch)``).

    ``batch`` is a pytree whose leaves lead with the global batch dim
    ``B = num_microbatches * mb * data_parallelism``; ``inject_fn(mb_batch)``
    extracts stage-0's input (default: the batch's ``"x"`` entry).

    ``instrument`` (None = follow the global :mod:`ddl25spring_tpu.obs`
    flag at build time; True/False hard-enable/-disable): each scan tick marks its host arrival time via
    ``jax.debug.callback`` so tick cadence (and thus the realized GPipe
    bubble) is observable without any device profiler; the schedule shape
    (S, M) is recorded as static counters.  Disabled, the lowered HLO is
    identical to an uninstrumented build.
    """
    from ddl25spring_tpu import obs

    S = len(stage_fns)
    assert S == mesh.shape[stage_axis], (S, mesh.shape)
    M = num_microbatches
    instr = obs.enabled() if instrument is None else bool(instrument)
    if instr:
        obs.counters.add_static("pipeline.num_stages", S)
        obs.counters.add_static("pipeline.num_microbatches", M)
        obs.counters.add_static(
            "pipeline.bubble_fraction_gpipe",
            obs.gpipe_bubble_fraction(S, M),
        )
    shapes = [tuple(in_shape)] + [tuple(s) for s in boundary_shapes]
    mb = shapes[0][0]
    assert all(s[0] == mb for s in shapes), f"microbatch dims differ: {shapes}"
    # stage 0 injects its input from the batch and never reads the buffer,
    # so only the S boundary shapes size the ppermute hop
    buf_elems = max(_flat_size(s) for s in shapes[1:])
    inject = inject_fn if inject_fn is not None else (lambda b: b["x"])

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), P(None, data_axis)),
        out_specs=P(),
    )
    def pipelined(params, batch_mb):
        s = lax.axis_index(stage_axis)
        axes = (stage_axis,) + ((data_axis,) if data_axis else ())
        # varying copies so the transpose's cotangent psum over the stage
        # axis runs uniformly on every device (not inside switch branches)
        vparams = pcast(params, axes, to="varying")

        def pack(x):
            flat = x.reshape(mb, -1).astype(compute_dtype)
            pad = buf_elems - flat.shape[1]
            return jnp.pad(flat, ((0, 0), (0, pad))) if pad else flat

        def unpack(buf, shape):
            return buf[:, : _flat_size(shape)].reshape(shape)

        def tick(carry, t):
            buf_in, loss_sum = carry
            if instr:
                # host arrival time of each tick: the cadence estimator
                # for the realized (not just analytic) bubble fraction
                obs.counters.mark("pipeline.tick", t, force=True)
            mb_t = jax.tree.map(lambda x: x[jnp.minimum(t, M - 1)], batch_mb)

            def branch(i):
                def run(buf):
                    if i == 0:
                        x = inject(mb_t).astype(compute_dtype)
                    else:
                        x = unpack(buf, shapes[i])
                    return pack(stage_fns[i](vparams[i], x))

                return run

            buf_out = lax.switch(s, [branch(i) for i in range(S)], buf_in)

            done = t - (S - 1)
            mb_done = jax.tree.map(
                lambda x: x[jnp.clip(done, 0, M - 1)], batch_mb
            )
            loss_mb = lax.cond(
                jnp.logical_and(s == S - 1, done >= 0),
                lambda b, y: loss_fn(unpack(b, shapes[S]).astype(jnp.float32), y),
                lambda b, y: pcast(jnp.float32(0.0), axes, to="varying"),
                buf_out,
                mb_done,
            )

            outgoing = lax.ppermute(
                buf_out, stage_axis, [(i, (i + 1) % S) for i in range(S)]
            )
            return (outgoing, loss_sum + loss_mb), None

        carry0 = (
            pcast(
                jnp.zeros((mb, buf_elems), compute_dtype), axes, to="varying"
            ),
            pcast(jnp.float32(0.0), axes, to="varying"),
        )
        (_, loss_sum), _ = lax.scan(tick, carry0, jnp.arange(M + S - 1))

        total = lax.psum(loss_sum, stage_axis) / M
        if data_axis is not None:
            total = lax.pmean(total, data_axis)
        return total

    def loss(params, batch):
        leaves = jax.tree.leaves(batch)
        B = leaves[0].shape[0]
        if B % M:
            raise ValueError(f"batch {B} not divisible by {M} microbatches")
        batch_mb = jax.tree.map(
            lambda x: x.reshape((M, B // M) + x.shape[1:]), batch
        )
        return pipelined(params, batch_mb)

    return loss


def make_het_pipeline_train_step(
    stage_fns: Sequence[StageFn],
    loss_fn: Callable[[jax.Array, Any], jax.Array],
    in_shape: Sequence[int],
    boundary_shapes: Sequence[Sequence[int]],
    tx: optax.GradientTransformation,
    mesh: Mesh,
    num_microbatches: int,
    donate: bool | None = None,
    sentinel: bool | None = None,
    **kw,
):
    """Jitted DPxPP train step over heterogeneous stages (the benchmark
    topology: 2-stage ResNet pipeline x DP with microbatches).
    ``sentinel`` opts into the in-step numerics sentinels
    (:mod:`ddl25spring_tpu.obs.sentinels`)."""
    from ddl25spring_tpu.obs import sentinels

    s_on, s_policy = sentinels.resolve(sentinel)
    pipe_loss = make_het_pipeline_loss(
        stage_fns, loss_fn, in_shape, boundary_shapes, mesh,
        num_microbatches, **kw,
    )

    @partial(jax.jit, donate_argnums=donate_argnums(donate))
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(pipe_loss)(params, batch)
        updates, new_state = tx.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        new_params, new_state = sentinels.guard(
            "het_pipeline", (new_params, new_state), loss=loss,
            grads=grads, params=params, updates=updates,
            fallback=(params, opt_state), enabled=s_on, policy=s_policy,
        )
        return new_params, new_state, loss

    return step


def describe(
    mesh: Mesh,
    num_microbatches: int = 4,
    stage_axis: str = "stage",
    data_axis: str | None = None,
):
    """Registry hook for :mod:`ddl25spring_tpu.obs.xla_analytics`: a
    minimal 2-stage heterogeneous pipeline (two dense stages with
    *different* boundary widths — the property the flat-buffer packing
    exists for) + its analytic collective signature: one
    ``collective-permute`` of the padded boundary buffer per tick,
    ``M + S - 1`` ticks per direction, plus the all-reduce autodiff puts
    on the grads of params this (replicated) path holds whole on every
    stage."""
    if data_axis is None and "data" in mesh.axis_names:
        data_axis = "data"
    S = mesh.shape[stage_axis]
    if S != 2:
        raise ValueError(f"het_pipeline describe() ships 2 stages, got {S}")
    M = num_microbatches
    dp = mesh.shape[data_axis] if data_axis else 1
    mb, d_in, d_mid, d_out = 2, 8, 16, 4
    params = (
        {"w": jnp.zeros((d_in, d_mid), jnp.float32)},
        {"w": jnp.zeros((d_mid, d_out), jnp.float32)},
    )
    stage_fns = [
        lambda p, x: jnp.tanh(x @ p["w"]),
        lambda p, x: x @ p["w"],
    ]
    loss = make_het_pipeline_loss(
        stage_fns,
        lambda out, b: jnp.mean((out - b["y"]) ** 2),
        (mb, d_in), [(mb, d_mid), (mb, d_out)],
        mesh, M, stage_axis=stage_axis, data_axis=data_axis,
        instrument=False,
    )
    B = M * mb * dp
    batch = {
        "x": jnp.zeros((B, d_in), jnp.float32),
        "y": jnp.zeros((B, d_out), jnp.float32),
    }
    fn = jax.jit(jax.value_and_grad(loss))
    T = M + S - 1
    hops = 2 * T  # the scan transpose replays the ring in reverse
    buf_bytes = mb * max(d_mid, d_out) * 4  # padded flat boundary, f32
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    return {
        "fn": fn,
        "args": (params, batch),
        "lowered": "value_and_grad",
        "meta": {
            "num_stages": S,
            "num_microbatches": M,
            "ticks": T,
            "boundary_bytes": buf_bytes,
            "bubble_fraction": (S - 1) / T,
        },
        "expected": {
            "scalar_bytes": 64,
            "collective-permute": {
                "min_count": hops,
                "max_count": hops + T,
                "axes": [stage_axis],
            },
            # every stage holds every param: each grad leaf is summed
            # over the stage axis once (and over data under DP x PP),
            # beside the scalar loss reductions
            "all-reduce": {
                "min_bytes": param_bytes,
                "max_bytes": (2 if data_axis else 1) * param_bytes + 256,
                "axes": [stage_axis] + ([data_axis] if data_axis else []),
            },
            "forbidden": ["all-to-all", "reduce-scatter", "all-gather"],
            "memory": {"max_peak_hbm_bytes": 8 * 1024 * 1024},
        },
    }


# ------------------------------------------------------------------ sharded


def pack_stage_params(stage_params: Sequence[Params]):
    """Pack per-stage pytrees (different structures/shapes) into one
    ``[S, maxP]`` fp32 buffer shardable over the mesh ``stage`` axis.

    The replicated path above holds EVERY stage's params on every device —
    fine at ResNet-18 scale, but it abandons the parameter-memory scaling
    that is pipeline parallelism's point.  Flattening each stage to a padded
    flat vector restores it: per-device param (and optimizer-state) memory
    is ``max_s |params_s|`` instead of ``sum_s |params_s|``, at the price of
    the padding waste ``maxP - |params_s|`` (zero for balanced splits).

    Returns ``(stacked [S, maxP], metas)``; ``metas[i]`` reconstructs stage
    ``i``'s pytree inside its ``lax.switch`` branch via
    :func:`unpack_stage_params` (static slicing — free under XLA).
    """
    metas, flats = [], []
    for p in stage_params:
        leaves, treedef = jax.tree.flatten(p)
        shapes = [jnp.shape(l) for l in leaves]
        dtypes = [jnp.result_type(l) for l in leaves]
        flat = (
            jnp.concatenate([jnp.ravel(l).astype(jnp.float32) for l in leaves])
            if leaves else jnp.zeros((0,), jnp.float32)
        )
        flats.append(flat)
        metas.append((treedef, shapes, dtypes))
    max_p = max(f.shape[0] for f in flats)
    stacked = jnp.stack([jnp.pad(f, (0, max_p - f.shape[0])) for f in flats])
    return stacked, metas


def unpack_stage_params(flat: jax.Array, meta) -> Params:
    """Rebuild one stage's pytree from its flat row (inverse of
    :func:`pack_stage_params` for a single stage)."""
    treedef, shapes, dtypes = meta
    leaves, off = [], 0
    for shape, dt in zip(shapes, dtypes):
        n = math.prod(shape)
        leaves.append(flat[off : off + n].reshape(shape).astype(dt))
        off += n
    return jax.tree.unflatten(treedef, leaves)


def make_sharded_het_pipeline_loss(
    stage_fns: Sequence[StageFn],
    param_metas: Sequence[Any],
    loss_fn: Callable[[jax.Array, Any], jax.Array],
    in_shape: Sequence[int],
    boundary_shapes: Sequence[Sequence[int]],
    mesh: Mesh,
    num_microbatches: int,
    inject_fn: Callable[[Any], jax.Array] | None = None,
    stage_axis: str = "stage",
    data_axis: str | None = None,
    compute_dtype: Any = jnp.float32,
):
    """Stage-SHARDED variant of :func:`make_het_pipeline_loss`:
    ``loss(stacked_params [S, maxP], batch)`` with the param buffer sharded
    over the ``stage`` axis — each device materializes only its own stage's
    branch inside the switch.  Schedule, boundary packing, and DP semantics
    are identical to the replicated path (equivalence asserted in
    ``tests/test_het_pipeline.py``)."""
    S = len(stage_fns)
    assert S == mesh.shape[stage_axis], (S, mesh.shape)
    M = num_microbatches
    shapes = [tuple(in_shape)] + [tuple(s) for s in boundary_shapes]
    mb = shapes[0][0]
    assert all(s[0] == mb for s in shapes), f"microbatch dims differ: {shapes}"
    buf_elems = max(_flat_size(s) for s in shapes[1:])
    inject = inject_fn if inject_fn is not None else (lambda b: b["x"])

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(stage_axis), P(None, data_axis)),
        out_specs=P(),
    )
    def pipelined(stacked, batch_mb):
        s = lax.axis_index(stage_axis)
        axes = (stage_axis,) + ((data_axis,) if data_axis else ())
        # local row [1, maxP] -> [maxP]; already stage-varying (sharded in),
        # pcast over data so cotangents stay per-shard until the final pmean
        local_flat = stacked[0]
        if data_axis:
            local_flat = pcast(local_flat, data_axis, to="varying")

        def pack(x):
            flat = x.reshape(mb, -1).astype(compute_dtype)
            pad = buf_elems - flat.shape[1]
            return jnp.pad(flat, ((0, 0), (0, pad))) if pad else flat

        def unpack(buf, shape):
            return buf[:, : _flat_size(shape)].reshape(shape)

        def tick(carry, t):
            buf_in, loss_sum = carry
            mb_t = jax.tree.map(lambda x: x[jnp.minimum(t, M - 1)], batch_mb)

            def branch(i):
                def run(buf):
                    p_i = unpack_stage_params(local_flat, param_metas[i])
                    if i == 0:
                        x = inject(mb_t).astype(compute_dtype)
                    else:
                        x = unpack(buf, shapes[i])
                    return pack(stage_fns[i](p_i, x))

                return run

            buf_out = lax.switch(s, [branch(i) for i in range(S)], buf_in)

            done = t - (S - 1)
            mb_done = jax.tree.map(
                lambda x: x[jnp.clip(done, 0, M - 1)], batch_mb
            )
            loss_mb = lax.cond(
                jnp.logical_and(s == S - 1, done >= 0),
                lambda b, y: loss_fn(unpack(b, shapes[S]).astype(jnp.float32), y),
                lambda b, y: pcast(jnp.float32(0.0), axes, to="varying"),
                buf_out,
                mb_done,
            )

            outgoing = lax.ppermute(
                buf_out, stage_axis, [(i, (i + 1) % S) for i in range(S)]
            )
            return (outgoing, loss_sum + loss_mb), None

        carry0 = (
            pcast(
                jnp.zeros((mb, buf_elems), compute_dtype), axes, to="varying"
            ),
            pcast(jnp.float32(0.0), axes, to="varying"),
        )
        (_, loss_sum), _ = lax.scan(tick, carry0, jnp.arange(M + S - 1))

        total = lax.psum(loss_sum, stage_axis) / M
        if data_axis is not None:
            total = lax.pmean(total, data_axis)
        return total

    def loss(stacked, batch):
        leaves = jax.tree.leaves(batch)
        B = leaves[0].shape[0]
        if B % M:
            raise ValueError(f"batch {B} not divisible by {M} microbatches")
        batch_mb = jax.tree.map(
            lambda x: x.reshape((M, B // M) + x.shape[1:]), batch
        )
        return pipelined(stacked, batch_mb)

    return loss


def make_sharded_het_pipeline_train_step(
    stage_fns: Sequence[StageFn],
    stage_params: Sequence[Params],
    loss_fn: Callable[[jax.Array, Any], jax.Array],
    in_shape: Sequence[int],
    boundary_shapes: Sequence[Sequence[int]],
    tx: optax.GradientTransformation,
    mesh: Mesh,
    num_microbatches: int,
    stage_axis: str = "stage",
    donate: bool | None = None,
    sentinel: bool | None = None,
    **kw,
):
    """Stage-sharded DPxPP train step: params AND optimizer state live
    sharded ``[S, maxP]`` over the stage axis (optax transforms are
    elementwise on the flat buffer, so sharding propagates through the
    update).  Returns ``(step, stacked_params, opt_state)`` with both
    pytrees placed on the mesh.  ``sentinel`` opts into the in-step
    numerics sentinels (:mod:`ddl25spring_tpu.obs.sentinels`)."""
    from jax.sharding import NamedSharding

    from ddl25spring_tpu.obs import sentinels

    s_on, s_policy = sentinels.resolve(sentinel)
    stacked, metas = pack_stage_params(stage_params)
    stacked = jax.device_put(stacked, NamedSharding(mesh, P(stage_axis)))
    pipe_loss = make_sharded_het_pipeline_loss(
        stage_fns, metas, loss_fn, in_shape, boundary_shapes, mesh,
        num_microbatches, stage_axis=stage_axis, **kw,
    )
    opt_state = tx.init(stacked)
    @partial(jax.jit, donate_argnums=donate_argnums(donate))
    def step(stacked, opt_state, batch):
        loss, grads = jax.value_and_grad(pipe_loss)(stacked, batch)
        updates, new_state = tx.update(grads, opt_state, stacked)
        new_stacked = optax.apply_updates(stacked, updates)
        new_stacked, new_state = sentinels.guard(
            "het_pipeline-sharded", (new_stacked, new_state), loss=loss,
            grads=grads, params=stacked, updates=updates,
            fallback=(stacked, opt_state), enabled=s_on, policy=s_policy,
        )
        return new_stacked, new_state, loss

    return step, stacked, opt_state
