"""ZeRO/FSDP-style data parallelism: params, grads, and optimizer state
sharded over the ``data`` axis.

The reference's DP keeps a FULL model replica + optimizer state on every
rank (`lab/tutorial_1b/DP/gradient_aggr/intro_DP_GA.py:35-39` records every
parameter's size on each process; the all_reduce at `:63` moves the whole
flattened gradient vector).  That replication is the memory ceiling of data
parallelism.  The TPU-native memory-scaled variant implemented here is the
ZeRO-3 / FSDP decomposition expressed as explicit ICI collectives inside
one ``shard_map``:

- every parameter leaf is flattened, padded to a multiple of ``n`` and
  stored as an ``[n, k]`` array sharded over the data axis — each device
  holds ``1/n`` of the model and ``1/n`` of the optimizer state;
- the forward ``lax.all_gather``\\ s the shards into full parameters
  (tiled, riding ICI) *inside the differentiated function*, so XLA's
  transpose of the gather is exactly the backward's reduce-scatter;
- gradients leave the backward as ``lax.psum_scatter`` shards — the
  all_reduce of ``intro_DP_GA.py:63-66`` split into its reduce-scatter
  half, keeping the summed gradient sharded instead of replicated;
- the optax update runs on the local ``[1, k]`` shard only (elementwise
  optimizers — SGD/momentum/Adam/AdamW — are positionwise, so updating
  shards equals updating the full tensor).

Per-device memory for params + grads + opt state drops from ``O(P)`` to
``O(P/n)``; per-step communication is the same 2 x P words an all_reduce
costs (one all_gather + one reduce-scatter), on the MXU-free ICI path.

Padding note: padded tail entries see zero gradients and zero moments, so
they stay exactly zero through any optax chain whose update at (g=0, m=0,
v=0) is 0 (true for SGD/momentum/Adam/AdamW without weight decay on the
padding — weight decay also keeps an exact zero at zero).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax, shard_map
from jax.lax import pcast
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from ddl25spring_tpu.parallel import bucketing
from ddl25spring_tpu.parallel.bucketing import donate_argnums

LossFn = Callable[[Any, Any, jax.Array], jax.Array]


def _leaf_meta(leaf, n: int):
    size = int(np.prod(leaf.shape)) if leaf.shape else 1
    k = -(-size // n)  # ceil
    return size, k


def _row_plan(params_template, n: int, bucket_bytes, order: str = "forward"):
    """Bucket plan over the padded ``[n, k]`` row layout: leaf ``i``
    contributes its ``k_i`` shard-row elements per device (not its raw
    size), so one packed bucket row is exactly what one device holds of
    the bucket's leaves.  ``order="backward"`` plans buckets in
    backward-readiness order (the overlapped variants)."""
    ks = [
        _leaf_meta(leaf, n)[1]
        for leaf in jax.tree.leaves(params_template)
    ]
    return bucketing.plan_buckets(
        params_template, bucket_bytes, sizes=ks, order=order
    )


def _pack_rows(plan, tree):
    """Pytree of ``[r, k_i]`` leaves -> one ``[r, K_b]`` buffer per bucket
    (column concat in bucket order; ``r`` is 1 inside shard_map, ``n``
    outside)."""
    leaves = plan.treedef.flatten_up_to(tree)
    return [
        leaves[idxs[0]] if len(idxs) == 1
        else jnp.concatenate([leaves[i] for i in idxs], axis=1)
        for idxs in plan.buckets
    ]


def _split_rows(plan, bufs):
    """Inverse of :func:`_pack_rows`: ``[r, K_b]`` buffers -> pytree of
    ``[r, k_i]`` leaves."""
    leaves: list = [None] * plan.n_leaves
    for b, idxs in enumerate(plan.buckets):
        for i, off in zip(idxs, plan.offsets(b)):
            leaves[i] = bufs[b][:, off:off + plan.sizes[i]]
    return plan.treedef.unflatten(leaves)


def _overlap_row_scatter_reduce(plan, n: int, axis: str):
    """Bucket reducer for :func:`~ddl25spring_tpu.parallel.bucketing.
    overlap_wrap` on ZeRO-2's row layout: pack the bucket's cotangents
    into the padded ``[n, K]`` row buffer and ``psum_scatter`` straight
    into this device's row — the stage-2 collective, emitted inside the
    backward the moment the bucket's cotangents exist.

    A ``custom_vjp`` bwd must return full-leaf-shaped cotangents, so
    the scattered ``[1, K]`` row is re-seated at row ``i`` of a zeroed
    ``[n, K]`` buffer and unpacked; rows != i are zero and the step
    slices row ``i`` straight back out (the zeros never reach the
    optimizer).  The padded container is transient bwd-local memory —
    the same order as the cotangents feeding it — so stage 2 keeps its
    O(P/n) *persistent* grad state."""

    def reduce_bucket(cts, b):
        idxs = plan.buckets[b]
        i = lax.axis_index(axis)
        rows = []
        for ct, li in zip(cts, idxs):
            k = plan.sizes[li]
            size = int(np.prod(plan.shapes[li])) if plan.shapes[li] else 1
            rows.append(
                jnp.pad(ct.reshape(-1), (0, n * k - size)).reshape(n, k)
            )
        buf = rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=1)
        shard = lax.psum_scatter(
            buf, axis, scatter_dimension=0, tiled=True
        ) / n
        padded = lax.dynamic_update_slice_in_dim(
            jnp.zeros_like(buf), shard, i, 0
        )
        out = []
        for li, off in zip(idxs, plan.offsets(b)):
            size = int(np.prod(plan.shapes[li])) if plan.shapes[li] else 1
            out.append(
                padded[:, off:off + plan.sizes[li]]
                .reshape(-1)[:size]
                .reshape(plan.shapes[li])
                .astype(plan.dtypes[li])
            )
        return tuple(out)

    return reduce_bucket


def _gather_bucketed(plan, shards, axis: str, n: int):
    """One tiled all-gather per BUCKET of packed ``[1, k]`` shard rows ->
    the full param pytree.  The single gather site both ZeRO-3 steps ride
    (whole-tree in :func:`make_zero_dp_train_step`, per-layer/outer in
    :func:`make_zero3_llama_train_step`); its transpose is one
    psum_scatter per bucket — the O(n_leaves) -> O(n_buckets) collapse
    the analytics pin."""
    bufs = [
        lax.all_gather(b.reshape(-1), axis, tiled=True)
        .reshape(n, plan.bucket_size(i))
        for i, b in enumerate(_pack_rows(plan, shards))
    ]
    return _unpack_full(plan, bufs)


def _unpack_full(plan, bufs2d):
    """Gathered ``[n, K_b]`` bucket buffers -> the ORIGINAL param pytree
    (shapes/dtypes from the plan's template): per leaf, slice its column
    band, drop the padding tail, reshape."""
    leaves: list = [None] * plan.n_leaves
    for b, idxs in enumerate(plan.buckets):
        for i, off in zip(idxs, plan.offsets(b)):
            shape = plan.shapes[i]
            size = int(np.prod(shape)) if shape else 1
            leaves[i] = (
                bufs2d[b][:, off:off + plan.sizes[i]]
                .reshape(-1)[:size]
                .reshape(shape)
                .astype(plan.dtypes[i])
            )
    return plan.treedef.unflatten(leaves)


def zero_shard_params(params, mesh: Mesh, axis: str = "data"):
    """Pack a replicated param pytree into the sharded ``[n, k]`` layout.

    Returns a pytree with the same treedef whose leaves are ``[n, k]``
    arrays laid out with ``NamedSharding(mesh, P(axis))`` — device ``i``
    holds rows ``i`` only.
    """
    n = mesh.shape[axis]

    def pack(leaf):
        leaf = jnp.asarray(leaf)
        size, k = _leaf_meta(leaf, n)
        flat = jnp.pad(leaf.reshape(-1), (0, n * k - size))
        return jax.device_put(
            flat.reshape(n, k), NamedSharding(mesh, P(axis))
        )

    return jax.tree.map(pack, params)


def zero_unshard_params(shards, template):
    """Inverse of :func:`zero_shard_params` — gather ``[n, k]`` shards back
    into the template's shapes/dtypes (host-side; for eval/checkpoint)."""

    def unpack(s, t):
        size = int(np.prod(t.shape)) if t.shape else 1
        return s.reshape(-1)[:size].reshape(t.shape).astype(t.dtype)

    return jax.tree.map(unpack, shards, template)


def make_zero_dp_train_step(
    loss_fn: LossFn,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    params_template,
    axis: str = "data",
    per_shard_rng: bool = True,
    num_microbatches: int = 1,
    instrument: bool | None = None,
    bucket_bytes: int | float | None = bucketing.AUTO,
    donate: bool | None = None,
    sentinel: bool | None = None,
    overlap: bool = False,
):
    """Build the fully-sharded trainstep.

    ``step(param_shards, opt_state, batch, key)`` where ``param_shards``
    comes from :func:`zero_shard_params`, ``opt_state = tx.init(param_
    shards)`` (state leaves inherit the ``[n, k]`` sharding; scalar leaves
    like Adam's ``count`` stay replicated), and ``batch`` is sharded on its
    leading dim.  Numerically ≡ :func:`~ddl25spring_tpu.parallel.dp.
    make_dp_train_step` up to fp32 reduction order (asserted in
    ``tests/test_zero.py``).

    Caveat: the optax chain runs on LOCAL shards, so a transform needing a
    global reduction over the whole tree would compute shard-local norms.
    For global-norm clipping use :func:`zero_clip_by_global_norm` (one psum
    of shard square-norms makes it exact); other global-reduction
    transforms need the same treatment before they are safe here.

    ``instrument`` (None = follow the global :mod:`ddl25spring_tpu.obs`
    flag at build time; True/False hard-enable/-disable): records the per-step ICI volume — the bytes one
    device gathers (all_gather) and reduce-scatters per step, derived from
    the padded ``[n, k]`` layout at trace time — as static counters, and
    emits the per-step loss via ``jax.debug.callback``.  Disabled, the
    lowered HLO is identical to an uninstrumented build.

    ``num_microbatches > 1`` adds FSDP-style gradient accumulation: the
    per-device batch is split along its leading dim and scanned — each
    microbatch re-gathers params and reduce-scatters its gradient (the
    standard FSDP schedule), while the accumulator holds only the SHARDED
    ``[1, k]`` grads, so peak memory stays O(P/n) + one microbatch of
    activations.  The update is mathematically the full-batch update
    (mean of microbatch means; same reference semantics as
    ``s01_b1_microbatches.py``'s ``.grad`` accumulation).

    ``bucket_bytes`` (default 4 MiB): gather the forward's parameters per
    flat dtype-homogeneous BUCKET instead of per leaf — and, because the
    gather sits inside the differentiated function, the backward's
    reduce-scatters collapse identically: O(n_buckets) collective
    launches instead of O(n_leaves), same bytes.  ``None``/``0`` restores
    the per-leaf path; both paths are numerically identical (the packed
    psum is elementwise — equality pinned in ``tests/test_bucketing.py``,
    launch counts pinned in ``tests/test_xla_analytics.py``).

    ``donate`` (default on, :func:`~ddl25spring_tpu.parallel.dp.
    donate_argnums`): alias the param-shard and opt-state inputs to the
    outputs — the sharded update runs in place.

    ``overlap`` (requires bucketing): ZeRO-3's backward reduce-scatter
    is *already* emitted inside the backward — it is the transpose of
    the forward's in-function all-gather, so XLA places each bucket's
    scatter exactly where that bucket's cotangents complete.  What the
    sync plan forfeits is bucket COMPOSITION: flatten-order buckets mix
    early and late layers, so a scatter still waits for its earliest
    member — the very end of the backward.  ``overlap=True`` plans the
    row buckets in backward-readiness order (reversed flatten: bucket 0
    = the last layers, ready first), letting each scatter fire while
    earlier layers' backward still computes.  Identical bytes, launch
    count, and numerics (the scatter sums elementwise regardless of
    packing order — pinned in ``tests/test_bucketing.py``).

    ``sentinel`` (None = follow ``DDL25_SENTINELS`` at build time):
    in-step numerics sentinels over the SHARDED gradient tree — the
    square-norm and non-finite flags psum/pmax over ``axis`` before
    crossing to the host, so the facts are global even though each
    device only ever holds its ``[1, k]`` rows
    (:mod:`ddl25spring_tpu.obs.sentinels`).
    """
    from ddl25spring_tpu import obs
    from ddl25spring_tpu.obs import sentinels as _sentinels

    s_on, s_policy = _sentinels.resolve(sentinel)

    if num_microbatches < 1:
        raise ValueError(f"num_microbatches must be >= 1, got {num_microbatches}")
    bucket_bytes = bucketing.resolve_bucket_bytes(bucket_bytes)
    if overlap and not bucket_bytes:
        raise ValueError(
            "overlap=True needs the bucketed path; pass a bucket_bytes "
            "threshold (or leave the AUTO default)"
        )
    n = mesh.shape[axis]
    shapes = jax.tree.map(lambda l: jnp.shape(l), params_template)
    dtypes = jax.tree.map(lambda l: jnp.result_type(l), params_template)

    instr = obs.enabled() if instrument is None else bool(instrument)
    if instr:
        # per-device ICI volume per step, from the padded [n, k] layout:
        # each device RECEIVES (n-1)/n of every gathered leaf and sends
        # the mirror amount in the backward's reduce-scatter; the
        # microbatch loop re-runs both per microbatch
        gathered = sum(
            n * _leaf_meta(leaf, n)[1] * jnp.result_type(leaf).itemsize
            for leaf in jax.tree.leaves(params_template)
        )
        wire = gathered * (n - 1) // n * num_microbatches
        obs.counters.add_static("zero.allgather_bytes_per_step", wire)
        obs.counters.add_static("zero.reduce_scatter_bytes_per_step", wire)
        obs.counters.add_static("zero.params_bytes_gathered", gathered)

    plan = (
        _row_plan(params_template, n, bucket_bytes,
                  order="backward" if overlap else "forward")
        if bucket_bytes else None
    )

    def gather_full(shards):
        if plan is not None:
            return _gather_bucketed(plan, shards, axis, n)

        def g(s, shape, dtype):
            full = lax.all_gather(s.reshape(-1), axis, tiled=True)
            size = int(np.prod(shape)) if shape else 1
            return full[:size].reshape(shape).astype(dtype)

        return jax.tree.map(g, shards, shapes, dtypes)

    def step(param_shards, opt_state, batch, key):
        # param-shaped [n, k] leaves are sharded; scalars/counters replicated.
        # The rank-2 heuristic is validated: any 2-D state leaf whose shape
        # is not one of the [n, k] shard layouts (e.g. a transform carrying
        # its own matrix state) would be mis-sharded, so reject it loudly.
        shard_shapes = {jnp.shape(l) for l in jax.tree.leaves(param_shards)}
        state_specs = _opt_state_specs(opt_state, shard_shapes, axis)

        @partial(
            shard_map,
            mesh=mesh,
            in_specs=(P(axis), state_specs, P(axis), P()),
            out_specs=(P(axis), state_specs, P()),
        )
        def sharded_step(pshards, ostate, b, key):
            if per_shard_rng:
                key = jax.random.fold_in(key, lax.axis_index(axis))

            def grads_for(mb, mb_key):
                # all_gather inside the differentiated fn: its transpose IS
                # the backward reduce-scatter, so full grads never
                # materialize as a replicated tree — jax.grad w.r.t. the
                # [1, k] shards.
                def shard_loss(pshards):
                    params = gather_full(pshards)
                    return loss_fn(params, mb, mb_key)

                return jax.value_and_grad(shard_loss)(pshards)

            if num_microbatches == 1:
                loss, gshards = grads_for(b, key)
            else:
                # FSDP grad accumulation: scan microbatches; carry holds
                # only SHARDED [1, k] grad sums
                per_dev = jax.tree.leaves(b)[0].shape[0]
                if per_dev % num_microbatches:
                    raise ValueError(
                        f"per-device batch {per_dev} not divisible by "
                        f"num_microbatches={num_microbatches}"
                    )
                mbs = jax.tree.map(
                    lambda x: x.reshape(
                        (num_microbatches, x.shape[0] // num_microbatches)
                        + x.shape[1:]
                    ),
                    b,
                )

                def acc_body(carry, mb_i):
                    mb, i = mb_i
                    l, g = grads_for(mb, jax.random.fold_in(key, i))
                    return jax.tree.map(jnp.add, carry, (l, g)), None

                zero_g = jax.tree.map(jnp.zeros_like, pshards)
                # the per-microbatch loss is device-varying; the init must
                # match (VMA typing under shard_map)
                zero_l = pcast(jnp.float32(0.0), axis, to="varying")
                (loss, gshards), _ = lax.scan(
                    acc_body,
                    (zero_l, zero_g),
                    (mbs, jnp.arange(num_microbatches)),
                )
                loss = loss / num_microbatches
                gshards = jax.tree.map(
                    lambda g: g / num_microbatches, gshards
                )

            # the transpose of the tiled all_gather is a psum_scatter: each
            # device's gshards already hold the cross-device SUM of local
            # grads for its rows; ÷n converts sum to the DP mean
            gshards = jax.tree.map(lambda g: g / n, gshards)
            if instr:
                obs.counters.emit("zero.loss", lax.pmean(loss, axis), force=True)
            updates, new_state = tx.update(gshards, ostate, pshards)
            new_shards = optax.apply_updates(pshards, updates)
            new_shards, new_state = _sentinels.guard(
                "zero3-overlap" if overlap else "zero3",
                (new_shards, new_state),
                loss=lax.pmean(loss, axis), grads=gshards, params=pshards,
                updates=updates, fallback=(pshards, ostate), axis=axis,
                enabled=s_on, policy=s_policy,
            )
            return new_shards, new_state, lax.pmean(loss, axis)

        return sharded_step(param_shards, opt_state, batch, key)

    return jax.jit(step, donate_argnums=donate_argnums(donate))


def _opt_state_specs(
    opt_state, shard_shapes: set, axis: str,
    stacked_shapes: set | frozenset = frozenset(),
):
    """PartitionSpecs for an optax state over the ``[n, k]`` shard layout:
    param-shaped 2-D leaves shard over ``axis``, scalars/counters stay
    replicated; any other 2-D leaf is rejected loudly (shared by the
    ZeRO-3 step and the ZeRO-1/2 steps below).  ``stacked_shapes`` names
    the layer-stacked ``[L, n, k]`` layouts of the scanned-LLaMA ZeRO-3
    step — those shard their middle dim (``P(None, axis)``); any other
    3-D leaf is rejected like a mismatched 2-D one."""

    def spec_for(leaf):
        if jnp.ndim(leaf) == 3 and stacked_shapes:
            if jnp.shape(leaf) not in stacked_shapes:
                raise ValueError(
                    f"optimizer state carries a 3-D leaf of shape "
                    f"{jnp.shape(leaf)} that matches no [L, n, k] stacked "
                    f"shard {sorted(stacked_shapes)}; this optax transform "
                    "is not supported by the ZeRO sharding heuristic"
                )
            return P(None, axis)
        if jnp.ndim(leaf) != 2:
            return P()
        if jnp.shape(leaf) not in shard_shapes:
            raise ValueError(
                f"optimizer state carries a 2-D leaf of shape "
                f"{jnp.shape(leaf)} that matches no [n, k] param shard "
                f"{sorted(shard_shapes)}; this optax transform is not "
                "supported by the ZeRO sharding heuristic"
            )
        return P(axis)

    return jax.tree.map(spec_for, opt_state)


def make_zero_partitioned_train_step(
    loss_fn: LossFn,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    params_template,
    axis: str = "data",
    stage: int = 2,
    per_shard_rng: bool = True,
    bucket_bytes: int | float | None = bucketing.AUTO,
    donate: bool | None = None,
    sentinel: bool | None = None,
    overlap: bool = False,
):
    """ZeRO stage-1/2 trainstep: REPLICATED params, SHARDED optimizer
    state (and, at stage 2, sharded reduced gradients).

    Where :func:`make_zero_dp_train_step` (the stage-3/FSDP decomposition)
    shards the parameters themselves, the classic ZeRO-1 and ZeRO-2
    optimizer-sharding stages keep a full replica for the forward/backward
    and partition only the *update*: each device owns rows ``i`` of every
    leaf's padded ``[n, k]`` layout (the same layout as
    :func:`zero_shard_params`, so ``opt_state = tx.init(zero_shard_params
    (params, mesh))`` serves all three stages) and steps only its shard.
    The two stages differ in how the summed gradient reaches the shard —
    exactly the collective signature the compile-time analytics pin
    (``tests/test_xla_analytics.py``):

    - **stage 1**: ``all-reduce`` the full gradient (every device holds
      the sum, as in plain DP), then slice the local rows — grad memory
      stays O(P), comms = all_reduce(P) + all_gather(P);
    - **stage 2**: ``reduce-scatter`` the packed gradient straight into
      the local rows — grad memory O(P/n), comms = reduce_scatter(P) +
      all_gather(P), the 2P-words total of a plain all_reduce.

    Both finish by all-gathering the updated rows back into replicated
    params (the partitioner inserts one all-gather per leaf for the
    ``P(axis) -> P()`` resharding).  Update math is elementwise-optimizer
    exact: identical to replicated DP + the same optax chain (asserted
    against :func:`~ddl25spring_tpu.parallel.dp.make_dp_train_step` in
    ``tests/test_zero.py``).  ``step(params, opt_state, batch, key)``
    with ``params`` replicated and ``opt_state`` in the ``[n, k]``
    sharded layout.

    ``bucket_bytes`` (default :data:`~ddl25spring_tpu.parallel.
    bucketing.AUTO` = the ``DDL25_BUCKET_BYTES`` knob, 4 MiB unset)
    routes all three collectives through
    flat buckets — the stage-1 all-reduce, the stage-2 reduce-scatter,
    and the updated-rows all-gather each launch once per BUCKET instead
    of once per leaf; ``donate`` (default on) aliases params/opt-state in
    place; ``sentinel`` opts into the in-step numerics sentinels over
    the sharded grad rows (:mod:`ddl25spring_tpu.obs.sentinels`).

    ``overlap`` (requires bucketing): emit the gradient collective
    inside the backward instead of after the full grad tree — params
    route through a per-bucket ``custom_vjp`` (:func:`~ddl25spring_tpu.
    parallel.bucketing.overlap_wrap`, buckets planned in backward-
    readiness order) whose bwd rule issues the bucket's **all-reduce**
    (stage 1) or **reduce-scatter into this device's rows** (stage 2)
    as soon as that bucket's cotangents exist, overlappable with the
    remaining backward compute.  The update-side all-gather is
    unchanged (it depends on the optimizer output by construction).
    Numerics match the post-hoc path within elementwise-reduction
    equality — pinned in ``tests/test_bucketing.py``.
    """
    from ddl25spring_tpu.obs import sentinels as _sentinels

    s_on, s_policy = _sentinels.resolve(sentinel)
    if stage not in (1, 2):
        raise ValueError(f"stage must be 1 or 2, got {stage} "
                         "(stage 3 is make_zero_dp_train_step)")
    bucket_bytes = bucketing.resolve_bucket_bytes(bucket_bytes)
    if overlap and not bucket_bytes:
        raise ValueError(
            "overlap=True needs the bucketed path; pass a bucket_bytes "
            "threshold (or leave the AUTO default)"
        )
    n = mesh.shape[axis]
    treedef = jax.tree.structure(params_template)
    metas = [
        _leaf_meta(jnp.asarray(l), n)
        for l in jax.tree.leaves(params_template)
    ]
    shard_shapes = {(n, k) for _, k in metas}
    plan = (
        _row_plan(params_template, n, bucket_bytes,
                  order="backward" if overlap else "forward")
        if bucket_bytes else None
    )
    # the overlapped stage-1 all-reduce packs the RAW cotangents (flat
    # concat, no row padding) — same wire bytes as the grads themselves
    flat_plan = (
        bucketing.plan_buckets(params_template, bucket_bytes,
                               order="backward")
        if overlap and stage == 1 else None
    )

    def pack(leaf, meta):
        size, k = meta
        flat = jnp.pad(leaf.reshape(-1), (0, n * k - size))
        return flat.reshape(n, k)

    def pack_tree(tree):
        return treedef.unflatten([
            pack(l, m) for l, m in zip(treedef.flatten_up_to(tree), metas)
        ])

    def step(params, opt_state, batch, key):
        state_specs = _opt_state_specs(opt_state, shard_shapes, axis)
        out_params_specs = (
            tuple(P(axis) for _ in plan.buckets) if plan is not None
            else P(axis)
        )

        @partial(
            shard_map,
            mesh=mesh,
            in_specs=(P(), state_specs, P(axis), P()),
            out_specs=(out_params_specs, state_specs, P()),
        )
        def sharded_step(params, ostate, b, key):
            if per_shard_rng:
                key = jax.random.fold_in(key, lax.axis_index(axis))
            # local copies -> local grads (an invariant param's
            # autodiff would psum each leaf's cotangent pre-emptively)
            lparams = pcast(params, axis, to="varying")
            i = lax.axis_index(axis)
            if overlap:
                # the grad collective fires inside the backward, per
                # bucket: value_and_grad hands back the REDUCED grads
                # (stage 1: the pmean'd full tree; stage 2: this
                # device's scattered rows re-seated at row i of a
                # zeroed padded layout) and the slice below is local
                def reduced_loss(q):
                    if stage == 1:
                        q = bucketing.overlap_wrap(
                            q, flat_plan,
                            bucketing.flat_bucket_reduce(flat_plan, axis),
                        )
                    else:
                        q = bucketing.overlap_wrap(
                            q, plan,
                            _overlap_row_scatter_reduce(plan, n, axis),
                        )
                    return loss_fn(q, b, key)

                loss, grads = jax.value_and_grad(reduced_loss)(lparams)
                gshard = jax.tree.map(
                    lambda g: lax.dynamic_slice_in_dim(g, i, 1, 0),
                    pack_tree(grads),
                )
            else:
                loss, grads = jax.value_and_grad(loss_fn)(lparams, b, key)
                g2d = pack_tree(grads)
                if plan is not None:
                    # packed [n, K_b] bucket buffers: one collective per
                    # bucket below instead of one per leaf
                    g2d = _pack_rows(plan, g2d)

                def reduce_to_shard(g):
                    if stage == 1:
                        # sum everywhere (grad memory O(P)), then take
                        # our rows
                        return lax.dynamic_slice_in_dim(
                            lax.pmean(g, axis), i, 1, 0
                        )
                    # stage 2: reduce straight into our rows (grad mem
                    # O(P/n))
                    return lax.psum_scatter(
                        g, axis, scatter_dimension=0, tiled=True
                    ) / n

                if plan is not None:
                    gshard = _split_rows(
                        plan, [reduce_to_shard(g) for g in g2d]
                    )
                else:
                    gshard = jax.tree.map(reduce_to_shard, g2d)
            pshard = jax.tree.map(
                lambda p: lax.dynamic_slice_in_dim(p, i, 1, 0),
                pack_tree(params),
            )
            updates, new_state = tx.update(gshard, ostate, pshard)
            new_shard = optax.apply_updates(pshard, updates)
            new_shard, new_state = _sentinels.guard(
                f"zero{stage}-overlap" if overlap else f"zero{stage}",
                (new_shard, new_state),
                loss=lax.pmean(loss, axis), grads=gshard, params=pshard,
                updates=updates, fallback=(pshard, ostate), axis=axis,
                enabled=s_on, policy=s_policy,
            )
            if plan is not None:
                # hand the updated rows back bucket-packed so the
                # P(axis) -> P() resharding below gathers per bucket
                new_shard = tuple(_pack_rows(plan, new_shard))
            return new_shard, new_state, lax.pmean(loss, axis)

        new_shards, opt_state, loss = sharded_step(
            params, opt_state, batch, key
        )
        # P(axis) -> P(): the partitioner lowers this resharding to ONE
        # all-gather per leaf (per BUCKET when packing) — the explicit
        # gather half of the stage-1/2 comms story
        gathered = jax.lax.with_sharding_constraint(
            new_shards, NamedSharding(mesh, P())
        )
        if plan is not None:
            params = _unpack_full(plan, list(gathered))
        else:
            params = zero_unshard_params(gathered, params)
        return params, opt_state, loss

    return jax.jit(step, donate_argnums=donate_argnums(donate))


# ------------------------------------------------- scanned-LLaMA prefetch


def zero_shard_llama_params(params, mesh: Mesh, axis: str = "data"):
    """LLaMA param pytree -> the per-LAYER ZeRO-3 shard layout the
    prefetch step consumes: each stacked ``blocks`` leaf ``[L, ...]``
    packs layer-wise into ``[L, n, k]`` (``P(None, axis)`` — device ``i``
    holds row ``i`` of every layer), the outer leaves (embed/ln_f/
    unembed) into the ordinary ``[n, k]`` of :func:`zero_shard_params`.
    Layer-wise packing is what lets the scan gather ONE layer's params
    at a time instead of the whole stack."""
    n = mesh.shape[axis]

    def pack_block(leaf):
        leaf = jnp.asarray(leaf)
        L = leaf.shape[0]
        size = int(np.prod(leaf.shape[1:])) if leaf.shape[1:] else 1
        k = -(-size // n)
        flat = jnp.pad(leaf.reshape(L, -1), ((0, 0), (0, n * k - size)))
        return jax.device_put(
            flat.reshape(L, n, k), NamedSharding(mesh, P(None, axis))
        )

    out = dict(params)
    out["blocks"] = jax.tree.map(pack_block, params["blocks"])
    outer = {k: v for k, v in params.items() if k != "blocks"}
    out.update(zero_shard_params(outer, mesh, axis))
    return out


def zero_unshard_llama_params(shards, template):
    """Inverse of :func:`zero_shard_llama_params` (host-side; for eval/
    checkpoint interop with the replicated model)."""

    def unpack_block(s, t):
        L = s.shape[0]
        size = int(np.prod(t.shape[1:])) if t.shape[1:] else 1
        return (
            s.reshape(L, -1)[:, :size].reshape(t.shape).astype(t.dtype)
        )

    out = dict(shards)
    out["blocks"] = jax.tree.map(
        unpack_block, shards["blocks"], template["blocks"]
    )
    outer_t = {k: v for k, v in template.items() if k != "blocks"}
    out.update(zero_unshard_params(
        {k: shards[k] for k in outer_t}, outer_t
    ))
    return out


# ------------------------------------------- serving weight streaming
#
# The serve engine's ZeRO-3 weight streaming (PR 18) rides the SAME
# [L, n, k] per-layer row layout and bucketed gather the zero3-prefetch
# train step uses — these helpers expose that path for a forward-only
# consumer: blocks stay resident as rows (param_bytes/n per chip), each
# decode position gathers ONE full layer at a time (double-buffered by
# the caller's scan), and the outer leaves (embed/ln_f/unembed) stay
# replicated because sampling is a global decision over tiny logits.


def stream_block_plan(block_tmpl, n: int,
                      bucket_bytes: int | float = bucketing.AUTO):
    """The per-LAYER bucket plan streamed serving gathers through: built
    over one layer's leaf shapes (the stacked ``[L, ...]`` dims dropped),
    with slot sizes in padded ``[n, k]`` shard rows — identical to the
    plan :func:`make_zero3_llama_train_step` scans with."""
    bucket_bytes = bucketing.resolve_bucket_bytes(bucket_bytes)
    if not bucket_bytes:
        raise ValueError(
            "weight streaming is bucketed by construction; bucket_bytes "
            "must be a positive threshold (DDL25_BUCKET_BYTES=0 cannot "
            "apply here)"
        )
    layer_tmpl = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype), block_tmpl
    )
    return _row_plan(layer_tmpl, n, bucket_bytes)


def zero_stream_llama_params(params, mesh: Mesh, axis: str = "model"):
    """LLaMA params -> the serving STREAM layout: each stacked
    ``blocks`` leaf ``[L, ...]`` packs layer-wise into ``[L, n, k]``
    rows at ``P(None, axis)`` (device ``i`` holds row ``i`` of every
    layer — ``blocks_bytes/n`` resident per chip), while the outer
    leaves stay REPLICATED (unlike :func:`zero_shard_llama_params`'s
    ``[n, k]`` outer shards: serving reads embed/unembed every token
    and keeps sampling a global decision)."""
    n = mesh.shape[axis]

    def pack_block(leaf):
        leaf = jnp.asarray(leaf)
        L = leaf.shape[0]
        size = int(np.prod(leaf.shape[1:])) if leaf.shape[1:] else 1
        k = -(-size // n)
        flat = jnp.pad(leaf.reshape(L, -1), ((0, 0), (0, n * k - size)))
        return jax.device_put(
            flat.reshape(L, n, k), NamedSharding(mesh, P(None, axis))
        )

    out = {
        k: (jax.tree.map(pack_block, v) if k == "blocks"
            else jax.device_put(v, NamedSharding(mesh, P())))
        for k, v in params.items()
    }
    return out


def stream_param_specs(params, axis: str = "model"):
    """The shard_map in/out specs matching
    :func:`zero_stream_llama_params`'s placement: block rows
    ``P(None, axis)`` (dim 1 of the ``[L, n, k]`` row layout), outer
    leaves replicated."""
    return {
        k: (jax.tree.map(lambda _: P(None, axis), v) if k == "blocks"
            else jax.tree.map(lambda _: P(), v))
        for k, v in params.items()
    }


def stream_layer_bufs(plan, block_rows, L: int):
    """Local block rows (``[L, 1, k]`` per leaf inside shard_map) ->
    one packed ``[L, K_b]`` buffer per bucket, scan-indexable by layer."""
    leaves = plan.treedef.flatten_up_to(block_rows)
    return [
        jnp.concatenate(
            [leaves[i].reshape(L, -1) for i in idxs], axis=1
        )
        for idxs in plan.buckets
    ]


def stream_gather_layer(plan, rows, axis: str, n: int):
    """One layer's local bucket rows (``[K_b]`` each) -> that layer's
    FULL param tree: one tiled all-gather per bucket, then the plan's
    unpack — bit-identical to the original leaves (pad/reshape round
    trip), which is what keeps streamed decode bitwise equal to the
    resident-weight program."""
    bufs = [
        lax.all_gather(r, axis, tiled=True)
        .reshape(n, plan.bucket_size(b))
        for b, r in enumerate(rows)
    ]
    return _unpack_full(plan, bufs)


def stream_gather_blocks(plan, block_rows, axis: str, n: int):
    """Reconstruct the ENTIRE stacked blocks tree from local ``[L, 1,
    k]`` rows — one all-gather per bucket over the ``[L, K_b]`` packed
    buffers.  The whole stack is TRANSIENT (prefill-scoped): streamed
    serving uses this for the prompt scan, where gathering per position
    x per layer would cost ``L x max_prompt_len`` gather rounds."""
    L = jax.tree.leaves(block_rows)[0].shape[0]
    bufs = [
        lax.all_gather(b, axis, tiled=False)  # [n, L, K_b]
        for b in stream_layer_bufs(plan, block_rows, L)
    ]
    leaves: list = [None] * plan.n_leaves
    for b, idxs in enumerate(plan.buckets):
        for i, off in zip(idxs, plan.offsets(b)):
            shape = plan.shapes[i]
            size = int(np.prod(shape)) if shape else 1
            leaves[i] = (
                bufs[b][:, :, off:off + plan.sizes[i]]
                .transpose(1, 0, 2)  # [L, n, k]
                .reshape(L, -1)[:, :size]
                .reshape((L,) + tuple(shape))
                .astype(plan.dtypes[i])
            )
    return plan.treedef.unflatten(leaves)


def zero_resume_template(
    params_template,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    axis: str = "data",
    llama: bool = False,
    abstract: bool = False,
):
    """The restore template for a (possibly cross-mesh) ZeRO resume:
    ``{"params": shards, "opt_state": tx.init(shards)}`` laid out for
    ``mesh`` exactly as a fresh run would build it, with every
    placement-less leaf (Adam's ``count`` scalar…) replicated via
    :func:`~ddl25spring_tpu.utils.checkpoint.with_mesh_placement`.

    Hand this (plus cursors, via ``ft.autosave.resume_bundle``) to
    :meth:`ft.autosave.AutoSaver.restore_or_init`: when the checkpoint
    was saved on a DIFFERENT device count, the restore re-lands each
    saved ``[n, k]`` shard onto this template's ``[m, k']`` layout
    through :mod:`ddl25spring_tpu.ft.reshard` — the elastic half of the
    weight-update-sharding math (arXiv:2004.13336) this module's
    forward/backward implements.

    ``abstract=True`` returns sharding-carrying ``ShapeDtypeStruct``
    leaves instead of materialized zeros — the elastic in-run reshape
    (:mod:`ddl25spring_tpu.ft.elastic`) templates with it so the
    survivor mesh never allocates a throwaway full state right when a
    device just died and memory headroom is at its worst.  Shapes come
    from ``jax.eval_shape`` over the SAME shard+init path the concrete
    template runs; shardings follow the saved-layout contract
    (:data:`ddl25spring_tpu.ft.reshard.SAVED_SHARD_DIMS`: rank 2 ->
    rows on dim 0, rank 3 -> dim 1, anything else replicated — the
    layout H013 verifies at compile time)."""
    from ddl25spring_tpu.utils.checkpoint import with_mesh_placement

    shard = zero_shard_llama_params if llama else zero_shard_params
    if not abstract:
        shards = shard(params_template, mesh, axis)
        return with_mesh_placement(
            {"params": shards, "opt_state": tx.init(shards)}, mesh
        )

    from ddl25spring_tpu.ft.reshard import SAVED_SHARD_DIMS

    n = mesh.shape[axis]
    abs_tree = jax.eval_shape(
        lambda p: (lambda s: {"params": s, "opt_state": tx.init(s)})(
            shard(p, mesh, axis)
        ),
        params_template,
    )

    def place(leaf):
        dim = SAVED_SHARD_DIMS.get(len(leaf.shape))
        spec = (
            P(*([None] * dim + [axis]))  # trailing dims unsharded
            if dim is not None and leaf.shape[dim] == n
            else P()
        )
        return jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=NamedSharding(mesh, spec)
        )

    return jax.tree.map(place, abs_tree)


def make_zero3_llama_train_step(
    cfg,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    axis: str = "data",
    bucket_bytes: int | float = bucketing.AUTO,
    prefetch: bool = True,
    per_shard_rng: bool = True,
    donate: bool | None = None,
    sentinel: bool | None = None,
):
    """ZeRO-3 over the scanned LLaMA layer stack with GATHER PREFETCH:
    the all-gather for layer ``i+1``'s parameters is issued *before*
    layer ``i``'s compute consumes its own — a double-buffered scan
    carry — so XLA's async collective pair (``all-gather-start`` /
    ``-done``) can overlap the ICI transfer with the MXU work of the
    current layer (the overlap schedule of arXiv:2204.06514 §4.2
    expressed in one shard_map program).

    Where :func:`make_zero_dp_train_step` gathers the WHOLE tree up
    front (every layer's params resident before the first matmul and an
    exposed gather latency at step start), this step walks the stacked
    ``blocks`` with ``lax.scan`` and keeps at most TWO layers' full
    params live in the forward: the one being consumed and the one in
    flight.  Collectives ride the flat-bucket path per layer
    (:mod:`ddl25spring_tpu.parallel.bucketing`), so the program shows
    ONE gather site per layer-bucket inside a while loop whose trip
    count XLA pins to ``n_layers`` — the shape
    ``tests/test_xla_analytics.py`` asserts.

    ``prefetch=False`` drops the double buffer and instead gathers
    inside a ``jax.checkpoint``-wrapped layer body: no issue-ahead, but
    the backward re-gathers instead of keeping the scan's stacked
    gathered-params residuals — the memory-lean FSDP schedule.  With
    ``prefetch=True`` the scan transpose stores each iteration's carry
    (the gathered layer params, ``O(P)`` across the stack), trading
    backward-pass HBM for the forward overlap — the right trade on the
    ICI-bound configs this step targets; hand-rolling the backward to
    get both is future work (ROADMAP).

    ``step(param_shards, opt_state, tokens, key)`` with ``param_shards``
    from :func:`zero_shard_llama_params`, ``opt_state = tx.init(param_
    shards)``, ``tokens [B, ctx]`` sharded on the leading dim.  Loss is
    ``causal_lm_loss`` (+ ``cfg.moe_aux_weight`` x the router aux for
    switch-MoE configs).  Numerically == replicated DP + the same optax
    chain (asserted in ``tests/test_bucketing.py``).
    """
    from ddl25spring_tpu.models import llama
    from ddl25spring_tpu.obs import sentinels as _sentinels
    from ddl25spring_tpu.ops.losses import causal_lm_loss

    s_on, s_policy = _sentinels.resolve(sentinel)

    bucket_bytes = bucketing.resolve_bucket_bytes(bucket_bytes)
    if not bucket_bytes:
        raise ValueError(
            "the scanned-LLaMA ZeRO-3 step is bucketed by construction; "
            "bucket_bytes must be a positive threshold (DDL25_BUCKET_"
            "BYTES=0 cannot apply here)"
        )
    n = mesh.shape[axis]
    L = cfg.n_layers
    template = jax.eval_shape(
        lambda: llama.init_llama_params(jax.random.PRNGKey(0), cfg)
    )
    block_tmpl = template["blocks"]
    outer_tmpl = {k: v for k, v in template.items() if k != "blocks"}
    # per-LAYER plan: slot sizes are one layer's padded k rows
    layer_tmpl = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype), block_tmpl
    )
    layer_plan = _row_plan(layer_tmpl, n, bucket_bytes)
    outer_plan = _row_plan(outer_tmpl, n, bucket_bytes)
    shard_shapes = {
        (n, _leaf_meta(l, n)[1]) for l in jax.tree.leaves(outer_tmpl)
    }
    stacked_shapes = {
        (L, n, _leaf_meta(jax.ShapeDtypeStruct(l.shape[1:], l.dtype), n)[1])
        for l in jax.tree.leaves(block_tmpl)
    }

    def step(param_shards, opt_state, tokens, key):
        state_specs = _opt_state_specs(
            opt_state, shard_shapes, axis, stacked_shapes=stacked_shapes
        )
        pspecs = dict(
            {k: P(axis) for k in outer_tmpl},
            blocks=jax.tree.map(lambda _: P(None, axis), block_tmpl),
        )

        @partial(
            shard_map,
            mesh=mesh,
            in_specs=(pspecs, state_specs, P(axis), P()),
            out_specs=(pspecs, state_specs, P()),
        )
        def sharded_step(pshards, ostate, toks, key):
            if per_shard_rng:
                key = jax.random.fold_in(key, lax.axis_index(axis))

            def shard_loss(pshards):
                outer = _gather_bucketed(
                    outer_plan,
                    {k: pshards[k] for k in outer_tmpl},
                    axis, n,
                )
                # local block rows [L, 1, k] -> packed [L, K_b] buffers
                layer_bufs = [
                    jnp.concatenate(
                        [
                            layer_plan.treedef.flatten_up_to(
                                pshards["blocks"]
                            )[i].reshape(L, -1)
                            for i in idxs
                        ],
                        axis=1,
                    )
                    for idxs in layer_plan.buckets
                ]

                def gather_layer(rows):
                    # rows: one [K_b] row per bucket -> full layer params
                    bufs = [
                        lax.all_gather(r, axis, tiled=True)
                        .reshape(n, layer_plan.bucket_size(b))
                        for b, r in enumerate(rows)
                    ]
                    return _unpack_full(layer_plan, bufs)

                x = llama.embed(outer, toks, cfg)
                aux0 = pcast(jnp.float32(0.0), axis, to="varying")
                if prefetch:
                    def rows_at(i):
                        return [
                            lax.dynamic_index_in_dim(b, i, 0, keepdims=False)
                            for b in layer_bufs
                        ]

                    def body(carry, i):
                        x, aux, cur = carry
                        # issue layer i+1's gather BEFORE layer i's
                        # compute: the double buffer XLA can turn into
                        # an in-flight all-gather-start/-done pair
                        nxt = gather_layer(rows_at(i + 1))
                        x, a = llama.block_forward(cur, x, cfg)
                        return (x, aux + a, nxt), None

                    # the last layer is peeled out of the scan: it has
                    # nothing left to prefetch, so running it in the loop
                    # would re-gather layer L-1 only to drop the result
                    cur = gather_layer(rows_at(0))
                    aux = aux0
                    if L > 1:
                        (x, aux, cur), _ = lax.scan(
                            body, (x, aux, cur), jnp.arange(L - 1)
                        )
                    x, a = llama.block_forward(cur, x, cfg)
                    aux = aux + a
                else:
                    # memory-lean remat: the gather lives INSIDE the
                    # checkpointed body, so the backward re-gathers each
                    # layer instead of storing the gathered stack
                    @jax.checkpoint
                    def one_layer(rows, x):
                        return llama.block_forward(
                            gather_layer(list(rows)), x, cfg
                        )

                    def body(carry, rows):
                        x, aux = carry
                        x, a = one_layer(rows, x)
                        return (x, aux + a), None

                    (x, aux), _ = lax.scan(
                        body, (x, aux0), tuple(layer_bufs)
                    )
                logits = llama.unembed(outer, x, cfg)
                loss = causal_lm_loss(logits, toks)
                if cfg.n_experts > 0:
                    loss = loss + cfg.moe_aux_weight * aux
                return loss

            loss, gshards = jax.value_and_grad(shard_loss)(pshards)
            # gather transposes deliver cross-device SUMS; /n -> DP mean
            gshards = jax.tree.map(lambda g: g / n, gshards)
            updates, new_state = tx.update(gshards, ostate, pshards)
            new_shards = optax.apply_updates(pshards, updates)
            new_shards, new_state = _sentinels.guard(
                "zero3-prefetch" if prefetch else "zero3-llama",
                (new_shards, new_state), loss=lax.pmean(loss, axis),
                grads=gshards, params=pshards, updates=updates,
                fallback=(pshards, ostate), axis=axis, enabled=s_on, policy=s_policy,
            )
            return new_shards, new_state, lax.pmean(loss, axis)

        return sharded_step(param_shards, opt_state, tokens, key)

    return jax.jit(step, donate_argnums=donate_argnums(donate))


def _llama_workload(n: int, n_layers: int = 4):
    """Tiny LLaMA LM workload for the compile-time analytics: a param
    tree with a realistic leaf count (stacked blocks + embed/ln_f/
    unembed), so the per-leaf vs bucketed collective-count gap is
    visible — the O(n_leaves) -> O(n_buckets) pin runs on this tree."""
    from ddl25spring_tpu.models import llama
    from ddl25spring_tpu.ops.losses import causal_lm_loss
    from ddl25spring_tpu.utils.config import LlamaConfig

    cfg = LlamaConfig(
        vocab_size=64, dmodel=16, num_heads=2, n_layers=n_layers,
        ctx_size=16, dtype="float32",
    )
    params = llama.init_llama_params(jax.random.PRNGKey(0), cfg)

    def loss_fn(p, tokens, key):
        del key
        return causal_lm_loss(llama.llama_forward(p, tokens, cfg), tokens)

    tokens = jnp.zeros((2 * n, cfg.ctx_size), jnp.int32)
    param_bytes = sum(
        l.size * l.dtype.itemsize for l in jax.tree.leaves(params)
    )
    return cfg, params, loss_fn, tokens, param_bytes


def describe(
    mesh: Mesh,
    stage: int = 3,
    axis: str = "data",
    bucketed: bool = True,
    workload: str = "mlp",
    prefetch: bool = False,
    overlap: bool = False,
    bucket_bytes: int | float | None = None,
):
    """Registry hook for :mod:`ddl25spring_tpu.obs.xla_analytics`: the
    lowerable ZeRO train step (stage 1, 2, or 3) + example inputs + the
    analytic collective signature.

    The three stages are *distinguishable by their compiled collectives*
    alone — the point of pinning them:

    - stage 1: one all-reduce of the full (padded) grad bytes + one
      all-gather of the updated param rows;
    - stage 2: reduce-scatter (result = the 1/n grad shard) + the same
      all-gather — no full-grad all-reduce anywhere;
    - stage 3: all-gathers of the padded params in the forward and
      reduce-scatters out of the backward — no param-sized all-reduce,
      no update-side gather.

    ``bucketed`` (the builders' default): the per-leaf launches above
    collapse to per-BUCKET launches — the expected counts pin
    O(n_buckets), strictly below ``n_param_leaves`` whenever the tree
    has more leaves than dtype-buckets.  ``bucketed=False`` describes
    the legacy per-leaf path (the comparison baseline the bucketing
    tests compile).  ``workload="llama"`` swaps the 3-leaf MLP for a
    tiny LLaMA tree (12 leaves at 4 layers) where that gap is real.
    ``prefetch=True`` (stage 3 only) describes
    :func:`make_zero3_llama_train_step`: the gather site sits INSIDE the
    layer scan — one all-gather per layer-bucket per trip, trip count ==
    ``n_layers``, the double-buffered overlap shape.

    ``overlap=True`` describes the backward-issued variants
    (``zero1-overlap`` / ``zero2-overlap`` / ``zero3-overlap``): stage
    1's all-reduce packs the RAW grad bytes (flat concat, no row
    padding) per backward-readiness bucket; stage 2's reduce-scatter
    and stage 3's gather/scatter keep the padded row layout with
    backward-ordered bucket composition.  Counts, axes, forbidden
    kinds, and donation floors pin identically — the overlap is a
    dataflow restructure, not a traffic change.  ``bucket_bytes`` pins
    an explicit threshold for the sweep harness (default
    :data:`~ddl25spring_tpu.parallel.bucketing.DEFAULT_BUCKET_BYTES`,
    never the env knob — signatures must not drift with ambient
    ``DDL25_BUCKET_BYTES``).
    """
    from ddl25spring_tpu.parallel.dp import _tiny_mlp_workload

    if overlap and not bucketed:
        raise ValueError("overlap describes the bucketed paths only")
    if overlap and prefetch:
        raise ValueError("prefetch is already the overlapped scanned-"
                         "LLaMA shape; overlap applies to the whole-tree"
                         " steps")
    n = mesh.shape[axis]
    key = jax.random.PRNGKey(0)
    slack = 256
    # MLP describes default to the multi-bucket threshold (the sched
    # verifier's overlap-vs-sync window pins need >= 2 launches; see
    # dp.DESCRIBE_BUCKET_BYTES); the LLaMA trees keep the runtime
    # default — their leaf count already exercises the bucketed path
    from ddl25spring_tpu.parallel.dp import DESCRIBE_BUCKET_BYTES

    default_bb = (
        bucketing.DEFAULT_BUCKET_BYTES
        if (prefetch or workload == "llama")
        else DESCRIBE_BUCKET_BYTES
    )
    bb = (bucket_bytes or default_bb) if bucketed else None

    if prefetch:
        if stage != 3 or not bucketed:
            raise ValueError("prefetch describes the bucketed stage-3 "
                             "scanned-LLaMA step only")
        cfg, params, _, tokens, param_bytes = _llama_workload(n)
        L = cfg.n_layers
        tx = optax.sgd(0.1)
        shards = zero_shard_llama_params(params, mesh, axis)
        step = make_zero3_llama_train_step(
            cfg, tx, mesh, axis, bucket_bytes=bb, prefetch=True,
            per_shard_rng=False, donate=True,
        )
        shard_bytes = sum(
            l.size * l.dtype.itemsize for l in jax.tree.leaves(shards)
        )
        layer_tmpl = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype),
            params["blocks"],
        )
        n_lb = _row_plan(layer_tmpl, n, bb).n_buckets
        outer_tmpl = {k: v for k, v in params.items() if k != "blocks"}
        n_ob = _row_plan(outer_tmpl, n, bb).n_buckets
        return {
            "fn": step,
            "args": (shards, tx.init(shards), tokens, key),
            "lowered": "train_step",
            "meta": {
                "zero_stage": 3,
                "prefetch": True,
                "n_layers": L,
                "param_bytes": param_bytes,
                "n_param_leaves": len(jax.tree.leaves(params)),
                "n_buckets": n_lb + n_ob,
                "n_layer_buckets": n_lb,
                "n_outer_buckets": n_ob,
                "bucket_bytes": bb,
            },
            "expected": {
                "scalar_bytes": 64,
                # the in-scan gather executes once per layer-bucket per
                # trip (trip count == L-1, annotated on the while; the
                # peeled last layer has nothing left to prefetch) plus
                # the initial double-buffer fill and the outer gathers;
                # the backward may re-play gathers, hence the x3 ceiling
                "all-gather": {
                    "min_count": n_lb * L + n_ob,
                    "max_count": 3 * n_lb * L + 2 * n_ob,
                    "axes": [axis],
                },
                "reduce-scatter": {
                    "min_count": n_lb + n_ob,
                    "axes": [axis],
                },
                "all-reduce": {"max_bytes": slack},
                "forbidden": ["collective-permute", "all-to-all"],
                # the compiled module is the per-DEVICE SPMD program, so
                # the aliased bytes are one device's shard of the tree
                "donation": {"min_saved_bytes": shard_bytes // n},
                "memory": {"max_peak_hbm_bytes": 24 * 1024 * 1024},
            },
        }

    if workload == "llama":
        _, params, loss_fn, batch, param_bytes = _llama_workload(n)
        mem_budget = 24 * 1024 * 1024
    else:
        params, loss_fn, batch, param_bytes = _tiny_mlp_workload(n)
        mem_budget = 4 * 1024 * 1024
    padded_bytes = sum(
        n * _leaf_meta(leaf, n)[1] * jnp.result_type(leaf).itemsize
        for leaf in jax.tree.leaves(params)
    )
    tx = optax.sgd(0.1)
    shards = zero_shard_params(params, mesh, axis)
    opt_state = tx.init(shards)
    n_leaves = len(jax.tree.leaves(params))
    plan_order = "backward" if overlap else "forward"
    n_buckets = (
        _row_plan(params, n, bb, order=plan_order).n_buckets
        if bucketed else None
    )
    # collective sites per sweep over the tree: one per bucket when
    # packing, one per leaf otherwise
    launches = n_buckets if bucketed else n_leaves
    if stage == 3:
        step = make_zero_dp_train_step(
            loss_fn, tx, mesh, params, axis,
            per_shard_rng=False, instrument=False,
            bucket_bytes=bb, donate=True, overlap=overlap,
        )
        args = (shards, opt_state, batch, key)
        expected = {
            "scalar_bytes": 64,
            "all-gather": {
                "min_bytes": padded_bytes,
                "max_bytes": 2 * padded_bytes + slack,  # bwd may re-gather
                "axes": [axis],
                "min_count": launches,
                "max_count": 2 * launches,
            },
            "reduce-scatter": {
                "min_bytes": padded_bytes // n,
                "max_bytes": padded_bytes // n + slack,
                "axes": [axis],
                "min_count": launches,
                "max_count": launches,
            },
            # a param-sized all-reduce would mean the sharding collapsed
            # back to replicated DP
            "all-reduce": {"max_bytes": slack},
            "forbidden": ["collective-permute", "all-to-all"],
            # per-DEVICE aliased bytes: stage 3's inputs are the [n, k]
            # shards, of which this device holds 1/n
            "donation": {"min_saved_bytes": padded_bytes // n},
        }
    else:
        step = make_zero_partitioned_train_step(
            loss_fn, tx, mesh, params, axis, stage=stage,
            per_shard_rng=False, bucket_bytes=bb, donate=True,
            overlap=overlap,
        )
        args = (params, opt_state, batch, key)
        expected = {
            "scalar_bytes": 64,
            "all-gather": {
                "min_bytes": padded_bytes,
                "max_bytes": padded_bytes + slack,
                "axes": [axis],
                "min_count": launches,
                "max_count": launches,
            },
            "forbidden": ["collective-permute", "all-to-all"],
            "donation": {"min_saved_bytes": param_bytes},
        }
        if stage == 1:
            # the overlapped variant all-reduces the RAW cotangent
            # bytes (flat concat in the bwd rule, no row padding) over
            # its own flat backward-readiness plan; the sync path moves
            # the padded row layout.  meta's n_buckets follows the GRAD
            # plan — the launch structure a bucket sweep actually
            # varies — while the update gather keeps the row plan
            # (n_update_buckets below).
            grad_launches = (
                bucketing.plan_buckets(
                    params, bb, order="backward"
                ).n_buckets
                if overlap else launches
            )
            if overlap:
                n_update_buckets, n_buckets = n_buckets, grad_launches
            expected["all-reduce"] = {
                "min_bytes": param_bytes if overlap else padded_bytes,
                "max_bytes": padded_bytes + slack,
                "axes": [axis],
                # + up to 2 scalar loss reductions ride along
                "max_count": grad_launches + 2,
            }
            expected["forbidden"].append("reduce-scatter")
        else:
            expected["reduce-scatter"] = {
                "min_bytes": padded_bytes // n,
                "max_bytes": padded_bytes // n + slack,
                "axes": [axis],
                "min_count": launches,
                "max_count": launches,
            }
            # stage 2's defining property: NO full-grad all-reduce
            expected["all-reduce"] = {"max_bytes": slack}
    expected["memory"] = {"max_peak_hbm_bytes": mem_budget}
    return {
        "fn": step,
        "args": args,
        "lowered": "train_step",
        "meta": {
            "zero_stage": stage,
            "workload": workload,
            "param_bytes": param_bytes,
            "padded_param_bytes": padded_bytes,
            "n_param_leaves": n_leaves,
            **({"n_buckets": n_buckets} if bucketed else {}),
            # stage-1 overlap: the grad all-reduce rides the flat plan
            # (n_buckets above) while the update gather keeps the row
            # plan — both counts recorded so sweeps and signature
            # readers never conflate them
            **(
                {"n_update_buckets": n_update_buckets}
                if overlap and stage == 1 and bucketed else {}
            ),
            **({"bucket_bytes": bb} if bucketed else {}),
            **({"overlap": True} if overlap else {}),
        },
        "expected": expected,
    }


def zero_clip_by_global_norm(
    max_norm: float, axis: str = "data"
) -> optax.GradientTransformation:
    """``optax.clip_by_global_norm`` made correct on ZeRO's ``[1, k]``
    local shards (VERDICT r3 directive #4).

    Each device's update leaves hold disjoint rows of the ``[n, k]`` layout,
    so the true global square-norm is ONE ``lax.psum`` of the shard-local
    square-norms over the mesh axis (padded tail entries are exactly zero
    and contribute nothing).  Semantics mirror optax: updates pass through
    untouched when ``g_norm < max_norm``, else scale by
    ``max_norm / g_norm`` — so ZeRO + this transform equals replicated DP +
    ``optax.clip_by_global_norm`` (asserted in ``tests/test_zero.py``).

    Must run inside the optax chain handed to
    :func:`make_zero_dp_train_step` (the chain executes inside the
    ``shard_map``, where the axis name is bound).
    """

    def init_fn(params):
        del params
        return optax.EmptyState()

    def update_fn(updates, state, params=None):
        del params
        local_sq = sum(
            jnp.sum(jnp.square(u.astype(jnp.float32)))
            for u in jax.tree.leaves(updates)
        )
        g_norm = jnp.sqrt(lax.psum(local_sq, axis))
        trigger = g_norm < max_norm
        clipped = jax.tree.map(
            lambda t: jnp.where(
                trigger, t, (t / g_norm.astype(t.dtype)) * max_norm
            ),
            updates,
        )
        return clipped, state

    return optax.GradientTransformation(init_fn, update_fn)
