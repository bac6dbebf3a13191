"""Expert parallelism: a switch-style MoE FFN over a mesh ``expert`` axis.

The reference has no MoE (SURVEY §2: EP absent) — this is a beyond-parity
capability completing the framework's parallelism axis set (dp/pp/tp/sp/ep).
Design is TPU-first throughout:

- top-1 (switch) routing with a **capacity-bucketed dense dispatch**: the
  ragged token->expert assignment becomes one-hot ``[T, E, C]`` dispatch/
  combine tensors so everything is static-shaped einsums on the MXU — no
  gather/scatter, no dynamic shapes (the Mesh-TensorFlow/Switch formulation);
- tokens over capacity are dropped (their residual stream passes through
  untouched), the standard switch behavior;
- experts are bias-free SwiGLU blocks stacked ``[E, ...]``; under EP the
  stack is sharded over the ``expert`` axis and tokens are sharded over the
  same axis, with two ``lax.all_to_all`` hops (dispatch out, combine back)
  riding ICI — the TPU-native equivalent of NCCL all-to-all in GPU MoE
  stacks;
- an auxiliary load-balancing loss (mean fraction x mean router prob per
  expert, scaled by E) is returned alongside the output.

``moe_ffn`` is the single-device reference; ``make_ep_moe_fn`` returns the
EP-sharded version.  With ample capacity the two are exactly equal
(asserted in ``tests/test_ep.py``); under overflow they differ only in
which tokens drop (per-shard vs global capacity).
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.lax import pcast

from ddl25spring_tpu.parallel.bucketing import donate_argnums
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Params = dict[str, Any]


def init_moe_params(
    key: jax.Array, dmodel: int, ffn_dim: int, n_experts: int
) -> Params:
    """Router ``[D, E]`` + stacked bias-free SwiGLU experts ``[E, ...]``.
    (Bias-free so a zero capacity-padding row maps to zero — dispatch
    correctness does not depend on masking expert internals.)"""
    ks = jax.random.split(key, 4)
    s = 0.02

    def dense(k, shape):
        return (s * jax.random.normal(k, shape)).astype(jnp.float32)

    return {
        "router": dense(ks[0], (dmodel, n_experts)),
        "w_gate": dense(ks[1], (n_experts, dmodel, ffn_dim)),
        "w_up": dense(ks[2], (n_experts, dmodel, ffn_dim)),
        "w_down": dense(ks[3], (n_experts, ffn_dim, dmodel)),
    }


def _expert_ffn(p: Params, x: jax.Array) -> jax.Array:
    """Apply all experts to their capacity buckets: ``x [E, C, D]`` with the
    stacked expert weights — one batched einsum per matmul (MXU-friendly),
    no per-expert Python loop."""
    dtype = x.dtype
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, p["w_gate"].astype(dtype)))
    up = jnp.einsum("ecd,edf->ecf", x, p["w_up"].astype(dtype))
    return jnp.einsum("ecf,efd->ecd", gate * up, p["w_down"].astype(dtype))


def _dispatch_tensors(
    router_logits: jax.Array, capacity: int, top_k: int = 1
):
    """Routed dispatch: one-hot ``[T, E, C]`` dispatch mask and
    gate-weighted combine tensor, plus the load-balancing auxiliary loss.

    ``top_k == 1`` is switch routing (gate = the winning softmax prob);
    ``top_k > 1`` is Mixtral-style top-k routing: each token dispatches to
    its k highest-prob experts with gates renormalized over the k choices,
    and bucket slots fill CHOICE-MAJOR (every token's first choice before
    any second choice), so under overflow second choices drop first — the
    GShard discipline.  The aux loss stays the Switch estimator on
    first-choice assignments in both cases."""
    T, E = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    if top_k == 1:
        gate = jnp.max(probs, axis=-1)                    # [T]
        expert = jnp.argmax(probs, axis=-1)               # [T]
        onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)  # [T, E]
        # position of each token within its expert's bucket (arrival order)
        pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot      # [T, E]
        keep = onehot * (pos < capacity)                       # overflow drops
        disp = keep[:, :, None] * jax.nn.one_hot(
            pos.sum(-1).astype(jnp.int32), capacity, dtype=jnp.float32
        )[:, None, :]                                          # [T, E, C]
        combine = disp * gate[:, None, None]
        first_choice = onehot
        kept = keep.sum(0)
    else:
        gates, experts = lax.top_k(probs, top_k)          # [T, k]
        gates = gates / jnp.maximum(
            gates.sum(-1, keepdims=True), 1e-9
        )                                                  # renormalize
        onehots = jax.nn.one_hot(experts, E, dtype=jnp.float32)  # [T, k, E]
        # choice-major arrival order: flatten [k, T, E] so cumsum fills
        # all first choices before any second choice
        oh_flat = onehots.transpose(1, 0, 2).reshape(top_k * T, E)
        pos = (jnp.cumsum(oh_flat, axis=0) - 1.0) * oh_flat
        keep = oh_flat * (pos < capacity)
        disp_flat = keep[:, :, None] * jax.nn.one_hot(
            pos.sum(-1).astype(jnp.int32), capacity, dtype=jnp.float32
        )[:, None, :]                                      # [kT, E, C]
        disp_k = disp_flat.reshape(top_k, T, E, capacity)
        # each (t, e) pair appears in at most one choice (top_k experts
        # are distinct), so the sums below never collide slots
        disp = disp_k.sum(0)
        combine = (
            disp_k * gates.T[:, :, None, None]
        ).sum(0)
        first_choice = onehots[:, 0]
        kept = keep.reshape(top_k, T, E).sum((0, 1))
    # Switch aux loss: E * sum_e fraction_e * mean-prob_e.  fraction_e is
    # the ASSIGNED first-choice fraction (pre-drop routing decisions), not
    # the kept fraction — kept saturates at C under overflow, which would
    # under-penalize imbalance exactly when drops occur
    frac = first_choice.sum(0) / jnp.maximum(first_choice.sum(), 1.0)
    aux = E * jnp.sum(frac * probs.mean(0))
    # kept-token count per expert [E] (dropped = assigned - kept): the
    # overflow accounting the EP/dense equivalence tests pin
    return disp, combine, aux, kept


def moe_ffn(
    p: Params,
    x: jax.Array,
    capacity_factor: float = 1.25,
    return_stats: bool = False,
    top_k: int = 1,
):
    """Single-device reference MoE: ``x [T, D] -> ([T, D], aux_loss)``.

    ``return_stats=True`` appends ``{"kept": [E], "assigned": T * top_k}``
    — both counts are SLOT assignments (a token makes ``top_k`` routing
    decisions), so dropped slots = ``assigned - kept.sum()`` for every k.
    ``top_k``: experts per token (1 = switch, 2 = Mixtral-style; see
    :func:`_dispatch_tensors`); capacity scales with k."""
    T, D = x.shape
    E = p["router"].shape[1]
    C = max(1, int(T * capacity_factor * top_k / E))
    logits = x.astype(jnp.float32) @ p["router"]
    disp, combine, aux, kept = _dispatch_tensors(logits, C, top_k)
    expert_in = jnp.einsum("tec,td->ecd", disp.astype(x.dtype), x)
    expert_out = _expert_ffn(p, expert_in)
    y = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), expert_out)
    if return_stats:
        return y, aux, {"kept": kept, "assigned": jnp.float32(T * top_k)}
    return y, aux


def ep_moe_local(
    p: Params,
    x: jax.Array,
    *,
    axis: str,
    ep: int,
    capacity_factor: float = 1.25,
    vary_axes: tuple[str, ...] = (),
    return_stats: bool = False,
    top_k: int = 1,
):
    """The expert-parallel MoE body, for use INSIDE an enclosing
    ``shard_map``: ``x [T_local, D]`` is this shard's token slice along
    ``axis`` (size ``ep``), ``p`` holds the local ``[E/ep, ...]`` expert
    stacks and the replicated router.  Returns the per-shard ``(y, aux)``
    (aux NOT reduced over shards — callers choose the estimator; with
    stats, the local kept/assigned counts).

    ``vary_axes``: mesh axes the router param is *invariant* over but the
    tokens vary over (it is pcast before use).  Factored out of
    :func:`make_ep_moe_fn` so other sharded programs — e.g. the pipeline,
    whose blocks already run inside a ``(data, stage)`` shard_map — can
    ride expert parallelism over one of their existing axes
    (``parallel.pipeline`` EP x DP x PP)."""
    T_local, D = x.shape
    E = p["router"].shape[1]          # global expert count
    E_local = E // ep
    C = max(1, int(T_local * capacity_factor * top_k / E))
    router = p["router"]
    if vary_axes:
        router = pcast(router, vary_axes, to="varying")
    logits = x.astype(jnp.float32) @ router
    disp, combine, aux, kept = _dispatch_tensors(logits, C, top_k)

    expert_in = jnp.einsum("tec,td->ecd", disp.astype(x.dtype), x)
    # regroup [E, C, D] = [ep, E_local, C, D]: hand shard s's buckets
    # for expert group g to device g; receive every shard's buckets for
    # OUR experts (dim0 becomes the source shard)
    a2a = lax.all_to_all(
        expert_in.reshape(ep, E_local, C, D), axis, 0, 0, tiled=False
    )                                  # [ep, E_local, C, D], dim0 = src
    mine = a2a.transpose(1, 0, 2, 3).reshape(E_local, ep * C, D)
    # the sharded-in expert stacks are already this device's [E_local,...]
    out = _expert_ffn(
        {k: p[k] for k in ("w_gate", "w_up", "w_down")}, mine
    )
    back = lax.all_to_all(
        out.reshape(E_local, ep, C, D).transpose(1, 0, 2, 3), axis, 0, 0,
        tiled=False,
    )                                  # [ep, E_local, C, D] -> our tokens
    expert_out = back.reshape(E, C, D)
    y = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), expert_out)
    if return_stats:
        return y, aux, kept
    return y, aux


def make_ep_moe_fn(
    mesh: Mesh,
    axis: str = "expert",
    capacity_factor: float = 1.25,
    return_stats: bool = False,
    data_axis: str | None = None,
    top_k: int = 1,
):
    """EP-sharded MoE: tokens AND experts sharded over ``mesh[axis]``.

    ``f(params, x)``: ``params`` with expert stacks sharded ``[E, ...]``
    over the axis (router replicated), ``x [T, D]`` sharded on tokens.
    Per shard: local dispatch to all E experts -> ``all_to_all`` so each
    device holds its local experts' buckets from every shard -> batched
    expert FFN -> ``all_to_all`` back -> local combine.

    ``data_axis``: EP x DP on a 2-D ``(data, expert)`` mesh — tokens
    shard over BOTH axes, expert stacks shard over ``axis`` and replicate
    over ``data_axis`` (each data row runs an independent expert-parallel
    group whose ``all_to_all`` stays inside the row; expert-weight
    gradients psum over ``data_axis`` automatically, since the stacks are
    data-invariant inputs under ``shard_map`` autodiff).

    ``return_stats=True`` appends ``{"kept": [E], "assigned":
    T_global * top_k}`` (psum over shards; slot accounting as in
    :func:`moe_ffn`).  Because each shard dispatches its own token group
    with capacity ``T_local*cf/E``, the kept counts equal the dense
    :func:`moe_ffn` run per shard group — pinned in ``tests/test_ep.py``.
    """
    ep = mesh.shape[axis]
    tok_axes = (data_axis, axis) if data_axis else axis

    param_specs = {
        "router": P(),
        "w_gate": P(axis),
        "w_up": P(axis),
        "w_down": P(axis),
    }

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(param_specs, P(tok_axes)),
        out_specs=(
            (P(tok_axes), P(), P())
            if return_stats else (P(tok_axes), P())
        ),
    )
    def f(p: Params, x: jax.Array):
        vary_axes = (axis,) + ((data_axis,) if data_axis else ())
        res = ep_moe_local(
            p, x, axis=axis, ep=ep, capacity_factor=capacity_factor,
            vary_axes=vary_axes, return_stats=return_stats, top_k=top_k,
        )
        # aux is the mean of per-shard switch losses (each over its token
        # shard) — the standard sharded-MoE estimator; it converges to the
        # global loss but is not bitwise equal to it (product of means !=
        # mean of products)
        # reductions run over the same axes the router was pcast over:
        # expert, plus data on the 2-D mesh
        if return_stats:
            y, aux, kept = res
            n_shards = ep * (mesh.shape[data_axis] if data_axis else 1)
            stats = {
                "kept": lax.psum(kept, vary_axes),
                # slot assignments (T_global routing decisions x top_k),
                # matching moe_ffn's accounting for every k
                "assigned": jnp.float32(x.shape[0] * n_shards * top_k),
            }
            return y, lax.pmean(aux, vary_axes), stats
        y, aux = res
        return y, lax.pmean(aux, vary_axes)

    return f


def shard_moe_params(p: Params, mesh: Mesh, axis: str = "expert") -> Params:
    """Place the expert stacks sharded over ``axis``, router replicated."""
    return jax.device_put(p, {
        "router": NamedSharding(mesh, P()),
        "w_gate": NamedSharding(mesh, P(axis)),
        "w_up": NamedSharding(mesh, P(axis)),
        "w_down": NamedSharding(mesh, P(axis)),
    })


def make_ep_train_step(
    tx,
    mesh: Mesh,
    axis: str = "expert",
    capacity_factor: float = 1.25,
    donate: bool | None = None,
    sentinel: bool | None = None,
):
    """Jitted train step for the standalone EP MoE layer: regression to a
    target output plus the load-balancing aux loss — the train-step
    surface the other parallel modules expose, completing the donation
    contract across ``parallel/*`` (params/opt-state alias in place,
    :func:`~ddl25spring_tpu.parallel.dp.donate_argnums`).

    ``step(params, opt_state, (x, y))`` with ``params`` from
    :func:`shard_moe_params` (expert stacks sharded over ``axis``),
    ``x/y [T, D]`` token-sharded on the leading dim.  The router grad
    psums over the expert axis automatically (the router is an
    axis-invariant input under shard_map autodiff), so the compiled step
    adds one small all-reduce to the layer's all-to-all signature.

    ``sentinel`` opts into the in-step numerics sentinels
    (:mod:`ddl25spring_tpu.obs.sentinels`).
    """
    import optax

    from ddl25spring_tpu.obs import sentinels

    s_on, s_policy = sentinels.resolve(sentinel)
    moe = make_ep_moe_fn(mesh, axis, capacity_factor=capacity_factor)

    def loss_fn(p, batch):
        x, y = batch
        out, aux = moe(p, x)
        return jnp.mean((out - y) ** 2) + aux

    @partial(jax.jit, donate_argnums=donate_argnums(donate))
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, new_state = tx.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        new_params, new_state = sentinels.guard(
            "ep", (new_params, new_state), loss=loss, grads=grads,
            params=params, updates=updates,
            fallback=(params, opt_state), enabled=s_on, policy=s_policy,
        )
        return new_params, new_state, loss

    return step


def describe(mesh: Mesh, axis: str = "expert"):
    """Registry hook for :mod:`ddl25spring_tpu.obs.xla_analytics`: the
    expert-parallel MoE train step + its analytic collective signature.

    EP is the only strategy whose defining collective is ``all-to-all``:
    exactly two per forward (dispatch + combine) and two more in the
    backward (an all_to_all transposes to the inverse all_to_all), every
    one over the expert axis.  A reduce-scatter or collective-permute
    here means the dispatch stopped being a pure bucket exchange.  The
    full train step adds the replicated router's gradient all-reduce
    (small, axis-grouped) on top.
    """
    import optax

    cfg_E = mesh.shape[axis]  # experts == axis size: E/ep == 1 per device
    D, F, T = 16, 32, 16 * cfg_E
    params = init_moe_params(jax.random.PRNGKey(0), D, F, cfg_E)
    params = shard_moe_params(params, mesh, axis)
    tx = optax.sgd(0.1)
    fn = make_ep_train_step(tx, mesh, axis, donate=True)
    x = jnp.zeros((T, D), jnp.float32)
    batch = (x, jnp.zeros_like(x))
    router_bytes = D * cfg_E * 4
    return {
        "fn": fn,
        "args": (params, tx.init(params), batch),
        "lowered": "train_step",
        "meta": {
            "n_experts": cfg_E,
            "tokens": T,
            "dmodel": D,
            "router_bytes": router_bytes,
        },
        "expected": {
            "scalar_bytes": 64,
            "all-to-all": {
                "min_count": 2,      # dispatch + combine (fwd); bwd may CSE
                "max_count": 4,
                "axes": [axis],
            },
            # router grad (+ scalar aux reductions) — nothing param-stack
            # sized may all-reduce here
            "all-reduce": {
                "min_bytes": router_bytes,
                "max_bytes": router_bytes + 256,
                "axes": [axis],
            },
            "forbidden": ["collective-permute", "reduce-scatter"],
            # per-device aliased bytes: router + this device's expert slice
            "donation": {"min_saved_bytes": router_bytes},
            "memory": {"max_peak_hbm_bytes": 4 * 1024 * 1024},
        },
    }
