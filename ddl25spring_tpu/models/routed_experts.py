"""The routed-expert layer of a served block, for every model that has one
(:mod:`.mistral4`, :mod:`.qwen3_next`): a softmax top-k router over ALL of a
layer's experts, and the part of the routed sum that the experts HELD on
this chip give, with static shapes, no capacity and no dropped token.

``N`` rows give ``k N`` assignments; those whose expert is not held here,
or whose row is not live, sort behind the held ones and cost the sort and
nothing more; the rows are gathered in expert order,
:func:`~ddl25spring_tpu.ops.moe_gmm.moe_gmm` runs over the held experts'
stacks with the group sizes as data (three calls: gate, up, down), and each
row takes its weighted results back (a gather through the inverse
permutation and a sum over its ``k``: what a scatter-add would give, in a
fixed order).  The group sizes over the pass's live positions are the
layer's load counts, which a block hands on as its ``aux`` beside the count
of live positions (:func:`pass_stats` reads them back on the host).

``cfg`` is any configuration object that states ``num_experts_per_tok``,
``norm_topk_prob``, ``routed_scaling_factor``, ``n_held`` (experts held
here) and ``expert_offset`` (the first of them, among the router's).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ddl25spring_tpu.ops.moe_gmm import moe_gmm


def swiglu(h, w_gate, w_up, w_down):
    """The expert's own arithmetic, as a shared expert applies it to every
    position: ``(silu(h W_g) * (h W_u)) W_d``."""
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def route(h2, w_router, cfg):
    """``(experts [N, k] int32, weights [N, k] float32)`` of rows ``h2
    [N, D]``: softmax over ALL experts in float32 (products of the stored
    values, accumulated in float32), the ``k`` largest, normalised."""
    logits = jnp.dot(h2, w_router, preferred_element_type=jnp.float32)
    p = jax.nn.softmax(logits, axis=-1)
    w, e = lax.top_k(p, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return e.astype(jnp.int32), w * cfg.routed_scaling_factor


def routed_experts(h2, experts, weights, live, stacks, layer, cfg):
    """The held experts' part of the routed sum for rows ``h2 [N, D]``
    with their ``experts``/``weights [N, k]``; ``live [N]`` marks the rows
    of a request.  Returns ``(y [N, D] float32, load [E_held] int32)``."""
    N, D = h2.shape
    k, E = cfg.num_experts_per_tok, cfg.n_held
    local = experts.reshape(-1) - cfg.expert_offset  # [kN]
    held = (local >= 0) & (local < E) & jnp.repeat(live, k)
    group = jnp.where(held, local, E)  # not held, or not live: behind
    order = jnp.argsort(group, stable=True)
    load = jnp.zeros(E + 1, jnp.int32).at[group].add(1)[:E]
    rows = h2[order // k]  # [kN, D] in expert order
    gate = moe_gmm(rows, stacks["w_gate"], load, layer)
    up = moe_gmm(rows, stacks["w_up"], load, layer)
    act = (jax.nn.silu(gate.astype(jnp.float32))
           * up.astype(jnp.float32)).astype(h2.dtype)
    out = moe_gmm(act, stacks["w_down"], load, layer)  # [kN, D]
    # each row's k results, back in its own order (zeros where not held)
    back = jnp.zeros(k * N, jnp.int32).at[order].set(
        jnp.arange(k * N, dtype=jnp.int32)
    )
    mine = out[back].reshape(N, k, D).astype(jnp.float32)
    w = jnp.where(held.reshape(N, k), weights, 0.0)
    return jnp.einsum("nk,nkd->nd", w, mine), load


def pass_stats(aux, cfg) -> tuple[dict[str, int], dict[str, int]]:
    """The fetched counts of one pass, ``aux [n_layers, E_held + 1]`` (a
    layer's load over its held experts, then its live positions), as the
    seam's two dicts (:mod:`ddl25spring_tpu.serve.paged_model`): the three
    sampled into the rings ``serve.moe.*``, and the span's own stat."""
    load, live = aux[:, :-1], aux[:, -1]
    return {
        "moe.assignments_here": int(load.sum()),
        "moe.experts_hit": int((load > 0).sum()),
        "moe.load_max": int(load.max(axis=-1).sum()),
    }, {"assignments": cfg.num_experts_per_tok * int(live.sum())}
