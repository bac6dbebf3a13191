"""The plain reference of the ``qwen3next`` family: the hybrid decoder written
from its layer equations in straightforward ``jax.numpy``, float32, matmul
precision ``highest``; no kernel, no cache, no page, no slot, no chunk, no
import of ``ddl25spring_tpu/models``.  ``families/qwen3next.py`` states the
equations' source and what was assumed; this file is only the arithmetic.

``w`` is the configuration's widths as a plain dict (``families/qwen3next.py``
``widths``).  The weights are the family file's own (``init_params``), in the
layout it documents; they are stored in bfloat16 and upcast here, one layer
at a time and within a layer one routed expert at a time, so that the
reference fits beside the served model.

The gated delta rule is a scan over the sequence, ONE TOKEN a step, on a
state ``[value heads, dk, dv]``: independent of the program's chunked prompt
pass and of its one-step kernel.  Full attention is a plain causal softmax
over the whole sequence.  The experts are a loop over the held ones, each
applied to every position and weighted by what the router gave it there
(zero where it was not chosen): no sort, no grouping, nothing that could
drop a position.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def norm(x, scale, eps):
    """RMSNorm with the zero-centred scale: ``(1 + w)``."""
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + scale)


def rope(x, pos, w: dict):
    """``x [T, H, hd]``: the first ``partial_rotary_factor hd`` dims of each
    head turned by ``pos * inv_freq``, halves rotated; the rest passes."""
    d = int(w["head_dim"] * w["partial_rotary_factor"])
    inv = jnp.asarray((w["rope_theta"] ** (
        -np.arange(0, d, 2, dtype=np.float64) / d)).astype(np.float32))
    ang = pos.astype(jnp.float32)[:, None, None] * inv[None, None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    turn, keep = x[..., :d], x[..., d:]
    half = jnp.concatenate([-turn[..., d // 2:], turn[..., :d // 2]], axis=-1)
    return jnp.concatenate([turn * cos + half * sin, keep], axis=-1)


def attention(p, x, w: dict):
    """``x + o_proj(softmax attention * sigmoid(gate))`` of ``norm(x)``."""
    T = x.shape[0]
    H, KV, hd = w["num_attention_heads"], w["num_key_value_heads"], w["head_dim"]
    eps = w["rms_norm_eps"]
    pos = jnp.arange(T)
    h = norm(x, p["ln1"], eps)
    qg = (h @ p["wq"]).reshape(T, H, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:].reshape(T, H * hd)
    k = (h @ p["wk"]).reshape(T, KV, hd)
    v = (h @ p["wv"]).reshape(T, KV, hd)
    q = rope(norm(q, p["q_norm"], eps), pos, w)
    k = rope(norm(k, p["k_norm"], eps), pos, w)
    k = jnp.repeat(k, H // KV, axis=1)  # a KV head serves H / KV query heads
    v = jnp.repeat(v, H // KV, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) * hd ** -0.5
    causal = pos[:, None] >= pos[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shd->thd", probs, v).reshape(T, H * hd)
    return x + (o * jax.nn.sigmoid(gate)) @ p["wo"]


def delta_rule(p, x, w: dict, state_dtype=jnp.float32):
    """``(x + gated delta rule of norm(x), S [nv, dk, dv], tail [K - 1,
    channels])``: the state after the last token and the convolution's last
    ``K - 1`` inputs.  ``state_dtype`` rounds the carried state after every
    token (the lower-precision control)."""
    T = x.shape[0]
    nk, nv = w["linear_num_key_heads"], w["linear_num_value_heads"]
    dk, dv = w["linear_key_head_dim"], w["linear_value_head_dim"]
    K, Kd = w["linear_conv_kernel_dim"], nk * dk
    C = 2 * Kd + nv * dv
    h = norm(x, p["ln1"], w["rms_norm_eps"])
    qkvz = h @ p["in_qkvz"]
    mixed, z = qkvz[:, :C], qkvz[:, C:].reshape(T, nv, dv)
    ba = h @ p["in_ba"]
    beta = jax.nn.sigmoid(ba[:, :nv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, nv:] + p["dt_bias"])
    padded = jnp.concatenate([jnp.zeros((K - 1, C)), mixed])
    y = sum(padded[j:j + T] * p["conv_w"][j] for j in range(K))
    y = jax.nn.silu(y)

    def unit(a):
        return a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + w["l2_eps"])

    q = jnp.repeat(unit(y[:, :Kd].reshape(T, nk, dk)) * dk ** -0.5, nv // nk, axis=1)
    k = jnp.repeat(unit(y[:, Kd:2 * Kd].reshape(T, nk, dk)), nv // nk, axis=1)
    v = y[:, 2 * Kd:].reshape(T, nv, dv)

    def token(S, t):
        q_t, k_t, v_t, g_t, b_t = t
        S = S.astype(jnp.float32) * jnp.exp(g_t)[:, None, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[:, :, None] * u[:, None, :]
        return S.astype(state_dtype), jnp.einsum("hkv,hk->hv", S, q_t)

    S, o = lax.scan(token, jnp.zeros((nv, dk, dv), state_dtype), (q, k, v, g, beta))
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + w["rms_norm_eps"])
    o = o * p["o_norm"] * jax.nn.silu(z)
    return x + o.reshape(T, nv * dv) @ p["out_proj"], S, padded[T:]


def swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def experts(p, stacks, li, x, w: dict, held: tuple[int, int]):
    """``(x + held routed experts + gated shared expert, gap [T])``; ``gap``
    is the router's ``k``-th logit less its ``k + 1``-th where one of the two
    experts is held here, else infinite: a flip between two experts on other
    chips changes nothing on this one but a normalising sum that the two
    leave nearly equal."""
    k = w["num_experts_per_tok"]
    offset, n_held = held
    h2 = norm(x, p["ln2"], w["rms_norm_eps"])
    logits = h2 @ p["router"]
    top, chosen = lax.top_k(logits, k + 1)
    edge = chosen[:, k - 1:] - offset
    here = jnp.any((edge >= 0) & (edge < n_held), axis=-1)
    gap = jnp.where(here, top[:, k - 1] - top[:, k], jnp.inf)
    prob = jax.nn.softmax(logits, axis=-1)
    pk = jnp.take_along_axis(prob, chosen[:, :k], axis=-1)
    wk = pk / jnp.sum(pk, axis=-1, keepdims=True) if w["norm_topk_prob"] else pk

    def one(y, e):
        mine = jnp.sum(jnp.where(chosen[:, :k] == offset + e, wk, 0.0), axis=-1)
        f32 = [stacks[n][li, e].astype(jnp.float32)
               for n in ("w_gate", "w_up", "w_down")]
        return y + mine[:, None] * swiglu(h2, *f32), None

    routed, _ = lax.scan(one, jnp.zeros_like(x), jnp.arange(n_held))
    shared = swiglu(h2, p["ws_gate"], p["ws_up"], p["ws_down"])
    return x + routed + jax.nn.sigmoid(h2 @ p["w_sg"])[:, None] * shared, gap


def _f32(tree, *index):
    return jax.tree.map(lambda a: a[index].astype(jnp.float32), tree)


@partial(jax.jit, static_argnames=("j", "w", "held", "state_dtype", "mixer"))
def _linear_layer(blocks, stacks, u, *, j, li, x, w, held, state_dtype, mixer):
    w = dict(w)
    with jax.default_matmul_precision("highest"):
        if mixer:
            x, _, _ = delta_rule(_f32(blocks["lin"][j], u), x, w,
                                 jnp.dtype(state_dtype))
        return experts(_f32(blocks["moe"][j], u), stacks, li, x, w, held)


@partial(jax.jit, static_argnames=("j", "w", "held", "mixer"))
def _full_layer(blocks, stacks, u, *, j, li, x, w, held, mixer):
    w = dict(w)
    with jax.default_matmul_precision("highest"):
        if mixer:
            x = attention(_f32(blocks["full"], u), x, w)
        return experts(_f32(blocks["moe"][j], u), stacks, li, x, w, held)


@partial(jax.jit, static_argnames=("eps",))
def _head(ln_f, unembed, x, *, eps):
    with jax.default_matmul_precision("highest"):
        return norm(x, ln_f.astype(jnp.float32), eps) @ unembed.astype(jnp.float32)


def forward(params, tokens, w: dict, *, held: tuple[int, int] | None = None,
            skip_mixers: tuple = (), state_dtype: str = "float32"):
    """``(logits [T, V] float32, gap [T])`` of one sequence ``tokens [T]``:
    the full forward pass, and each position's smallest router gap over
    the layers.  ``held = (offset, count)`` says which experts the weights'
    stacks hold (default: all the router's).  ``skip_mixers`` (layers whose
    mixer is left out) and ``state_dtype`` are for the negative controls."""
    if held is None:
        held = (0, w["num_experts"])
    frozen = tuple(sorted(w.items()))
    interval = w["full_attention_interval"]
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    gap = jnp.full((x.shape[0],), jnp.inf)
    for li in range(w["num_hidden_layers"]):
        u, j = divmod(li, interval)
        common = dict(w=frozen, held=held, mixer=li not in skip_mixers)
        if j < interval - 1:
            x, g = _linear_layer(params["blocks"], params["experts"], u, j=j,
                                 li=li, x=x, state_dtype=state_dtype, **common)
        else:
            x, g = _full_layer(params["blocks"], params["experts"], u, j=j,
                               li=li, x=x, **common)
        gap = jnp.minimum(gap, g)
    return _head(params["ln_f"], params["unembed"], x, eps=w["rms_norm_eps"]), gap
