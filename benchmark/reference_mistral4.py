"""The plain reference of the ``mistral4`` family: the decoder written from
its layer equations in straightforward ``jax.numpy``, float32, matmul
precision ``highest``; no kernel, no cache, no page, no import of
``ddl25spring_tpu/models/mistral4.py``.  ``families/mistral4.py`` states the
equations' source and what was assumed; this file is only the arithmetic.

``w`` is the configuration's widths as a plain dict (``families/mistral4.py``
``widths``).  The weights are the family file's own (``init_params``), in
the layout it documents; they are stored in bfloat16 and upcast here, one
layer at a time and within a layer one routed expert at a time, so that the
reference fits beside the served model (a layer's held experts would be 3.2
GB in float32; one expert is 0.1).

Attention is the NON-absorbed association only: every position's ``c_kv`` is
projected to per-head keys and values and attended plainly.  The experts
are a loop over the held ones, each applied to every position and weighted
by what the router gave it there (zero where it was not chosen): no sort, no
grouping, nothing that could drop a position.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def yarn_inv_freq(w: dict) -> np.ndarray:
    d, base, factor = w["qk_rope_head_dim"], w["rope_theta"], w["rope_factor"]
    f = base ** (-np.arange(0, d, 2, dtype=np.float64) / d)

    def at(r):
        return (d * math.log(w["rope_original_max"] / (2 * math.pi * r))
                / (2 * math.log(base)))

    low = min(max(math.floor(at(w["rope_beta_fast"])), 0), d - 1)
    high = min(max(math.ceil(at(w["rope_beta_slow"])), 0), d - 1)
    g = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return (g * f / factor + (1.0 - g) * f).astype(np.float32)


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, pos, inv_freq):
    """``x [T, ..., d]``: interleaved pairs turned by ``pos * inv_freq``."""
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[-1],)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1)
    return out.reshape(x.shape)


def attention(p, x, w: dict):
    T = x.shape[0]
    H, dn, dr, dv, r = (w["num_attention_heads"], w["qk_nope_head_dim"],
                        w["qk_rope_head_dim"], w["v_head_dim"], w["kv_lora_rank"])
    eps = w["rms_norm_eps"]
    pos = jnp.arange(T)
    inv = jnp.asarray(yarn_inv_freq(w))
    h = rms_norm(x, p["ln1"], eps)
    q = (rms_norm(h @ p["wq_a"], p["q_norm"], eps) @ p["wq_b"]).reshape(T, H, dn + dr)
    q = q * (1.0 + w["llama_4_scaling_beta"] * jnp.log1p(
        jnp.floor(pos / w["rope_original_max"])))[:, None, None]
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos, inv)], axis=-1)
    kv = h @ p["wkv_a"]
    c_kv = rms_norm(kv[:, :r], p["kv_norm"], eps)
    k_pe = rope(kv[:, r:], pos, inv)  # [T, dr]: one head for all
    kv_h = (c_kv @ p["wkv_b"]).reshape(T, H, dn + dv)
    k = jnp.concatenate(
        [kv_h[..., :dn], jnp.broadcast_to(k_pe[:, None, :], (T, H, dr))], axis=-1
    )
    m = 0.1 * w["rope_mscale_all_dim"] * math.log(w["rope_factor"]) + 1.0
    scores = jnp.einsum("thd,shd->hts", q, k) * ((dn + dr) ** -0.5 * m * m)
    causal = pos[:, None] >= pos[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shv->thv", probs, kv_h[..., dn:])
    return x + o.reshape(T, H * dv) @ p["wo"]


def swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def experts(p, stacks, li, x, w: dict, held: tuple[int, int]):
    """``(x + held routed experts + shared expert, gap [T])``; ``gap`` is
    the router's 4th logit less its 5th (``k``-th less ``k + 1``-th)
    where one of the two experts is held here, else infinite: a flip
    between two experts on other chips changes nothing on this one but a
    normalising sum that the two leave nearly equal."""
    k = w["num_experts_per_tok"]
    offset, n_held = held
    h2 = rms_norm(x, p["ln2"], w["rms_norm_eps"])
    logits = h2 @ p["router"]
    top, chosen = lax.top_k(logits, k + 1)
    edge = chosen[:, k - 1:] - offset
    here = jnp.any((edge >= 0) & (edge < n_held), axis=-1)
    gap = jnp.where(here, top[:, k - 1] - top[:, k], jnp.inf)
    prob = jax.nn.softmax(logits, axis=-1)
    pk = jnp.take_along_axis(prob, chosen[:, :k], axis=-1)
    wk = pk / jnp.sum(pk, axis=-1, keepdims=True) * w["routed_scaling_factor"]

    def one(y, e):
        mine = jnp.sum(jnp.where(chosen[:, :k] == offset + e, wk, 0.0), axis=-1)
        f32 = [stacks[n][li, e].astype(jnp.float32)
               for n in ("w_gate", "w_up", "w_down")]
        return y + mine[:, None] * swiglu(h2, *f32), None

    routed, _ = lax.scan(one, jnp.zeros_like(x), jnp.arange(n_held))
    return x + routed + swiglu(h2, p["ws_gate"], p["ws_up"], p["ws_down"]), gap


@partial(jax.jit, static_argnames=("w", "held"))
def _layer(blocks, stacks, li, x, *, w, held):
    w = dict(w)
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a[li].astype(jnp.float32), blocks)
        return experts(p, stacks, li, attention(p, x, w), w, held)


@partial(jax.jit, static_argnames=("eps",))
def _head(ln_f, unembed, x, *, eps):
    with jax.default_matmul_precision("highest"):
        return (rms_norm(x, ln_f.astype(jnp.float32), eps)
                @ unembed.astype(jnp.float32))


def forward(params, tokens, w: dict, *, held: tuple[int, int] | None = None,
            skip_layers: tuple = ()):
    """``(logits [T, V] float32, gap [T])`` of one sequence ``tokens [T]``:
    the full forward pass, and each position's smallest router gap over
    the layers.  ``held = (offset, count)`` says which experts the weights'
    stacks hold (default: all the router's).  ``skip_layers`` is for the
    negative controls of the tests."""
    if held is None:
        held = (0, w["n_routed_experts"])
    frozen = tuple(sorted(w.items()))
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    gap = jnp.full((x.shape[0],), jnp.inf)
    for li in range(w["num_hidden_layers"]):
        if li in skip_layers:
            continue
        x, g = _layer(params["blocks"], params["experts"], li, x, w=frozen,
                      held=held)
        gap = jnp.minimum(gap, g)
    return _head(params["ln_f"], params["unembed"], x, eps=w["rms_norm_eps"]), gap
