"""The plain reference of the ``zaya`` family: the decoder written from its
layer equations in straightforward ``jax.numpy``, float32, matmul precision
``highest``; no kernel, no cache, no page, no slot, no window, no import of
``ddl25spring_tpu/models``.  ``families/zaya.py`` states the equations'
source and what was assumed; this file is only the arithmetic.

``w`` is the configuration's widths as a plain dict (``families/zaya.py``
``widths``).  The weights are the family file's own (``init_params``), in the
layout it documents; they are stored in bfloat16 and upcast here, one layer
at a time and within a layer one routed expert at a time, so that the
reference fits beside the served model; the head is taken in blocks of the
table's rows (:func:`head`), never as ``[T, 262272]`` at once.

Both convolutions and the value half are EXPLICIT SHIFTS of the whole
sequence (:func:`shift`: rows moved later, zeros in front), attention is a
plain causal softmax over the whole sequence, the router's state passes from
layer to layer as a plain argument, and the experts are a loop over the held
ones, each applied to every position and weighted by what the router gave
it there (zero where it was not chosen): no sort, no grouping, nothing that
could drop a position.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def shift(a, n: int):
    """``a [T, ...]`` with every row ``n`` positions later and zeros in
    front: ``shift(a, n)[t] = a[t - n]``, zero for ``t < n``."""
    return a if n == 0 else jnp.concatenate([jnp.zeros_like(a[:n]), a[:-n]])


def conv_depthwise(u, taps, bias):
    """Causal depthwise convolution over the sequence: ``taps [k, C]``, the
    LAST tap is the current position's."""
    k = taps.shape[0]
    return bias + sum(shift(u, d) * taps[k - 1 - d] for d in range(k))


def conv_grouped(c, taps, bias):
    """Causal grouped convolution, a head a group: ``taps [k, heads, hd,
    hd]``, the last tap the current position's.  Its input is padded with
    ZEROS before position 0 (``shift``), not with what the depthwise
    convolution would make of zeros."""
    k, heads, hd, _ = taps.shape
    ch = c.reshape(c.shape[0], heads, hd)
    out = sum(jnp.einsum("thd,hde->the", shift(ch, d), taps[k - 1 - d])
              for d in range(k))
    return out.reshape(c.shape) + bias


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


def qk_mean(qt, kt):
    """``(mq [T, H, hd], mk [T, KV, hd])``: every query head averaged with
    its KV head's key latent, and each KV head's mean of those."""
    H, KV = qt.shape[1], kt.shape[1]
    mq = (qt + jnp.repeat(kt, H // KV, axis=1)) / 2
    return mq, mq.reshape(qt.shape[0], KV, H // KV, -1).mean(axis=2)


def unit_heads(a, hd: int, eps: float, hp):
    """Each head of ``a [T, heads, hd]`` at length ``sqrt(hd)``, computed
    in ``hp``."""
    a = a.astype(hp)
    n = jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + jnp.asarray(eps, hp))
    return (a * jnp.asarray(hd ** 0.5, hp) / n).astype(jnp.float32)


def cca_parts(p, h, w: dict, hp=jnp.float32):
    """The pieces of CCA on ``h [T, D]``: ``u, c1, c2, mq, mk, q, k, v,
    v_next`` (``q``, ``k`` normalised and turned; ``v [T, KV, hd]``)."""
    T = h.shape[0]
    H, KV, hd = w["num_attention_heads"], w["num_key_value_heads"], w["head_dim"]
    qt, kt = h @ p["wq"], h @ p["wk"]
    u = jnp.concatenate([qt, kt], axis=-1)
    c1 = conv_depthwise(u, p["conv_dw"], p["conv_dw_b"])
    c2 = conv_grouped(c1, p["conv_g"], p["conv_g_b"])
    mq, mk = qk_mean(qt.reshape(T, H, hd), kt.reshape(T, KV, hd))
    q = c2[:, :H * hd].reshape(T, H, hd) + mq
    k = c2[:, H * hd:].reshape(T, KV, hd) + mk
    q = unit_heads(q, hd, w["qk_norm_eps"], hp)
    k = unit_heads(k, hd, w["qk_norm_eps"], hp) * p["tau"][:, None]
    pos = jnp.arange(T)
    q, k = rope(q, pos, w), rope(k, pos, w)
    v_next = h @ p["wv2"]
    v = jnp.concatenate([h @ p["wv1"], shift(v_next, 1)], axis=-1)
    return dict(u=u, c1=c1, c2=c2, mq=mq, mk=mk, q=q, k=k,
                v=v.reshape(T, KV, hd), v_next=v_next)


def cca(p, h, w: dict, hp=jnp.float32):
    """``(CCA(h) [T, D], parts)``."""
    T = h.shape[0]
    H, KV, hd = w["num_attention_heads"], w["num_key_value_heads"], w["head_dim"]
    parts = cca_parts(p, h, w, hp)
    k = jnp.repeat(parts["k"], H // KV, axis=1)
    v = jnp.repeat(parts["v"], H // KV, axis=1)
    scores = jnp.einsum("thd,shd->hts", parts["q"], k) * hd ** -0.5
    pos = jnp.arange(T)
    causal = pos[:, None] >= pos[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shd->thd", probs, v).reshape(T, H * hd)
    return o @ p["wo"], parts


def gelu(x):
    return 0.5 * x * (1.0 + lax.erf(x / np.sqrt(2.0).astype(np.float32)))


def router(p, h, r_prev, w: dict, hp=jnp.float32):
    """``(chosen [T], weight [T], gap [T], r [T, R])`` of ``h = norm(x;
    ln2)`` and the previous layer's ``r``: ``gap`` is the largest of ``p +
    bias`` less the second largest."""
    cast = partial(jnp.asarray, dtype=hp)
    r = cast(h) @ cast(p["r_down"]) + cast(p["r_down_b"]) + cast(p["r_gamma"]) * cast(r_prev)
    z = cast(norm(r.astype(jnp.float32), p["r_ln"], w["rms_norm_eps"]))
    z = gelu(z @ cast(p["r_w1"]) + cast(p["r_b1"]))
    z = gelu(z @ cast(p["r_w2"]) + cast(p["r_b2"]))
    z = (z @ cast(p["r_w3"]) + cast(p["r_b3"])).astype(jnp.float32)
    prob = jax.nn.softmax(z, axis=-1)
    top, idx = lax.top_k(prob + p["r_bias"], 2)
    weight = jnp.take_along_axis(prob, idx[:, :1], axis=-1)[:, 0]
    return idx[:, 0], weight, top[:, 0] - top[:, 1], r.astype(jnp.float32)


def swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def experts(stacks, li, h, chosen, weight, held: tuple[int, int],
            mantissa_bits: int | None = None):
    """The held experts' part of ``MoE``: expert ``e`` applied to every
    position, kept where the router chose it.  With ``mantissa_bits`` each
    expert's weights are rounded to that many as they are read (3: what
    e4m3 keeps; a weight that has no more is left as it is)."""
    offset, n_held = held

    def stored(w):
        return w if mantissa_bits is None else lax.reduce_precision(
            w, exponent_bits=8, mantissa_bits=mantissa_bits)

    def one(y, e):
        mine = jnp.where(chosen == offset + e, weight, 0.0)
        f32 = [stored(stacks[n][li, e]).astype(jnp.float32)
               for n in ("w_gate", "w_up", "w_down")]
        return y + mine[:, None] * swiglu(h, *f32), None

    y, _ = lax.scan(one, jnp.zeros_like(h), jnp.arange(n_held))
    return y


def layer(p, stacks, li, x, r_prev, w: dict, held, hp=jnp.float32,
          attention: bool = True, expert_bits: int | None = None):
    """One layer: ``(x, r, gap, kept)``; ``kept`` holds what a test reads
    (``u``, ``v_next``, ``chosen``, ``weight``)."""
    eps = w["rms_norm_eps"]
    out, parts = cca(p, norm(x, p["ln1"], eps), w, hp)
    x = p["a1"] * x + (p["b1"] * out if attention else 0.0)
    h = norm(x, p["ln2"], eps)
    chosen, weight, gap, r = router(p, h, r_prev, w, hp)
    x = p["a2"] * x + p["b2"] * experts(stacks, li, h, chosen, weight, held,
                                        expert_bits)
    return x, r, gap, dict(u=parts["u"], v_next=parts["v_next"], chosen=chosen,
                           weight=weight)


def _f32(tree, *index):
    return jax.tree.map(lambda a: a[index].astype(jnp.float32), tree)


@partial(jax.jit, static_argnames=("w", "held", "hp", "attention", "expert_bits"))
def _layer(blocks, stacks, li, x, r, *, w, held, hp, attention, expert_bits):
    with jax.default_matmul_precision("highest"):
        return layer(_f32(blocks, li), stacks, li, x, r, dict(w), held,
                     jnp.dtype(hp), attention, expert_bits)


@partial(jax.jit, static_argnames=("eps",))
def _final_norm(ln_f, x, *, eps):
    return norm(x, ln_f.astype(jnp.float32), eps)


@jax.jit
def _head_block(table, h, start, cols, targets):
    """Of the logits ``h E[start : start + n]^T``: the largest, their sum,
    the ``targets``' where they fall in the block (else ``-inf``), and
    those at ``cols`` where they do (else 0)."""
    with jax.default_matmul_precision("highest"):
        z = h @ table.astype(jnp.float32).T  # [T, n]
    n = table.shape[0]

    def local(ids):
        at = ids - start
        return (at >= 0) & (at < n), jnp.clip(at, 0, n - 1)

    t_in, t_at = local(targets)
    at_target = jnp.where(
        t_in, jnp.take_along_axis(z, t_at[:, None], axis=-1)[:, 0], -jnp.inf)
    c_in, c_at = local(cols)
    at_cols = jnp.where(c_in[None, :], z[:, c_at], 0.0)
    return z.max(axis=-1), z.sum(axis=-1), at_target, at_cols


def head(table, h, cols, targets, block: int = 16384):
    """``(kept [T, len(cols)], top [T], mean [T], at_target [T])`` of the
    logits ``h E^T`` over EVERY row of the table ``E [V, D]`` (the
    embedding table, used as the head), taken ``block`` rows at a time."""
    cols, targets = jnp.asarray(cols), jnp.asarray(targets)
    T = h.shape[0]
    top, at_target = jnp.full((T,), -jnp.inf), jnp.full((T,), -jnp.inf)
    total, kept = jnp.zeros((T,)), jnp.zeros((T, cols.shape[0]))
    for start in range(0, table.shape[0], block):
        t, s, a, c = _head_block(
            table[start:start + block], h, start, cols, targets)
        top, at_target = jnp.maximum(top, t), jnp.maximum(at_target, a)
        total, kept = total + s, kept + c
    return kept, top, total / table.shape[0], at_target


@jax.jit
def head_at(table, h, cols):
    """The logits ``h E[cols]^T`` alone: ``[T, len(cols)]``."""
    with jax.default_matmul_precision("highest"):
        return h @ table[cols].astype(jnp.float32).T


def forward(params, tokens, w: dict, *, held: tuple[int, int] | None = None,
            high_prec: str = "float32", expert_bits: int | None = None,
            skip_attention: tuple = (), keep: bool = False):
    """``(h [T, D], gap [T], kept)`` of one sequence ``tokens [T]``: the
    hidden state after the final norm (:func:`head` or a plain ``h @
    E.T`` makes logits of it), each position's smallest top-2 gap of the
    router's ``p + bias`` over the layers, and with ``keep`` a layer's
    ``u``, ``v_next``, chosen expert and its weight.  ``held = (offset, count)`` says
    which experts the stacks hold (default: all).  ``high_prec`` (the type
    of the router and of the q/k normalisation) and ``expert_bits`` (the
    mantissa bits the experts' weights are rounded to as they are read)
    state the SAME model at the next lower precision: ``families/zaya.py``
    holds the served logits to both statements.  ``skip_attention`` (layers
    whose CCA is left out) is for the planted fault."""
    if held is None:
        held = (0, w["num_experts"])
    frozen = tuple(sorted(w.items()))
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    r = jnp.zeros((x.shape[0], w["router_hidden_size"]))
    gap = jnp.full((x.shape[0],), jnp.inf)
    kept = []
    for li in range(w["num_hidden_layers"]):
        x, r, g, k = _layer(params["blocks"], params["experts"], li, x, r,
                            w=frozen, held=held, hp=high_prec,
                            attention=li not in skip_attention,
                            expert_bits=expert_bits)
        gap = jnp.minimum(gap, g)
        if keep:
            kept.append(k)
    return _final_norm(params["ln_f"], x, eps=w["rms_norm_eps"]), gap, kept


def logits(params, h):
    """All the logits of ``h [T, D]``, at once: for small tables."""
    with jax.default_matmul_precision("highest"):
        return h @ params["embed"].astype(jnp.float32).T
