"""A decoder as ``model_type: zaya`` configures it, served through the page
pool: compressed convolutional attention (CCA) whose query and key latents
pass two causal convolutions over the sequence and whose values take half
their channels from the PREVIOUS token, and in every layer a float32 MLP
router over a narrow state that it also hands to the next layer's router,
top-1 over routed experts that drop nothing, no shared expert; one
embedding table that is also the head.  Every width is data
(:class:`ZayaConfig`).

**What the pool holds** (:mod:`ddl25spring_tpu.serve.paged_model`), in
EVERY layer both: the planes ``k``, ``v`` of ``(kv heads x head_dim,)`` (what
attention reads of a position: ONE row of 256, of which a KV head is a
128-aligned slice; declared as ``(2, 128)`` the chip lays a page out in
tiles of 2 x 128 and gathers it at a quarter of the rate, PERF.md section 6,
PR 36), and a slot of state a sequence, in the
served type: ``conv (cca_time0 + cca_time1 - 2, channels)``, the last rows
of the pre-convolution latents ``u = [q | k]`` (rows of ``u`` alone, not a
row of the first convolution's output: a tick recomputes that one row from
them in float32 exactly as the prompt pass computed it, so the two agree to
the bit), and ``vprev (kv heads head_dim / 2,)``, the value half that the
NEXT position will use.  Across layers a position hands on ``r [B, T,
router_hidden_size]`` in ``high_prec`` (float32): the seam's ``carry``.

**What one chip holds** of a deployment: whole layers (all ``num_experts``
of each, unless ``experts_held`` / ``expert_offset`` say a share, which the
expert layer computes the part of as in every routed model here) and the
whole table.

**The layer** (``x [B, T, D]``; ``norm(h; w) = h rsqrt(mean h^2 + eps) w``
in float32; ``a``, ``b`` per-channel float32 scales): ``x <- a1 x + b1
CCA(norm(x; ln1))``; ``x <- a2 x + b2 MoE(norm(x; ln2), r_prev)``.

- CCA on ``h``: ``qt = h W_q [H, hd]``, ``kt = h W_k [KV, hd]``; ``u = [qt
  | kt]``; ``c1 = conv_dw(u)``, causal depthwise over the sequence, kernel
  ``cca_time0``, bias; ``c2 = conv_g(c1)``, causal grouped (a head a group:
  a ``hd x hd`` matrix a tap), kernel ``cca_time1``, bias; both left-padded
  with zeros (position 0 sees itself only; the newest tap is the last); the
  convolutions accumulate in float32.  With ``G = H / KV``: ``mq[i] =
  (qt[i] + kt[i // G]) / 2``, ``mk[j]`` the mean of ``mq`` over group
  ``j``; ``q = c2[:H] + mq``, ``k = c2[H:] + mk``; ``q <- q sqrt(hd)
  rsqrt(sum q^2 + qk_norm_eps)`` a head, ``k`` likewise times ``tau[j]``
  (in ``high_prec``); rotary on the first ``partial_rotary_factor hd`` dims
  of each head, halves rotated; values ``v_t = [h_t W_v1 | h_{t-1} W_v2]``
  as ``[KV, hd]`` (``h_{-1} = 0``); causal softmax in float32 at scale
  ``hd^-0.5``, a KV head serving ``G`` consecutive query heads; ``attn
  W_o``.
- Router on ``norm(x; ln2)`` in ``high_prec``, matrix products at the
  highest precision: ``r = h W_down + b_down + gamma r_prev`` (zeros before
  layer 0), handed on; ``z = W3 gelu(W2 gelu(W1 norm(r; w_r) + b1) + b2) +
  b3`` (erf GELU); ``p = softmax(z)``; expert ``argmax(p + bias)``, weight
  ``p`` there, not renormalised.
- ``MoE = p[e] SwiGLU_e(h)`` through :mod:`.routed_experts` with ``k = 1``.
- Final ``norm``; logits ``x E^T`` over every row of the table ``E``.

``T = 1`` and ``T = W`` are one function (:func:`cca`): a pass lays its
positions behind a window of earlier rows, which a prompt pass (``T > 1``:
every pass starts a sequence) fills with zeros and a tick (``T = 1``) with
the slot's state; padded positions write nothing, and a prompt pass SEATS,
at the row's slot, the window as it stands after the row's last LIVE
position.

Parameters (matrices in ``cfg.dtype``, resident: nothing is cast at use;
norm scales, ``a``/``b``, ``tau``, the convolutions' biases and every leaf
of the router float32; whoever serves the model brings them, as
``benchmark/families/zaya.py`` draws seeded ones): ``embed [V, D]``, used
twice; ``blocks`` stacked ``[L, ...]`` and scanned (``ln1 a1 b1 wq wk wv1
wv2 conv_dw [k0, C] conv_dw_b conv_g [k1, H + KV, hd, hd] conv_g_b tau wo
ln2 a2 b2 r_down r_down_b r_gamma r_ln r_w1 r_b1 r_w2 r_b2 r_w3 r_b3
r_bias``); ``experts`` = ``w_gate, w_up [L, E, D, F]``, ``w_down [L, E, F,
D]``, NOT scanned; ``ln_f [D]``.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from ddl25spring_tpu.models.llama import rms_norm
from ddl25spring_tpu.models.qwen3_next import _rope, rope_tables
from ddl25spring_tpu.models.routed_experts import (
    pass_stats as moe_pass_stats,
    routed_experts,
)
from ddl25spring_tpu.serve import kv_pages
from ddl25spring_tpu.serve.paged_model import PagedModel

F32 = jnp.float32


@dataclass(frozen=True)
class ZayaConfig:
    """The published keys of a ``zaya`` ``config.json`` (same names), and
    this chip's share of the deployment."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    moe_intermediate_size: int
    num_experts: int                # the router's width: ALL experts
    router_hidden_size: int
    num_experts_per_tok: int = 1
    cca_time0: int = 2
    cca_time1: int = 2
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5e6
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    experts_held: int | None = None  # None: all of them
    expert_offset: int = 0
    qk_norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    high_prec: str = "float32"      # the router's and the q/k norm's type

    def __post_init__(self):
        held = self.n_held
        if not (0 < held and self.expert_offset >= 0
                and self.expert_offset + held <= self.num_experts):
            raise ValueError(
                f"experts {self.expert_offset}..{self.expert_offset + held}"
                f" are not among the router's {self.num_experts}"
            )
        if self.num_experts_per_tok != 1:
            raise ValueError(
                "the zaya router takes ONE expert a position, weighted by "
                f"its probability: num_experts_per_tok="
                f"{self.num_experts_per_tok}"
            )
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads are whole groups of KV heads")
        if (self.num_key_value_heads * self.head_dim) % 2:
            raise ValueError("the values split into two halves by channel")
        if min(self.cca_time0, self.cca_time1) < 1 or self.conv_tail < 1:
            raise ValueError(
                f"cca_time0={self.cca_time0}, cca_time1={self.cca_time1}: "
                "kernels of at least one tap that together look back"
            )
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError("RoPE rotates halves: an even rotary_dim <= head_dim")

    @property
    def n_held(self) -> int:
        return self.num_experts if self.experts_held is None else self.experts_held

    @property
    def n_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def ctx_size(self) -> int:
        return self.max_position_embeddings

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def conv_channels(self) -> int:
        """Channels of ``u = [q | k]``."""
        return (self.num_attention_heads + self.num_key_value_heads) * self.head_dim

    @property
    def conv_tail(self) -> int:
        """Rows of ``u`` before a position that its two convolutions read."""
        return self.cca_time0 + self.cca_time1 - 2

    @property
    def value_half(self) -> int:
        return self.num_key_value_heads * self.head_dim // 2

    def paged_model(self) -> PagedModel:
        return paged_model(self)


# --------------------------------------------------------------------- CCA


def cca_convs(p, window, first_pos, cfg: ZayaConfig):
    """``c2 [B, T, C]`` float32 of the ``T`` positions that end ``window
    [B, conv_tail + T, C]`` (rows of ``u``; the first of the ``T`` at
    absolute position ``first_pos [B]``): the depthwise convolution, whose
    rows BEFORE position 0 are the zero padding of the grouped one (not the
    bias a convolution of zeros would give), then the grouped one."""
    k0, k1 = cfg.cca_time0, cfg.cca_time1
    B, n, C = window.shape
    T = n - cfg.conv_tail
    hd, heads = cfg.head_dim, C // cfg.head_dim
    n1 = T + k1 - 1  # rows of c1 that the T positions read
    wf = window.astype(F32)
    c1 = sum(wf[:, j:j + n1] * p["conv_dw"][j].astype(F32) for j in range(k0))
    c1 = c1 + p["conv_dw_b"].astype(F32)
    at = first_pos[:, None] - (k1 - 1) + jnp.arange(n1, dtype=jnp.int32)
    c1 = jnp.where((at >= 0)[:, :, None], c1, 0.0)
    c1h = c1.astype(window.dtype).reshape(B, n1, heads, hd)
    c2 = sum(
        jnp.einsum("bthd,hde->bthe", c1h[:, j:j + T], p["conv_g"][j],
                   preferred_element_type=F32)
        for j in range(k1)
    )
    return c2.reshape(B, T, C) + p["conv_g_b"].astype(F32)


def cca(p, x, cache, layer, slots, rows, pages, offs, pos, live, cos, sin,
        cfg: ZayaConfig):
    """``CCA(norm(x; ln1))`` through the planes ``k``/``v`` and the slot
    state ``conv``/``vprev`` at ``layer``, for any ``T``: see the module's
    text.  Scopes ``cca_proj`` / ``cca_conv`` / ``cca_mean_norm`` /
    ``cca_shift`` / ``page_write`` / ``page_gather`` / ``attn``."""
    B, T, _ = x.shape
    H, KV, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    G, tail = H // KV, cfg.conv_tail
    dtype, hp = x.dtype, jnp.dtype(cfg.high_prec)
    conv_all, vprev_all = cache["conv"], cache["vprev"]

    with jax.named_scope("cca_proj"):
        h = rms_norm(x, p["ln1"], cfg.rms_norm_eps)
        u = jnp.concatenate([h @ p["wq"], h @ p["wk"]], axis=-1)  # [B, T, C]
        v_now, v_next = h @ p["wv1"], h @ p["wv2"]

    with jax.named_scope("cca_shift"):
        # the rows before this pass's are the slot's (a tick continues its
        # sequence) or none (a prompt pass starts one); the slot keeps the
        # window as it stands after the row's last LIVE position
        if T == 1:
            u_win = jnp.concatenate([conv_all[:, layer], u], axis=1)
            v_win = jnp.concatenate([vprev_all[:, layer][:, None], v_next], axis=1)
            keep = live[:, :, None]
            conv_all = conv_all.at[:, layer].set(
                jnp.where(keep, u_win[:, 1:], u_win[:, :-1]))
            vprev_all = vprev_all.at[:, layer].set(
                jnp.where(keep[:, 0], v_next[:, 0], v_win[:, 0]))
        else:
            u_win = jnp.pad(u, ((0, 0), (tail, 0), (0, 0)))
            v_win = jnp.pad(v_next, ((0, 0), (1, 0), (0, 0)))
            # a row that is seated: its slot; a padding row falls off the end
            seat = jnp.where(slots >= 0, slots, conv_all.shape[0])
            n_row = jnp.sum(live, axis=1, dtype=jnp.int32)
            at = n_row[:, None] + jnp.arange(tail, dtype=jnp.int32)[None, :]
            conv_all = conv_all.at[seat, layer].set(
                jnp.take_along_axis(u_win, at[:, :, None], axis=1), mode="drop")
            vprev_all = vprev_all.at[seat, layer].set(
                jnp.take_along_axis(v_win, n_row[:, None, None], axis=1)[:, 0],
                mode="drop")
        v = jnp.concatenate([v_now, v_win[:, :T]], axis=-1)  # [B, T, KV * hd]

    with jax.named_scope("cca_conv"):
        c2 = cca_convs(p, u_win, pos[:, 0], cfg)

    with jax.named_scope("cca_mean_norm"):
        qt = u[..., :H * hd].astype(F32).reshape(B, T, KV, G, hd)
        kt = u[..., H * hd:].astype(F32).reshape(B, T, KV, 1, hd)
        mq = (qt + kt) * 0.5
        mk = jnp.mean(mq, axis=3)
        q = (c2[..., :H * hd].reshape(B, T, KV, G, hd) + mq).astype(hp)
        k = (c2[..., H * hd:].reshape(B, T, KV, hd) + mk).astype(hp)

        def unit(a):
            ss = jnp.sum(a * a, axis=-1, keepdims=True)
            return a * (hd ** 0.5) * lax.rsqrt(ss + cfg.qk_norm_eps)

        q = unit(q).reshape(B, T, H, hd)
        k = unit(k) * p["tau"].astype(hp)[:, None]
        q = _rope(q, cos, sin).astype(dtype)
        k = _rope(k, cos, sin).astype(dtype)

    pages_kv = {"k": cache["k"], "v": cache["v"]}
    with jax.named_scope("page_write"):
        pages_kv = kv_pages.write_planes(
            pages_kv, layer, pages, offs,
            {"k": k.reshape(B, T, KV * hd), "v": v},
        )
    with jax.named_scope("page_gather"):
        view = kv_pages.gather_planes(pages_kv, layer, rows)
        ks, vs = view["k"], view["v"]  # [B, M, KV * hd]
        M = ks.shape[1]
    with jax.named_scope("attn"):
        qh = q.reshape(B, T, KV, G, hd)
        seen = jnp.arange(M)[None, None, :] <= pos[:, :, None]  # [B, T, M]
        heads = []
        for j in range(KV):  # a KV head is a 128-aligned slice of the row
            at = slice(j * hd, (j + 1) * hd)
            s = jnp.einsum("btgd,bmd->bgtm", qh[:, :, j], ks[..., at],
                           preferred_element_type=F32) * hd ** -0.5
            s = jnp.where(seen[:, None, :, :], s, -1e30)
            probs = jax.nn.softmax(s, axis=-1).astype(dtype)
            heads.append(jnp.einsum("bgtm,bmd->btgd", probs, vs[..., at]))
        o = jnp.stack(heads, axis=2).reshape(B, T, H * hd)
        out = o @ p["wo"]
    return out, {**cache, **pages_kv, "conv": conv_all, "vprev": vprev_all}


# ------------------------------------------------------ router and experts


def zaya_route(p, h, r_prev, cfg: ZayaConfig):
    """``(experts [N, 1] int32, weights [N, 1] float32, r)`` of ``h [B, T,
    D]`` (``norm(x; ln2)`` in ``high_prec``) and the previous layer's router
    state ``r_prev [B, T, R]``: the whole router in ``high_prec``, its
    matrix products at the highest precision."""
    hp = jnp.dtype(cfg.high_prec)
    hi = lax.Precision.HIGHEST

    def lin(a, w, b):
        return jnp.dot(a, p[w].astype(hp), precision=hi) + p[b].astype(hp)

    r = lin(h, "r_down", "r_down_b") + p["r_gamma"].astype(hp) * r_prev
    z = rms_norm(r, p["r_ln"], cfg.rms_norm_eps)
    z = jax.nn.gelu(lin(z, "r_w1", "r_b1"), approximate=False)
    z = jax.nn.gelu(lin(z, "r_w2", "r_b2"), approximate=False)
    prob = jax.nn.softmax(lin(z, "r_w3", "r_b3").astype(F32), axis=-1)
    prob = prob.reshape(-1, prob.shape[-1])
    chosen = jnp.argmax(prob + p["r_bias"].astype(F32), axis=-1)
    weight = jnp.take_along_axis(prob, chosen[:, None], axis=-1)
    return chosen[:, None].astype(jnp.int32), weight, r


def moe(p, x, r_prev, live, stacks, layer, cfg: ZayaConfig):
    """``(MoE(norm(x; ln2), r_prev) [B, T, D] float32, load, r)``: the
    router reads the norm in ``high_prec`` as it comes, the experts in the
    served type; scopes ``router`` / ``experts``."""
    B, T, D = x.shape
    with jax.named_scope("router"):
        h = rms_norm(x.astype(cfg.high_prec), p["ln2"], cfg.rms_norm_eps)
        experts, weights, r = zaya_route(p, h, r_prev, cfg)
    with jax.named_scope("experts"):
        y, load = routed_experts(
            h.astype(x.dtype).reshape(B * T, D), experts, weights,
            live.reshape(-1), stacks, layer, cfg
        )
    return y.reshape(B, T, D), load, r


def scaled_sum(a, x, b, y):
    """``a x + b y`` in float32, in ``x``'s type: the residual merge."""
    out = a.astype(F32) * x.astype(F32) + b.astype(F32) * y.astype(F32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- the seam


def paged_model(cfg: ZayaConfig) -> PagedModel:
    dtype = jnp.dtype(cfg.dtype)

    def layers(params, slots, rows, pages, offs, pos, live, tp_axis):
        if tp_axis is not None:
            raise ValueError("zaya offers no tensor-parallel block")
        cos, sin = rope_tables(pos, cfg)
        stacks = params["experts"]
        n_live = jnp.sum(live, dtype=jnp.int32)

        def run_layer(p, li, x, cache, r):
            out, cache = cca(p, x, cache, li, slots, rows, pages, offs, pos,
                             live, cos, sin, cfg)
            x = scaled_sum(p["a1"], x, p["b1"], out)
            y, load, r = moe(p, x, r, live, stacks, li, cfg)
            x = scaled_sum(p["a2"], x, p["b2"], y)
            return x, cache, jnp.append(load, n_live), r

        return run_layer

    def unembed(params, x):
        h = rms_norm(x, params["ln_f"], cfg.rms_norm_eps)
        return jnp.einsum("btd,vd->btv", h, params["embed"],
                          preferred_element_type=F32)

    kv = (cfg.num_key_value_heads * cfg.head_dim,)
    return PagedModel(
        planes={"k": kv, "v": kv},
        slot_state={
            "conv": ((cfg.conv_tail, cfg.conv_channels), cfg.dtype),
            "vprev": ((cfg.value_half,), cfg.dtype),
        },
        state_layers=cfg.n_layers,
        n_layers=cfg.n_layers,
        dtype=cfg.dtype,
        embed=lambda params, tokens: params["embed"].astype(dtype)[tokens],
        unembed=unembed,
        layers=layers,
        pass_stats=lambda aux: moe_pass_stats(aux, cfg),
        carry=lambda x: jnp.zeros(
            (*x.shape[:2], cfg.router_hidden_size), jnp.dtype(cfg.high_prec)),
    )
