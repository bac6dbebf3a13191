"""A DeepSeek-V3-style decoder block, as ``model_type: mistral4`` configures
it, served through the page pool: latent attention (MLA) with YaRN
frequencies, a softmax top-k router over routed experts that drops nothing,
and one shared expert.  Every width is data (:class:`Mistral4Config`).

**What one chip holds** of a deployment that shares each layer over several
chips by expert parallelism: the whole attention, the shared expert and the
router at its full width ``n_routed_experts``, the ``experts_held`` routed
experts that start at ``expert_offset``, and ``vocab_size`` rows of the
embedding and of the head.  A position routes over ALL experts; this chip
adds the part its held experts give (the weights still normalised over all
``num_experts_per_tok`` chosen) to the shared expert's, and that partial sum
goes on to the next layer.  Nothing here stands in for the absent chips or
their exchange.

**The layer** (``x [B, T, D]``; RMSNorm eps ``rms_norm_eps``; no bias):

- ``h = norm(x)``; ``c_q = norm_q(h W_qa)``; ``q = c_q W_qb`` -> heads of
  ``[q_nope | q_pe]``; ``[c | k_r] = h W_kva``; ``c_kv = norm_kv(c)``;
  ``k_pe = rope(k_r)``, one head shared by all; ``q_pe = rope(q_pe)``:
  interleaved pairs, YaRN frequencies (:func:`yarn_inv_freq`), cos and sin
  unscaled.  Queries are scaled by ``1 + beta ln(1 + floor(pos /
  original_max))``.  A position leaves ``c_kv`` and ``k_pe`` in the pool,
  and nothing else: the planes ``ckv (kv_lora_rank,)`` and ``kpe
  (qk_rope_head_dim,)``.
- ``[k_nope | v] = c_kv W_kvb`` per head; scores ``([q_nope | q_pe] .
  [k_nope | k_pe]) s`` with ``s = qk_head_dim^-0.5 m^2``, ``m = 0.1
  mscale_all_dim ln(factor) + 1``; causal softmax in float32.  Two
  associations of the same mathematics, chosen by ``T`` at trace time: a
  prompt batch (``T > 1``) projects the gathered ``c_kv`` to keys and
  values and attends plainly; ``T = 1`` ABSORBS the projection (``q' =
  q_nope W_kvb^K``, scores ``q' . c_kv + q_pe . k_pe``, ``o = (probs c_kv)
  W_kvb^V``), so that decode reads ``kv_lora_rank + qk_rope_head_dim``
  values a cached position and never builds per-head keys and values.
- ``h2 = norm(x')``; router logits ``h2 W_r`` in float32; ``p =
  softmax``; the ``k`` largest; weights ``p_e / sum`` (``norm_topk_prob``)
  times ``routed_scaling_factor``; ``E(h) = (silu(h W_g) * (h W_u)) W_d``;
  ``x_out = x' + sum over the chosen AND held e of w_e E_e(h2) + S(h2)``.

**The expert layer** (:mod:`.routed_experts`, shared with every served
model that routes: the router, the sort by held expert, three ``moe_gmm``
calls, the inverse permutation, the load counts) has static shapes and no
capacity.  The group sizes over the pass's live positions are the block's
``aux``: the held experts' load, a layer (kept by layer, because the sum
over layers hides how many experts one call reads), beside the count of
live positions.

Parameters (in ``cfg.dtype``, resident: nothing is cast at use and the
seam's ``resident`` stays the identity; whoever serves the model brings
them, as ``benchmark/families/mistral4.py`` draws seeded ones):
``embed [V, D]``; ``blocks`` stacked ``[L, ...]`` and scanned (``ln1 wq_a
q_norm wq_b wkv_a kv_norm wkv_b wo ln2 router ws_gate ws_up ws_down``);
``experts`` = ``w_gate, w_up [L, E, D, F]``, ``w_down [L, E, F, D]``, NOT
scanned (the kernel takes the whole stack and the layer's index: a slice
would be a copy); ``ln_f [D]``; ``unembed [D, V]``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ddl25spring_tpu.models.llama import rms_norm
from ddl25spring_tpu.models.routed_experts import (
    pass_stats as moe_pass_stats,
    route,
    routed_experts,
    swiglu,
)
from ddl25spring_tpu.serve import kv_pages
from ddl25spring_tpu.serve.paged_model import PagedModel


@dataclass(frozen=True)
class Mistral4Config:
    """The published keys of a ``mistral4`` ``config.json`` (same names),
    and this chip's share of the deployment."""

    vocab_size: int                 # rows held here (the slice)
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    moe_intermediate_size: int
    n_routed_experts: int           # the router's width: ALL experts
    num_experts_per_tok: int
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 8192
    rope_theta: float = 10000.0
    rope_factor: float = 1.0
    rope_original_max: int = 8192
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 1.0
    llama_4_scaling_beta: float = 0.0
    experts_held: int | None = None  # None: all of them
    expert_offset: int = 0
    dtype: str = "bfloat16"

    def __post_init__(self):
        held = self.n_held
        if not (0 < held and self.expert_offset >= 0
                and self.expert_offset + held <= self.n_routed_experts):
            raise ValueError(
                f"experts {self.expert_offset}..{self.expert_offset + held}"
                f" are not among the router's {self.n_routed_experts}"
            )
        if self.qk_rope_head_dim % 2:
            raise ValueError("RoPE rotates pairs: qk_rope_head_dim is even")
        if self.n_shared_experts != 1:
            raise ValueError("one shared expert, as every mistral4 config")
        if self.rope_factor <= 1.0:
            raise ValueError(
                f"rope_factor={self.rope_factor}: YaRN stretches the "
                "context (factor > 1), as every mistral4 config"
            )

    @property
    def n_held(self) -> int:
        return (self.n_routed_experts if self.experts_held is None
                else self.experts_held)

    @property
    def n_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def ctx_size(self) -> int:
        return self.max_position_embeddings

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        m = 0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) + 1.0
        return self.qk_head_dim ** -0.5 * m * m

    def paged_model(self) -> PagedModel:
        return paged_model(self)


def yarn_inv_freq(cfg: Mistral4Config) -> np.ndarray:
    """``[qk_rope_head_dim / 2]`` rotary frequencies: the base's where a
    pair turns more than ``beta_fast`` times over the original context,
    the base's over ``factor`` where it turns less than ``beta_slow``
    times, a linear ramp between."""
    d = cfg.qk_rope_head_dim
    f = cfg.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)

    def turns_at(r: float) -> float:
        return (d * math.log(cfg.rope_original_max / (2 * math.pi * r))
                / (2 * math.log(cfg.rope_theta)))

    low = min(max(math.floor(turns_at(cfg.rope_beta_fast)), 0), d - 1)
    high = min(max(math.ceil(turns_at(cfg.rope_beta_slow)), 0), d - 1)
    g = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (g * f / cfg.rope_factor + (1.0 - g) * f).astype(np.float32)


def _rope(x, cos, sin):
    """Interleaved pairs ``(x[2i], x[2i + 1])`` of ``x [B, T, ..., d]``
    turned by ``cos/sin [B, T, d/2]``."""
    shape = (*cos.shape[:2], *(1,) * (x.ndim - 3), cos.shape[-1])
    c, s = cos.reshape(shape), sin.reshape(shape)
    x1, x2 = x[..., 0::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    out = jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# --------------------------------------------------------------- the block


def mla_attention(p, x, planes, layer, rows, pages, offs, pos, cos, sin,
                  cfg: Mistral4Config):
    """``x + attention(norm(x))`` through the latent planes, for any
    ``T``: see the module's text.  Scopes ``mla_q`` / ``latent_write`` /
    ``latent_gather`` / ``attn``."""
    B, T = x.shape[:2]
    H = cfg.num_attention_heads
    dn, dr, dv, r = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.v_head_dim, cfg.kv_lora_rank)
    eps = cfg.rms_norm_eps
    dtype = x.dtype
    f32 = jnp.float32

    with jax.named_scope("mla_q"):
        h = rms_norm(x, p["ln1"], eps)
        c_q = rms_norm(h @ p["wq_a"], p["q_norm"], eps)
        q = (c_q @ p["wq_b"]).reshape(B, T, H, dn + dr)
        # llama-4 style query scaling: 1 below the original context
        beta = cfg.llama_4_scaling_beta
        grow = 1.0 + beta * jnp.log1p(
            jnp.floor(pos.astype(f32) / cfg.rope_original_max)
        )
        q = (q.astype(f32) * grow[:, :, None, None]).astype(dtype)
        q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], cos, sin)
        kv = h @ p["wkv_a"]
        c_kv = rms_norm(kv[..., :r], p["kv_norm"], eps)
        k_pe = _rope(kv[..., r:], cos, sin)
    with jax.named_scope("latent_write"):
        planes = kv_pages.write_planes(
            planes, layer, pages, offs, {"ckv": c_kv, "kpe": k_pe}
        )
    with jax.named_scope("latent_gather"):
        view = kv_pages.gather_planes(planes, layer, rows)
        ckv, kpe = view["ckv"], view["kpe"]  # [B, M, r], [B, M, dr]
        M = ckv.shape[1]

    with jax.named_scope("attn"):
        w_kvb = p["wkv_b"].reshape(r, H, dn + dv)
        s_pe = jnp.einsum("bthd,bmd->bhtm", q_pe, kpe,
                          preferred_element_type=f32)
        if T == 1:  # absorbed: the latent is the key and the value
            q_lat = jnp.einsum("bthd,rhd->bthr", q_nope, w_kvb[..., :dn])
            s = jnp.einsum("bthr,bmr->bhtm", q_lat, ckv,
                           preferred_element_type=f32)
        else:  # a prompt batch: keys and values projected after the gather
            kv_h = jnp.einsum("bmr,rhd->bmhd", ckv, w_kvb)
            s = jnp.einsum("bthd,bmhd->bhtm", q_nope, kv_h[..., :dn],
                           preferred_element_type=f32)
        s = (s + s_pe) * cfg.softmax_scale
        live = jnp.arange(M)[None, None, :] <= pos[:, :, None]
        s = jnp.where(live[:, None, :, :], s, -1e30)
        probs = jax.nn.softmax(s, axis=-1).astype(dtype)
        if T == 1:
            o_lat = jnp.einsum("bhtm,bmr->bthr", probs, ckv)
            o = jnp.einsum("bthr,rhv->bthv", o_lat, w_kvb[..., dn:])
        else:
            o = jnp.einsum("bhtm,bmhv->bthv", probs, kv_h[..., dn:])
        return x + o.reshape(B, T, H * dv) @ p["wo"], planes


def moe_ffn(p, x, live, stacks, layer, cfg: Mistral4Config):
    """``x + held routed experts + shared expert`` of ``norm(x)``; scopes
    ``router`` / ``experts`` / ``shared_expert``."""
    B, T, D = x.shape
    h2 = rms_norm(x, p["ln2"], cfg.rms_norm_eps)
    flat = h2.reshape(B * T, D)
    with jax.named_scope("router"):
        experts, weights = route(flat, p["router"], cfg)
    with jax.named_scope("experts"):
        routed, load = routed_experts(
            flat, experts, weights, live.reshape(-1), stacks, layer, cfg
        )
    with jax.named_scope("shared_expert"):
        shared = swiglu(h2, p["ws_gate"], p["ws_up"], p["ws_down"])
    y = routed.reshape(B, T, D) + shared.astype(jnp.float32)
    return x + y.astype(x.dtype), load


def rope_tables(pos, cfg: Mistral4Config):
    """``(cos, sin)``, each ``[B, T, d_rope / 2]``, of positions ``pos``."""
    ang = pos.astype(jnp.float32)[..., None] * jnp.asarray(yarn_inv_freq(cfg))
    return jnp.cos(ang), jnp.sin(ang)


def paged_model(cfg: Mistral4Config) -> PagedModel:
    dtype = jnp.dtype(cfg.dtype)

    def layers(params, slots, rows, pages, offs, pos, live, tp_axis):
        del slots  # nothing is kept a slot
        if tp_axis is not None:
            raise ValueError("mistral4 offers no tensor-parallel block")
        cos, sin = rope_tables(pos, cfg)
        stacks = params["experts"]
        n_live = jnp.sum(live, dtype=jnp.int32)

        def run_layer(p, li, x, planes):
            x, planes = mla_attention(
                p, x, planes, li, rows, pages, offs, pos, cos, sin, cfg
            )
            x, load = moe_ffn(p, x, live, stacks, li, cfg)
            return x, planes, jnp.append(load, n_live)

        return run_layer

    def unembed(params, x):
        h = rms_norm(x, params["ln_f"], cfg.rms_norm_eps)
        return jnp.dot(h, params["unembed"],
                       preferred_element_type=jnp.float32)

    return PagedModel(
        planes={"ckv": (cfg.kv_lora_rank,), "kpe": (cfg.qk_rope_head_dim,)},
        n_layers=cfg.n_layers,
        dtype=cfg.dtype,
        embed=lambda params, tokens: params["embed"].astype(dtype)[tokens],
        unembed=unembed,
        layers=layers,
        pass_stats=lambda aux: moe_pass_stats(aux, cfg),
    )
