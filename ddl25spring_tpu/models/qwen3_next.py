"""A hybrid decoder as ``model_type: qwen3_next`` configures it, served
through the page pool: gated delta-rule (linear attention) layers that keep
a FIXED recurrent state a sequence, gated grouped-query softmax attention on
every ``full_attention_interval``-th layer, and in every layer a softmax
top-k router over routed experts that drops nothing plus one gated shared
expert.  Every width is data (:class:`Qwen3NextConfig`).

**What the pool holds** (:mod:`ddl25spring_tpu.serve.paged_model`): the
full-attention layers alone leave keys and values in pages (planes ``k``,
``v`` of ``(kv heads, head_dim)``, held by ``L / interval`` layers); every
other layer keeps, a slot, the state ``S (value heads, dk, dv)`` in
``state_dtype`` (float32) and the last ``kernel - 1`` inputs of its causal
convolution, ``conv (kernel - 1, channels)`` in the served type.  The scan's
unit is one PERIOD: ``interval - 1`` linear layers, then one full layer.

**What one chip holds** of a deployment that shares each layer over several
chips by expert parallelism: both mixers, the shared expert and the router
at its full width ``num_experts``, the ``experts_held`` routed experts that
start at ``expert_offset``, and ``vocab_size`` rows of the embedding and of
the head; the chip adds the part its held experts give (weights normalised
over all ``num_experts_per_tok`` chosen).  Nothing stands in for the absent
chips (:mod:`.routed_experts`).

**The block** (``x [B, T, D]``; ``norm(h) = h rsqrt(mean h^2 + eps) (1 +
w)`` in float32, the zero-centred scale; no bias anywhere): ``x +=
mixer(norm(x))``; ``x += moe(norm(x))``.

- Full attention: ``q_proj`` gives a head ``[query | gate]`` of ``2
  head_dim``; ``k_proj``, ``v_proj`` give the KV heads; ``q`` and ``k`` pass
  a per-head ``norm`` (``1 + w``); rotary on the FIRST ``partial_rotary_factor
  head_dim`` dims of each head, halves rotated (not interleaved pairs);
  causal softmax in float32 at scale ``head_dim^-0.5``, a KV head serving
  ``heads / kv heads`` consecutive query heads; ``o_proj(attn *
  sigmoid(gate))``.
- Gated delta rule: ``in_proj_qkvz`` -> ``q, k [nk, dk]``, ``v, z [nv,
  dv]`` (columns laid out ``q|k|v|z``), ``in_proj_ba`` -> ``b, a [nv]``; a
  causal depthwise convolution (kernel ``linear_conv_kernel_dim``, no bias)
  over the channels of ``q|k|v``, then SiLU; ``beta = sigmoid(b)``, ``g =
  -exp(A_log) softplus(a + dt_bias)``; ``q``, ``k`` L2-normalised, ``q``
  scaled by ``dk^-0.5``, a key head repeated to ``nv / nk`` consecutive
  value heads; a head's state: ``S <- exp(g) S``; ``u = beta (v - S^T
  k)``; ``S <- S + k u^T``; ``o = S^T q``; output ``out_proj(rmsnorm(o)
  w_o silu(z))`` (this norm's scale is plain ``w_o``).  ``T = 1`` is ONE
  step of that recurrence, in place in the pool, by the kernel
  :func:`~ddl25spring_tpu.ops.gdn.gdn_step`; a prompt pass (``T = W``,
  from the empty state: every pass starts a sequence) runs the CHUNKED form
  (:func:`gdn_chunked`) and SEATS, at the row's slot, the state after the
  row's last live position: padded positions leave the state as it was
  (``beta = g = k = 0`` there) and the convolution's tail is the row's
  last ``kernel - 1`` LIVE inputs.
- Experts: router softmax in float32 over all ``num_experts``, the top
  ``k``, renormalised; the held routed SwiGLU experts (no capacity, no
  drop); plus ``sigmoid(h . w_sg) SwiGLU_shared(h)``.

Parameters (matrices in ``cfg.dtype``, resident: nothing is cast at use;
norm scales, ``A_log`` and ``dt_bias`` float32; whoever serves the model
brings them, as ``benchmark/families/qwen3next.py`` draws seeded ones), with
``U`` periods of ``I`` layers: ``embed [V, D]``; ``blocks``, every leaf
stacked ``[U, ...]`` and scanned (a leaf a layer of the period, so that the
scan's slice of it has ONE reader and is no copy): ``lin``, a list of the
period's ``I - 1`` linear layers (``ln1, in_qkvz, in_ba, conv_w [kernel,
channels], A_log, dt_bias, o_norm, out_proj``), ``full`` (``ln1, wq, wk, wv,
q_norm, k_norm, wo``), ``moe``, a list of its ``I`` expert layers (``ln2,
router, ws_gate, ws_up, ws_down, w_sg``); ``experts`` =
``w_gate, w_up [L, E, D, F]``, ``w_down [L, E, F, D]``, NOT scanned (the
kernel takes the whole stack and the layer's index); ``ln_f [D]``;
``unembed [D, V]``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ddl25spring_tpu.models.routed_experts import (
    pass_stats as moe_pass_stats,
    route,
    routed_experts,
    swiglu,
)
from ddl25spring_tpu.ops.gdn import gdn_step
from ddl25spring_tpu.serve import kv_pages
from ddl25spring_tpu.serve.paged_model import PagedModel

F32 = jnp.float32


@dataclass(frozen=True)
class Qwen3NextConfig:
    """The published keys of a ``qwen3_next`` ``config.json`` (same names),
    and this chip's share of the deployment."""

    vocab_size: int                 # rows held here (the slice)
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    linear_num_key_heads: int
    linear_num_value_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    num_experts: int                # the router's width: ALL experts
    num_experts_per_tok: int
    full_attention_interval: int = 4
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    experts_held: int | None = None  # None: all of them
    expert_offset: int = 0
    gdn_chunk: int = 64             # positions a chunk of a prompt pass
    l2_eps: float = 1e-6
    dtype: str = "bfloat16"
    state_dtype: str = "float32"    # the recurrent state's, in the pool

    routed_scaling_factor = 1.0     # the expert layer's protocol; no key

    def __post_init__(self):
        held = self.n_held
        if not (0 < held and self.expert_offset >= 0
                and self.expert_offset + held <= self.num_experts):
            raise ValueError(
                f"experts {self.expert_offset}..{self.expert_offset + held}"
                f" are not among the router's {self.num_experts}"
            )
        if (self.full_attention_interval < 2
                or self.num_hidden_layers % self.full_attention_interval):
            raise ValueError(
                f"{self.num_hidden_layers} layers are not whole periods of "
                f"{self.full_attention_interval} (linear layers, then one "
                "full-attention layer)"
            )
        if (self.num_attention_heads % self.num_key_value_heads
                or self.linear_num_value_heads % self.linear_num_key_heads):
            raise ValueError("query/value heads are whole groups of KV/key heads")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError("RoPE rotates halves: an even rotary_dim <= head_dim")

    @property
    def n_held(self) -> int:
        return self.num_experts if self.experts_held is None else self.experts_held

    @property
    def n_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def n_units(self) -> int:
        return self.num_hidden_layers // self.full_attention_interval

    @property
    def n_linear(self) -> int:
        """Linear layers a period."""
        return self.full_attention_interval - 1

    @property
    def ctx_size(self) -> int:
        return self.max_position_embeddings

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_channels(self) -> int:
        return 2 * self.key_dim + self.value_dim

    def paged_model(self) -> PagedModel:
        return paged_model(self)


def norm(x, w, eps: float):
    """RMSNorm in float32 with the zero-centred scale ``1 + w``."""
    xf = x.astype(F32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(F32))).astype(x.dtype)


def rope_tables(pos, cfg: Qwen3NextConfig):
    """``(cos, sin)``, each ``[B, T, rotary_dim / 2]``, of positions ``pos``."""
    d = cfg.rotary_dim
    inv = cfg.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = pos.astype(F32)[..., None] * jnp.asarray(inv.astype(np.float32))
    return jnp.cos(ang), jnp.sin(ang)


def _rope(x, cos, sin):
    """The first ``2 x cos.shape[-1]`` dims of each head of ``x [B, T, H,
    hd]`` turned by ``cos/sin [B, T, d/2]``, halves rotated: ``(x1, x2) ->
    (x1 c - x2 s, x2 c + x1 s)``; the rest passes."""
    half = cos.shape[-1]
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    x1 = x[..., :half].astype(F32)
    x2 = x[..., half:2 * half].astype(F32)
    turned = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return jnp.concatenate([turned.astype(x.dtype), x[..., 2 * half:]], axis=-1)


# ---------------------------------------------------------- full attention


def gated_attention(p, x, cache, layer, rows, pages, offs, pos, cos, sin,
                    cfg: Qwen3NextConfig):
    """``x + o_proj(attention(norm(x)) * sigmoid(gate))`` through the planes
    ``k``/``v`` at plane layer ``layer``, for any ``T``.  Scopes ``attn`` /
    ``page_write`` / ``page_gather``."""
    B, T = x.shape[:2]
    H, KV, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    eps = cfg.rms_norm_eps
    dtype = x.dtype
    with jax.named_scope("attn"):
        h = norm(x, p["ln1"], eps)
        qg = (h @ p["wq"]).reshape(B, T, H, 2 * hd)
        q, gate = qg[..., :hd], qg[..., hd:]
        k = (h @ p["wk"]).reshape(B, T, KV, hd)
        v = (h @ p["wv"]).reshape(B, T, KV, hd)
        q = _rope(norm(q, p["q_norm"], eps), cos, sin)
        k = _rope(norm(k, p["k_norm"], eps), cos, sin)
    pages_kv = {"k": cache["k"], "v": cache["v"]}
    with jax.named_scope("page_write"):
        pages_kv = kv_pages.write_planes(
            pages_kv, layer, pages, offs, {"k": k, "v": v}
        )
    with jax.named_scope("page_gather"):
        view = kv_pages.gather_planes(pages_kv, layer, rows)
        ks, vs = view["k"], view["v"]  # [B, M, KV, hd]
        M = ks.shape[1]
    with jax.named_scope("attn"):
        qh = q.reshape(B, T, KV, H // KV, hd)
        s = jnp.einsum("btkgd,bmkd->bkgtm", qh, ks,
                       preferred_element_type=F32) * hd ** -0.5
        seen = jnp.arange(M)[None, None, :] <= pos[:, :, None]  # [B, T, M]
        s = jnp.where(seen[:, None, None, :, :], s, -1e30)
        probs = jax.nn.softmax(s, axis=-1).astype(dtype)
        o = jnp.einsum("bkgtm,bmkd->btkgd", probs, vs).reshape(B, T, H * hd)
        o = o * jax.nn.sigmoid(gate.reshape(B, T, H * hd).astype(F32)).astype(dtype)
        return x + o @ p["wo"], {**cache, **pages_kv}


# ------------------------------------------------------ gated delta rule


def gdn_chunked(q, k, v, g, beta, chunk: int):
    """The gated delta rule over ``T`` positions from the EMPTY state, in
    chunks: ``q, k [B, T, H, dk]`` (normalised, scaled, repeated to the
    value heads), ``v [B, T, H, dv]``, ``g, beta [B, T, H]``, all float32;
    ``T`` a multiple of ``chunk``.  Returns ``(o [B, T, H, dv], S [B, H, dk,
    dv])``, the state after position ``T - 1``.

    Within a chunk (``gc`` the running sum of ``g``): with ``A[i, j] =
    beta_i (k_i . k_j) exp(gc_i - gc_j)`` for ``j < i`` (strictly lower
    triangular, so ``A^chunk = 0``), the rank-one updates ``u`` solve ``(I
    + A) U = beta v - (beta k exp(gc)) S0``; the inverse is the finite
    product ``(I - A)(I + A^2)(I + A^4)...`` (six factors at 64), all
    matrix products.  The state is carried chunk to chunk by a scan of
    ``T / chunk`` steps: never a token-serial walk."""
    B, T, H, dk = q.shape
    dv, C, n = v.shape[-1], chunk, T // chunk
    hi = lax.Precision.HIGHEST

    def mm(spec, a, b):
        return jnp.einsum(spec, a, b, precision=hi)

    def split(a):  # [B, T, H, d] -> [B, H, n, C, d]
        return a.reshape(B, n, C, H, -1).transpose(0, 3, 1, 2, 4)

    q, k, v = split(q), split(k), split(v)
    g, beta = split(g)[..., 0], split(beta)[..., 0]  # [B, H, n, C]
    gc = jnp.cumsum(g, axis=-1)
    lower = jnp.tril(jnp.ones((C, C), bool))
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    diff = gc[..., :, None] - gc[..., None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    kb, vb = k * beta[..., None], v * beta[..., None]
    A = jnp.where(strict, mm("bhnik,bhnjk->bhnij", kb, k) * decay, 0.0)
    eye = jnp.eye(C, dtype=F32)
    inv, power = eye - A, A
    for _ in range(max(0, math.ceil(math.log2(C)) - 1)):
        power = mm("bhnij,bhnjk->bhnik", power, power)
        inv = mm("bhnij,bhnjk->bhnik", inv, eye + power)
    w = mm("bhnij,bhnjv->bhniv", inv, vb)
    kc = mm("bhnij,bhnjk->bhnik", inv, kb * jnp.exp(gc)[..., None])
    local = jnp.where(lower, mm("bhnik,bhnjk->bhnij", q, k) * decay, 0.0)
    q_in = q * jnp.exp(gc)[..., None]
    k_out = k * jnp.exp(gc[..., -1:] - gc)[..., None]
    last = jnp.exp(gc[..., -1])  # [B, H, n]

    def step(S, c):
        w_c, kc_c, local_c, q_c, k_c, last_c = c
        u = w_c - mm("bhik,bhkv->bhiv", kc_c, S)
        o = mm("bhik,bhkv->bhiv", q_c, S) + mm("bhij,bhjv->bhiv", local_c, u)
        S = S * last_c[..., None, None] + mm("bhik,bhiv->bhkv", k_c, u)
        return S, o

    def chunks(a):  # the chunk axis first, for the scan
        return jnp.moveaxis(a, 2, 0)

    S, o = lax.scan(
        step, jnp.zeros((B, H, dk, dv), F32),
        tuple(chunks(a) for a in (w, kc, local, q_in, k_out, last)),
    )
    o = jnp.moveaxis(o, 0, 2)  # [B, H, n, C, dv]
    return o.transpose(0, 2, 3, 1, 4).reshape(B, T, H, dv), S


def gdn_mixer(p, x, cache, layer, slots, live, cfg: Qwen3NextConfig):
    """``x + gated delta rule of norm(x)`` against the slot state ``S`` /
    ``conv`` at state layer ``layer``, for any ``T``: see the module's
    text.  Scopes ``gdn_proj`` / ``gdn_conv`` / ``gdn_step`` or
    ``gdn_chunk`` / ``gdn_out``."""
    B, T, D = x.shape
    nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    Kd, K, C = cfg.key_dim, cfg.linear_conv_kernel_dim, cfg.conv_channels
    dtype = x.dtype
    S_all, conv_all = cache["S"], cache["conv"]
    # a row that is seated: its slot; a padding row falls off the end
    seat = jnp.where(slots >= 0, slots, S_all.shape[0])

    with jax.named_scope("gdn_proj"):
        h = norm(x, p["ln1"], cfg.rms_norm_eps)
        qkvz = h @ p["in_qkvz"]
        mixed, z = qkvz[..., :C], qkvz[..., C:]
        ba = (h @ p["in_ba"]).astype(F32)
        beta = jax.nn.sigmoid(ba[..., :nv])
        g = -jnp.exp(p["A_log"].astype(F32)) * jax.nn.softplus(
            ba[..., nv:] + p["dt_bias"].astype(F32))

    with jax.named_scope("gdn_conv"):
        taps = p["conv_w"].astype(F32)  # [K, C]; the last tap is the input's
        if T == 1:
            window = jnp.concatenate([conv_all[:, layer], mixed], axis=1)
            tail = jnp.where(live[:, :, None], window[:, 1:], window[:, :-1])
            conv_all = conv_all.at[:, layer].set(tail)
            y = jnp.einsum("bkc,kc->bc", window.astype(F32), taps)[:, None]
        else:
            padded = jnp.pad(mixed, ((0, 0), (K - 1, 0), (0, 0)))
            y = sum(padded[:, j:j + T].astype(F32) * taps[j] for j in range(K))
            # the row's last K - 1 LIVE inputs (zeros before its first)
            n_row = jnp.sum(live, axis=1, dtype=jnp.int32)
            at = n_row[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None, :]
            tail = jnp.take_along_axis(padded, at[:, :, None], axis=1)
            conv_all = conv_all.at[seat, layer].set(tail, mode="drop")
        y = jax.nn.silu(y)  # [B, T, C] float32

    def heads(a, n, d):
        return a.reshape(B, T, n, d)

    def l2(a):
        return a * lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + cfg.l2_eps)

    q = jnp.repeat(l2(heads(y[..., :Kd], nk, dk)) * dk ** -0.5, nv // nk, axis=2)
    k = jnp.repeat(l2(heads(y[..., Kd:2 * Kd], nk, dk)), nv // nk, axis=2)
    v = heads(y[..., 2 * Kd:], nv, dv)
    if T == 1:
        with jax.named_scope("gdn_step"):
            o, S_all = gdn_step(S_all, layer, q[:, 0], k[:, 0], v[:, 0],
                                g[:, 0], beta[:, 0], live[:, 0])
            o = o[:, None]
    else:
        with jax.named_scope("gdn_chunk"):
            # a padded position leaves the state as it was
            m = live[:, :, None].astype(F32)
            pad = -T % cfg.gdn_chunk
            args = [jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                    for a in (q, k * m[..., None], v, g * m, beta * m)]
            o, S_end = gdn_chunked(*args, cfg.gdn_chunk)
            o = o[:, :T]
            S_all = S_all.at[seat, layer].set(
                S_end.astype(S_all.dtype), mode="drop")

    with jax.named_scope("gdn_out"):
        o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + cfg.rms_norm_eps)
        o = o * p["o_norm"].astype(F32) * jax.nn.silu(
            heads(z, nv, dv).astype(F32))
        out = o.reshape(B, T, nv * dv).astype(dtype) @ p["out_proj"]
    return x + out, {**cache, "S": S_all, "conv": conv_all}


# ------------------------------------------------------------- the experts


def moe_ffn(p, x, live, stacks, layer, cfg: Qwen3NextConfig):
    """``x + held routed experts + gated shared expert`` of ``norm(x)``;
    scopes ``router`` / ``experts`` / ``shared_expert``."""
    B, T, D = x.shape
    h2 = norm(x, p["ln2"], cfg.rms_norm_eps)
    flat = h2.reshape(B * T, D)
    with jax.named_scope("router"):
        experts, weights = route(flat, p["router"], cfg)
    with jax.named_scope("experts"):
        routed, load = routed_experts(
            flat, experts, weights, live.reshape(-1), stacks, layer, cfg
        )
    with jax.named_scope("shared_expert"):
        shared = swiglu(h2, p["ws_gate"], p["ws_up"], p["ws_down"])
        opened = jax.nn.sigmoid(jnp.einsum(
            "btd,d->bt", h2, p["w_sg"], preferred_element_type=F32))
        shared = shared.astype(F32) * opened[..., None]
    y = routed.reshape(B, T, D) + shared
    return x + y.astype(x.dtype), load


# ---------------------------------------------------------------- the seam


def prompt_pass_counts(lens, rows: int, width: int, chunk: int) -> dict[str, int]:
    """Of a prompt pass of ``rows x width`` positions whose live rows are
    ``lens`` long: the chunks every linear layer scans, and those of them
    that hold a live position."""
    return {
        "gdn.chunks_live": int(sum(-(-int(n) // chunk) for n in lens)),
        "gdn.chunks_scanned": rows * -(-width // chunk),
    }


def paged_model(cfg: Qwen3NextConfig) -> PagedModel:
    dtype = jnp.dtype(cfg.dtype)
    I, n_lin = cfg.full_attention_interval, cfg.n_linear

    def layers(params, slots, rows, pages, offs, pos, live, tp_axis):
        if tp_axis is not None:
            raise ValueError("qwen3_next offers no tensor-parallel block")
        cos, sin = rope_tables(pos, cfg)
        stacks = params["experts"]
        n_live = jnp.sum(live, dtype=jnp.int32)

        def run_period(p, ui, x, cache):
            counts = []
            for j in range(I):
                if j < n_lin:
                    x, cache = gdn_mixer(
                        p["lin"][j], x, cache, ui * n_lin + j, slots, live,
                        cfg
                    )
                else:
                    x, cache = gated_attention(
                        p["full"], x, cache, ui, rows, pages, offs, pos,
                        cos, sin, cfg
                    )
                x, load = moe_ffn(p["moe"][j], x, live, stacks, ui * I + j, cfg)
                counts.append(jnp.append(load, n_live))
            return x, cache, jnp.concatenate(counts)

        return run_period

    def unembed(params, x):
        h = norm(x, params["ln_f"], cfg.rms_norm_eps)
        return jnp.dot(h, params["unembed"], preferred_element_type=F32)

    kv = (cfg.num_key_value_heads, cfg.head_dim)
    return PagedModel(
        planes={"k": kv, "v": kv},
        plane_layers={"k": cfg.n_units, "v": cfg.n_units},
        slot_state={
            "S": ((cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                   cfg.linear_value_head_dim), cfg.state_dtype),
            "conv": ((cfg.linear_conv_kernel_dim - 1, cfg.conv_channels),
                     cfg.dtype),
        },
        state_layers=cfg.n_units * n_lin,
        scan_units=cfg.n_units,
        n_layers=cfg.n_layers,
        dtype=cfg.dtype,
        embed=lambda params, tokens: params["embed"].astype(dtype)[tokens],
        unembed=unembed,
        layers=layers,
        pass_stats=lambda aux: moe_pass_stats(aux, cfg),
        prompt_pass_counts=lambda lens, rows, width: prompt_pass_counts(
            lens, rows, width, cfg.gdn_chunk),
    )
