"""LLaMA-style decoder, stage-splittable for pipeline parallelism.

The reference trains a LLaMA from the external ``simplellm`` package, split
into ``LLamaFirstStage`` (``.embed``), ``LLamaStage``, ``LLamaLastStage``
(logits) — one torch module per pipeline rank
(``lab/s01_b1_microbatches.py:30-61``) with workload constants dmodel=288,
6 heads, 6 layers, ctx 256 (``:21-24``).  This build keeps the whole model in
ONE parameter pytree with the transformer blocks *stacked* on a leading layer
axis, so pipeline partitioning is a reshape ``[L, ...] -> [S, L/S, ...]`` and
a ``PartitionSpec('stage', ...)`` — no per-stage module classes.

TPU-first choices:
- functional core (pure functions over explicit pytrees): composes freely
  with ``shard_map`` / ``scan`` / ``grad`` for the pipeline schedule;
- blocks applied via ``lax.scan`` over the stacked layer axis (one compiled
  block body regardless of depth);
- RMSNorm / RoPE / SwiGLU per LLaMA convention; attention einsums run in
  ``cfg.dtype`` (bfloat16 on TPU: MXU-native) with fp32 softmax and fp32
  master params;
- the parts of the model carry ``jax.named_scope`` names — ``embed``,
  ``attn``, ``mlp``, and ``blocks`` for the layer scan's own plumbing
  around them; the head is named by its callers, with what they make of
  the logits (``head_loss`` in ``parallel/pipeline.py``, ``head`` in the
  serving programs) — that every compiled program keeps in
  its operations' ``op_name`` (JAX wraps them as ``jvp(attn)`` /
  ``transpose(jvp(attn))`` in a backward pass), so that a profiler trace
  can be summed by part (``benchmark/tools/trace_scopes.py``).  Names are
  metadata: they change no operation.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ddl25spring_tpu.utils.config import LlamaConfig

Params = dict[str, Any]


# ---------------------------------------------------------------- init


def _dense(key, shape, scale=0.02):
    return (scale * jax.random.normal(key, shape)).astype(jnp.float32)


# the non-FFN block params (the schema init_block_params lays down);
# sharding-spec builders key off this so they cannot drift from the model
ATTN_BLOCK_KEYS = ("ln1", "wq", "wk", "wv", "wo", "ln2")


def init_block_params(key: jax.Array, cfg: LlamaConfig) -> Params:
    d, f = cfg.dmodel, cfg.ffn_dim
    ks = jax.random.split(key, 7)
    p = {
        "ln1": jnp.ones((d,), jnp.float32),
        "wq": _dense(ks[0], (d, d)),
        "wk": _dense(ks[1], (d, d)),
        "wv": _dense(ks[2], (d, d)),
        "wo": _dense(ks[3], (d, d)),
        "ln2": jnp.ones((d,), jnp.float32),
    }
    if cfg.n_experts > 0:
        # switch-MoE FFN (Switch Transformer, every block): router +
        # stacked bias-free SwiGLU experts, shared init with parallel/ep.py
        from ddl25spring_tpu.parallel.ep import init_moe_params

        p["moe"] = init_moe_params(ks[4], d, f, cfg.n_experts)
    else:
        p["w_gate"] = _dense(ks[4], (d, f))
        p["w_up"] = _dense(ks[5], (d, f))
        p["w_down"] = _dense(ks[6], (f, d))
    return p


def init_llama_params(key: jax.Array, cfg: LlamaConfig) -> Params:
    """Full model: ``embed [V,D]``, stacked ``blocks [L,...]``, final-norm
    scale, ``unembed [D,V]``."""
    k_embed, k_blocks, k_out = jax.random.split(key, 3)
    block_keys = jax.random.split(k_blocks, cfg.n_layers)
    blocks = jax.vmap(lambda k: init_block_params(k, cfg))(block_keys)
    return {
        "embed": _dense(k_embed, (cfg.vocab_size, cfg.dmodel)),
        "blocks": blocks,
        "ln_f": jnp.ones((cfg.dmodel,), jnp.float32),
        "unembed": _dense(k_out, (cfg.dmodel, cfg.vocab_size)),
    }


# ---------------------------------------------------------------- forward


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    rms = jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return ((x32 / rms) * scale).astype(x.dtype)


def rope_angles(
    seq_len: int,
    head_dim: int,
    base: float = 10_000.0,
    pos: jax.Array | None = None,
):
    """``pos`` overrides ``arange(seq_len)`` — sequence-parallel shards pass
    their GLOBAL positions so rotary phases match the unsharded model."""
    if pos is None:
        pos = jnp.arange(seq_len, dtype=jnp.float32)
    inv = base ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]  # [L, hd/2]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    # x: [B, L, H, hd]; rotate pairs (even, odd)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    c = cos[None, :, None, :]
    s = sin[None, :, None, :]
    out = jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def causal_attention(q, k, v, dtype):
    """Dense causal attention (fp32 softmax): the single-device / TP path."""
    hd = q.shape[-1]
    L, Lk = q.shape[1], k.shape[1]
    scores = jnp.einsum("blhd,bmhd->bhlm", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    mask = jnp.tril(jnp.ones((L, Lk), bool))
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    return jnp.einsum("bhlm,bmhd->blhd", probs, v)


def block_forward(
    p: Params,
    x: jax.Array,
    cfg: LlamaConfig,
    *,
    tp_axis: str | None = None,
    pos: jax.Array | None = None,
    attn_fn=None,
    moe_fn=None,
) -> tuple[jax.Array, jax.Array]:
    """One pre-norm transformer block: RMSNorm -> causal RoPE attention ->
    residual -> RMSNorm -> FFN -> residual.  Returns ``(x, aux)`` where
    ``aux`` is the switch-MoE load-balancing loss when ``cfg.n_experts > 0``
    (SwiGLU dense FFN and ``aux = 0.0`` otherwise).  ``moe_fn`` overrides
    the single-device ``ep.moe_ffn`` — inject
    ``ep.make_ep_moe_fn(mesh, capacity_factor=cfg.capacity_factor)`` for
    expert-parallel FFNs, mirroring the ``attn_fn`` hook (pass the config's
    capacity explicitly: the EP builder cannot see ``cfg``).

    Parallel hooks (both off by default = the serial block):

    - ``tp_axis``: Megatron-style tensor parallelism inside ``shard_map`` —
      ``p`` holds this device's column slice of wq/wk/wv/w_gate/w_up and row
      slice of wo/w_down; the two row-sharded matmuls are followed by a
      ``psum`` over the axis.  Local head count is derived from the param
      slice, so the same code runs sharded and unsharded.
    - ``pos`` / ``attn_fn``: sequence parallelism — global RoPE positions for
      this shard's tokens and a ring-attention implementation.
    """
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("attn"):
        x = _attn_half(p, x, cfg, dtype, tp_axis, pos, attn_fn)
    with jax.named_scope("mlp"):
        return _ffn_half(p, x, cfg, dtype, tp_axis, moe_fn)


def _attn_half(p, x, cfg, dtype, tp_axis, pos, attn_fn):
    """``x + attention(rms_norm(x))``: the first half of a block."""
    B, L, _ = x.shape
    hd = cfg.head_dim

    h = rms_norm(x, p["ln1"])
    q = (h @ p["wq"].astype(dtype)).reshape(B, L, -1, hd)
    k = (h @ p["wk"].astype(dtype)).reshape(B, L, -1, hd)
    v = (h @ p["wv"].astype(dtype)).reshape(B, L, -1, hd)
    cos, sin = rope_angles(L, hd, pos=pos)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    if attn_fn is None:
        if cfg.use_flash:

            def attn_fn(q, k, v, dtype):
                from ddl25spring_tpu.ops.flash_attention import (
                    flash_attention,
                    off_tpu,
                )

                # Off-TPU the kernel runs in Pallas interpret mode, which
                # cannot execute inside shard_map under JAX 0.9's VMA
                # checking (interpret lowering mixes varying data with
                # invariant block indices).  Detect that context — varying
                # mesh axes on the operand + non-TPU backend — and use the
                # dense path there, saying so; on TPU it is the kernel.
                in_shard_map = bool(jax.typeof(q).vma)
                if in_shard_map and off_tpu(
                    "use_flash inside shard_map runs DENSE attention"
                ):
                    return causal_attention(q, k, v, dtype)
                return flash_attention(q, k, v)
        else:
            attn_fn = causal_attention
    attn = attn_fn(q, k, v, dtype)
    attn = attn.reshape(B, L, -1)
    attn_out = attn @ p["wo"].astype(dtype)
    if tp_axis is not None:
        attn_out = lax.psum(attn_out, tp_axis)
    return x + attn_out


def _ffn_half(p, x, cfg, dtype, tp_axis, moe_fn):
    """``(x + ffn(rms_norm(x)), aux)``: the second half of a block."""
    B, L, D = x.shape
    h = rms_norm(x, p["ln2"])
    if cfg.n_experts > 0:
        if tp_axis is not None and moe_fn is None:
            # under TP the default (replicated) moe_ffn would be scaled by
            # the axis size by the row-parallel psum below — require the
            # expert-sharded partial-output variant instead
            raise NotImplementedError(
                "switch-MoE under tensor parallelism needs the expert-"
                "sharded moe_fn from parallel.tp.make_tp_moe_fn (whose "
                "partial output the row-parallel psum completes)"
            )
        if moe_fn is None:
            from ddl25spring_tpu.parallel.ep import moe_ffn

            def moe_fn(mp, flat):
                return moe_ffn(
                    mp, flat, capacity_factor=cfg.capacity_factor,
                    top_k=cfg.moe_top_k,
                )

        # tokens flattened [B*L, D]: ONE dispatch group per call, so under
        # capacity overflow a token's drop decision depends on the other
        # rows in the batch (inherent to switch-style bucketed dispatch;
        # examples are independent whenever nothing overflows)
        y, aux = moe_fn(p["moe"], h.reshape(B * L, D))
        ffn_out = y.reshape(B, L, D).astype(dtype)
    else:
        gate = jax.nn.silu(h @ p["w_gate"].astype(dtype))
        up = h @ p["w_up"].astype(dtype)
        ffn_out = (gate * up) @ p["w_down"].astype(dtype)
        aux = jnp.float32(0.0)
    if tp_axis is not None:
        ffn_out = lax.psum(ffn_out, tp_axis)
    x = x + ffn_out
    return x, aux


def apply_blocks(
    stacked: Params,
    x: jax.Array,
    cfg: LlamaConfig,
    with_aux: bool = False,
    **block_kw,
):
    """Apply a stack of blocks (leading layer axis) via ``lax.scan`` — the
    compiler-friendly loop (one block body compiled once).

    ``with_aux=True`` additionally returns the summed MoE load-balancing
    aux loss over layers (0.0 for dense-FFN configs) — opt-in so the
    pipeline/TP/SP callers keep their single-output contract."""

    def body(h, block_p):
        h, aux = block_forward(block_p, h, cfg, **block_kw)
        return h, aux

    # "blocks" names what the layer scan itself adds around attn / mlp:
    # slicing the stacked weights, stacking and reading back residuals
    with jax.named_scope("blocks"):
        out, aux = lax.scan(body, x, stacked)
    if with_aux:
        return out, aux.sum()
    return out


def embed(params: Params, tokens: jax.Array, cfg: LlamaConfig) -> jax.Array:
    """Token embedding (parity: ``LLamaFirstStage.embed``,
    ``lab/s01_b1_microbatches.py:84``)."""
    with jax.named_scope("embed"):
        return params["embed"].astype(jnp.dtype(cfg.dtype))[tokens]


def unembed(params: Params, x: jax.Array, cfg: LlamaConfig) -> jax.Array:
    """Final norm + output projection to logits (parity: ``LLamaLastStage``
    producing logits, ``lab/s01_b1_microbatches.py:52-59``)."""
    h = rms_norm(x, params["ln_f"])
    return (h @ params["unembed"].astype(h.dtype)).astype(jnp.float32)


def llama_forward(params: Params, tokens: jax.Array, cfg: LlamaConfig) -> jax.Array:
    """Full unpartitioned forward: the serial side of the pipeline
    equivalence oracle (SURVEY §4).

    Dense-FFN configs only: a switch-MoE config trained through this entry
    would silently drop the router load-balancing aux loss, so it raises —
    use :func:`llama_forward_with_aux` (mirroring the guards on the
    tp/sp/pipeline loss builders)."""
    if cfg.n_experts > 0:
        raise NotImplementedError(
            "cfg.n_experts > 0: use llama_forward_with_aux so the MoE "
            "load-balancing aux loss reaches the objective"
        )
    x = embed(params, tokens, cfg)
    x = apply_blocks(params["blocks"], x, cfg)
    return unembed(params, x, cfg)


def llama_forward_with_aux(
    params: Params, tokens: jax.Array, cfg: LlamaConfig
) -> tuple[jax.Array, jax.Array]:
    """Forward returning ``(logits, moe_aux)``.  Training a switch-MoE
    config (``cfg.n_experts > 0``) should minimize ``causal_lm_loss(logits,
    tokens) + cfg.moe_aux_weight * moe_aux`` so the router learns to
    balance expert load (Switch Transformer recipe); ``moe_aux`` is 0.0
    for dense-FFN configs."""
    x = embed(params, tokens, cfg)
    x, aux = apply_blocks(params["blocks"], x, cfg, with_aux=True)
    return unembed(params, x, cfg), aux


# ---------------------------------------------------------------- stage split


def split_blocks_for_stages(params: Params, num_stages: int) -> Params:
    """Reshape stacked blocks ``[L, ...] -> [S, L/S, ...]``.  Sharding dim 0
    over the mesh ``stage`` axis gives each stage its contiguous layer slice —
    the mesh analogue of ``n_layers = 6 // world_size`` per rank
    (``lab/s01_b1_microbatches.py:23``)."""
    L = jax.tree.leaves(params["blocks"])[0].shape[0]
    if L % num_stages:
        raise ValueError(f"{L} layers not divisible by {num_stages} stages")
    per = L // num_stages
    out = dict(params)
    out["blocks"] = jax.tree.map(
        lambda x: x.reshape((num_stages, per) + x.shape[1:]), params["blocks"]
    )
    return out


def split_blocks_interleaved(
    params: Params, num_stages: int, num_chunks: int
) -> Params:
    """Reshape stacked blocks ``[L, ...] -> [S, V, L/(S·V), ...]`` for the
    interleaved virtual-stage pipeline: device ``s`` holds the ``V`` chunks
    ``{v·S + s}`` (Megatron-LM interleaving), so ``blocks[s][v]`` is global
    chunk ``v·S + s`` = layers ``[(v·S+s)·Lc, (v·S+s+1)·Lc)``."""
    L = jax.tree.leaves(params["blocks"])[0].shape[0]
    S, V = num_stages, num_chunks
    if L % (S * V):
        raise ValueError(f"{L} layers not divisible by S*V = {S}*{V}")
    per = L // (S * V)
    out = dict(params)
    out["blocks"] = jax.tree.map(
        # [L] -> [V, S, Lc] (chunk-major: g = v*S + s) -> [S, V, Lc]
        lambda x: x.reshape((V, S, per) + x.shape[1:]).swapaxes(0, 1),
        params["blocks"],
    )
    return out


def merge_blocks_interleaved(params: Params) -> Params:
    """Inverse of :func:`split_blocks_interleaved`."""
    out = dict(params)
    out["blocks"] = jax.tree.map(
        lambda x: x.swapaxes(0, 1).reshape((-1,) + x.shape[3:]),
        params["blocks"],
    )
    return out


def merge_blocks_from_stages(params: Params) -> Params:
    """Inverse of :func:`split_blocks_for_stages`."""
    out = dict(params)
    out["blocks"] = jax.tree.map(
        lambda x: x.reshape((-1,) + x.shape[2:]), params["blocks"]
    )
    return out
