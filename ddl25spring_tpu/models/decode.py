"""Autoregressive generation with a KV cache for the LLaMA stack.

The reference never samples from its LLaMA (training-loss prints only,
``lab/s01_b1_microbatches.py:158``); this module completes the model
family with the standard inference path, TPU-first:

- the KV cache is ONE stacked array pair ``[n_layers, B, max_len, H, hd]``
  updated in place with ``lax.dynamic_update_slice`` (static shapes — no
  growing arrays under jit);
- the decode loop is a ``lax.scan`` over token positions (one compiled
  step body regardless of length), each step a ``[B, 1]``-token pass over
  all layers via an inner scan;
- prefill reuses the same cached step scanned over the prompt (weights
  are the bandwidth bound at B*1 shapes; a fused prompt pass would only
  help long prompts);
- greedy (``temperature=0``) or temperature sampling with explicit PRNG
  threading.

Equivalence oracle (``tests/test_decode.py``): greedy generation must
reproduce ``argmax(llama_forward(prompt + generated_so_far)[:, -1])`` at
every position — the cached incremental pass IS the full forward.  Scope
of "exact": fp32 dense-attention configs (the attention einsum follows
the training path's dtype policy, so bf16 rounds each path's
intermediates in a different order; near-tied logits may then argmax
differently — inherent to any cached-vs-full comparison in low
precision).  MoE decode always runs at ample capacity (see
``_block_decode``), so MoE equivalence holds whenever the full forward
dropped nothing.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.lax import pcast

from ddl25spring_tpu.models import llama
from ddl25spring_tpu.utils.config import LlamaConfig

Params = dict[str, Any]


def resolve_heads(cfg: LlamaConfig, num_heads: int | None) -> int:
    """The per-shard head count a KV cache is shaped with: ``num_heads``
    overrides the config for TP decode (each shard caches only its
    local ``H/t`` heads).

    An explicit non-positive override raises instead of silently
    falling back to ``cfg.num_heads`` — the ``num_heads or
    cfg.num_heads`` idiom treated ``num_heads=0`` as *unset* and would
    mis-shape the cache.  Shared by both cache layouts (the dense slab
    below and :mod:`ddl25spring_tpu.serve.kv_pages`' page pool), so
    they validate identically."""
    if num_heads is None:
        return cfg.num_heads
    if num_heads <= 0:
        raise ValueError(
            f"num_heads={num_heads}: a head-count override must be a "
            "positive per-shard count (pass None to use cfg.num_heads)"
        )
    return num_heads


def init_kv_cache(
    cfg: LlamaConfig, batch: int, max_len: int, num_heads: int | None = None
):
    """``(k, v)`` stacked over layers: ``[L, B, max_len, H, hd]``.
    ``num_heads`` overrides the config for TP decode, where each shard
    caches only its local ``H/t`` heads; explicit non-positive
    overrides raise (:func:`resolve_heads`)."""
    shape = (
        cfg.n_layers, batch, max_len, resolve_heads(cfg, num_heads),
        cfg.head_dim,
    )
    dtype = jnp.dtype(cfg.dtype)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def _block_decode(p: Params, x, k_cache, v_cache, pos, cos, sin,
                  cfg: LlamaConfig, tp_axis: str | None = None):
    """One block on a single-token slice ``x [B, 1, D]`` against the
    layer's cache ``[B, max_len, H, hd]``; returns updated caches.

    ``tp_axis``: Megatron TP inside ``shard_map`` — ``p`` holds this
    shard's column slice of wq/wk/wv (local heads fall out of the
    reshape) and row slice of wo/w_down; the two row-parallel matmuls
    are completed by a ``psum``, exactly the training-path layout
    (``llama.block_forward``), so TP decode reads the SAME sharded
    weights training produced.  The KV cache is head-sharded."""
    dtype = jnp.dtype(cfg.dtype)
    B = x.shape[0]
    hd = cfg.head_dim
    max_len = k_cache.shape[1]

    h = llama.rms_norm(x, p["ln1"])
    q = (h @ p["wq"].astype(dtype)).reshape(B, 1, -1, hd)
    k = (h @ p["wk"].astype(dtype)).reshape(B, 1, -1, hd)
    v = (h @ p["wv"].astype(dtype)).reshape(B, 1, -1, hd)
    q = llama.apply_rope(q, cos, sin)
    k = llama.apply_rope(k, cos, sin)

    k_cache = lax.dynamic_update_slice(k_cache, k, (0, pos, 0, 0))
    v_cache = lax.dynamic_update_slice(v_cache, v, (0, pos, 0, 0))

    # attention of the one query against positions <= pos; same dtype
    # policy as the training path (llama.causal_attention): einsum in
    # cfg.dtype, fp32 softmax — so fp32 configs match the full forward
    # bitwise
    s = jnp.einsum("bqhd,bmhd->bhqm", q, k_cache).astype(jnp.float32)
    s = s / jnp.sqrt(jnp.float32(hd))
    live = jnp.arange(max_len) <= pos
    s = jnp.where(live[None, None, None, :], s, -1e30)
    probs = jax.nn.softmax(s, axis=-1).astype(dtype)
    attn = jnp.einsum("bhqm,bmhd->bqhd", probs, v_cache)
    attn_out = attn.reshape(B, 1, -1) @ p["wo"].astype(dtype)
    if tp_axis is not None:
        attn_out = lax.psum(attn_out, tp_axis)
    x = x + attn_out

    h = llama.rms_norm(x, p["ln2"])
    if cfg.n_experts > 0:
        # ample decode-time capacity (C >= B*top_k): dropping tokens is
        # a TRAINING regularization artifact; at inference a drop would
        # silently zero a token's FFN, so decode never drops — and the
        # teacher-forcing oracle holds whenever the full forward didn't
        # drop either
        E = p["moe"]["router"].shape[1]
        if tp_axis is not None:
            from ddl25spring_tpu.parallel.tp import make_tp_moe_fn

            # global routing on every shard, local E/t expert slice,
            # partial combine completed by the psum below
            y, _ = make_tp_moe_fn(
                tp_axis, capacity_factor=float(E), top_k=cfg.moe_top_k
            )(p["moe"], h.reshape(B, -1))
        else:
            from ddl25spring_tpu.parallel.ep import moe_ffn

            y, _ = moe_ffn(
                p["moe"], h.reshape(B, -1),
                capacity_factor=float(E),
                top_k=cfg.moe_top_k,
            )
        ffn_out = y.reshape(B, 1, -1).astype(dtype)
    else:
        gate = jax.nn.silu(h @ p["w_gate"].astype(dtype))
        up = h @ p["w_up"].astype(dtype)
        ffn_out = (gate * up) @ p["w_down"].astype(dtype)
    if tp_axis is not None:
        ffn_out = lax.psum(ffn_out, tp_axis)
    return x + ffn_out, k_cache, v_cache


def decode_step(
    params: Params,
    cache,
    tokens_t,
    pos,
    cfg: LlamaConfig,
    tp_axis: str | None = None,
    shard_vocab: bool = False,
):
    """One incremental step: ``tokens_t [B]`` at position ``pos`` ->
    ``(logits [B, V], cache)``.

    Under ``tp_axis`` with ``shard_vocab`` the embed table is the local
    ``[V/t, D]`` slice (Megatron parallel embedding, one psum) and the
    unembed emits a ``[B, V/t]`` logit slice that one ``all_gather``
    assembles to the full ``[B, V]`` — the only full-vocab array decode
    ever materializes, needed because sampling is a global decision."""
    k_all, v_all = cache
    if shard_vocab:
        from ddl25spring_tpu.parallel.tp import vocab_sharded_embed

        x = vocab_sharded_embed(
            params["embed"], tokens_t[:, None], tp_axis, jnp.dtype(cfg.dtype)
        )
    else:
        x = llama.embed(params, tokens_t[:, None], cfg)  # [B, 1, D]
    # rotary phases depend only on the position — computed once per step,
    # shared by every layer
    cos, sin = llama.rope_angles(
        1, cfg.head_dim, pos=pos[None].astype(jnp.float32)
    )

    def layer(x, inputs):
        block_p, kc, vc = inputs
        x, kc, vc = _block_decode(
            block_p, x, kc, vc, pos, cos, sin, cfg, tp_axis=tp_axis
        )
        return x, (kc, vc)

    x, (k_all, v_all) = lax.scan(layer, x, (params["blocks"], k_all, v_all))
    logits = llama.unembed(params, x, cfg)[:, 0]
    if shard_vocab:
        # shard i holds vocab columns [i*V/t, (i+1)*V/t): index-ordered
        # concat reassembles the true vocab order
        logits = lax.all_gather(logits, tp_axis, axis=1, tiled=True)
    return logits, (k_all, v_all)


def sample_logits(
    logits: jax.Array,
    key: jax.Array,
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
) -> jax.Array:
    """Sample token ids from ``logits [B, V]`` — the standard decode
    controls, all static-shape jittable:

    - ``temperature=0`` -> greedy argmax (``top_k``/``top_p`` ignored);
    - ``top_k > 0`` -> keep only the k highest logits (``lax.top_k``,
      static k — no dynamic shapes under jit);
    - ``top_p < 1`` -> nucleus sampling: keep the smallest prefix of the
      probability-sorted vocab whose mass reaches ``top_p``.  The
      highest-probability token is always kept (the prefix is never
      empty), matching the usual convention.

    Filters compose (k first, then p) by masking pruned entries to -inf;
    renormalization is implicit in ``jax.random.categorical``.
    """
    if temperature == 0.0:
        return logits.argmax(-1).astype(jnp.int32)
    logits = logits.astype(jnp.float32) / jnp.float32(temperature)
    if top_k > 0 and top_k < logits.shape[-1]:
        kth = lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        # mass BEFORE each entry; entries whose preceding mass already
        # reaches top_p are cut, so the first entry always survives
        cum_before = jnp.cumsum(probs, axis=-1) - probs
        cutoff = jnp.sum(
            jnp.where(cum_before < top_p, 1, 0), axis=-1, keepdims=True
        )
        # top_p == 0.0 gives cutoff 0 (cum_before[0] = 0 is not < 0);
        # clamp so the best token is always kept instead of wrapping
        # take_along_axis to the weakest logit and disabling the filter
        cutoff = jnp.maximum(cutoff, 1)
        threshold = jnp.take_along_axis(sorted_logits, cutoff - 1, axis=-1)
        logits = jnp.where(logits < threshold, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def generate(
    params: Params,
    prompt: jax.Array,
    cfg: LlamaConfig,
    max_new_tokens: int,
    temperature: float = 0.0,
    key: jax.Array | None = None,
    max_len: int | None = None,
    top_k: int = 0,
    top_p: float = 1.0,
    tp_axis: str | None = None,
    shard_vocab: bool = False,
):
    """Generate ``max_new_tokens`` continuations of ``prompt [B, P]``.

    Returns ``[B, max_new_tokens]`` int32.  ``temperature=0`` is greedy;
    otherwise softmax sampling at the given temperature with ``key``,
    optionally truncated to the ``top_k`` highest logits and/or the
    ``top_p`` probability nucleus (``sample_logits``).  Jittable end to
    end (prefill scan + decode scan, static shapes).

    ``tp_axis``: for calls INSIDE a ``shard_map`` over a TP mesh axis —
    params carry the :func:`~ddl25spring_tpu.parallel.tp.tp_param_specs`
    layout, the KV cache is head-sharded, and every shard samples the
    identical token stream (same key, same assembled logits).  Use
    :func:`make_tp_generate` for the jitted entry point.
    """
    B, P = prompt.shape
    L_max = max_len or (P + max_new_tokens)
    if L_max < P + max_new_tokens:
        raise ValueError(
            f"max_len={L_max} < prompt {P} + max_new_tokens "
            f"{max_new_tokens}: dynamic_update_slice would clamp and "
            "silently corrupt the cache"
        )
    if key is None:
        key = jax.random.PRNGKey(0)
    # local head count from the param slice (H/t under TP, H otherwise)
    heads = params["blocks"]["wq"].shape[-1] // cfg.head_dim
    cache = init_kv_cache(cfg, B, L_max, num_heads=heads)

    def vary(x):
        # scan carries must hold a stable VMA type: the cache starts as
        # invariant zeros but becomes tp-varying at the first head-slice
        # write.  Logits are varying only under shard_vocab (local slices
        # all_gathered); without it the row-parallel psums leave the
        # activations — and hence logits — invariant.
        if tp_axis is None:
            return x
        return pcast(x, (tp_axis,), to="varying")

    vary_logits = vary if shard_vocab else (lambda x: x)
    cache = jax.tree.map(vary, cache)

    # prefill: feed prompt tokens through the cached step (logits of the
    # last prompt token seed the first generated one)
    def pre(carry, inp):
        cache, _ = carry
        t, pos = inp
        logits, cache = decode_step(
            params, cache, t, pos, cfg, tp_axis, shard_vocab
        )
        return (cache, logits), None

    (cache, logits), _ = lax.scan(
        pre,
        (cache, vary_logits(jnp.zeros((B, cfg.vocab_size), jnp.float32))),
        (prompt.T, jnp.arange(P)),
    )

    def pick(logits, k):
        return sample_logits(logits, k, temperature, top_k, top_p)

    def step(carry, inp):
        cache, logits, key = carry
        pos = inp
        key, sub = jax.random.split(key)
        tok = pick(logits, sub)
        logits, cache = decode_step(
            params, cache, tok, pos, cfg, tp_axis, shard_vocab
        )
        return (cache, logits, key), tok

    (_, _, _), toks = lax.scan(
        step, (cache, logits, key), P + jnp.arange(max_new_tokens)
    )
    return toks.T  # [B, max_new_tokens]


def make_tp_generate(
    cfg: LlamaConfig,
    mesh,
    max_new_tokens: int,
    model_axis: str = "model",
    shard_vocab: bool = True,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    max_len: int | None = None,
):
    """TP-sharded generation: ``gen(params, prompt, key) -> [B, new]``.

    Serving-side counterpart of the TP training step
    (:mod:`ddl25spring_tpu.parallel.tp`): params stay in the exact layout
    training produced (column/row-split matmuls, vocab-sharded
    embed/unembed when ``shard_vocab``), attention heads and the KV
    cache shard over ``model_axis``, and the per-step communication is
    the two row-parallel psums plus one ``[B, V]`` logits all_gather.
    Every shard runs the identical sampling chain (invariant key, equal
    assembled logits), so generation is exactly the single-device
    :func:`generate` — pinned in ``tests/test_decode.py``."""
    from functools import partial as _partial

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ddl25spring_tpu.parallel.tp import tp_param_specs

    if cfg.num_heads % mesh.shape[model_axis]:
        raise ValueError(
            f"num_heads ({cfg.num_heads}) not divisible by "
            f"{model_axis}={mesh.shape[model_axis]}"
        )
    specs = tp_param_specs(model_axis, shard_vocab, cfg.n_experts)

    @jax.jit
    @_partial(
        shard_map,
        mesh=mesh,
        in_specs=(specs, P(), P()),
        out_specs=P(),
    )
    def gen(params, prompt, key):
        toks = generate(
            params, prompt, cfg, max_new_tokens,
            temperature=temperature, key=key, max_len=max_len,
            top_k=top_k, top_p=top_p,
            tp_axis=model_axis, shard_vocab=shard_vocab,
        )
        if shard_vocab:
            # every shard holds the identical stream; pmax is an
            # idempotent re-type to the invariant out_spec (psum would
            # scale by t).  Without shard_vocab the logits — and the
            # sampled stream — are already invariant.
            toks = lax.pmax(toks, model_axis)
        return toks

    return gen
