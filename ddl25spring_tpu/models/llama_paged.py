"""The dense LLaMA block against a page pool: what ``LlamaConfig`` offers
the paged server (:mod:`ddl25spring_tpu.serve.paged_model`).

Two planes a position a layer, ``k`` and ``v`` of ``(heads, head_dim)``;
one block for any ``T`` (a decode tick is ``T = 1``, a prompt batch ``T =
W``); a tensor-parallel build splits both planes over their head axis.

The block casts every matrix to ``cfg.dtype`` where it uses it
(``.astype(dtype)``, as the trainer's ``llama.embed`` / ``unembed`` do on
float32 masters) and multiplies the norm scales in float32.  A server
never trains, so :func:`resident` rounds the matrices ONCE, when the
engine is built: the casts at use are then no-ops that the compiler drops,
every matmul and the embedding gather see the same bits as before, and no
pass reads four bytes a parameter or converts a whole layer stack.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ddl25spring_tpu.models import llama
from ddl25spring_tpu.serve import kv_pages
from ddl25spring_tpu.serve.paged_model import PagedModel
from ddl25spring_tpu.utils.config import LlamaConfig

# The TP page-pool layout contract, as data: a plane ``[n_pages + 1, L,
# page_len, H, hd]`` shards exactly ONE dimension — the heads — over the
# model axis (each shard caches its local ``H/t`` heads).  Prefill writes
# the pages decode reads, so every compiled serve program must agree on
# this split; the sharding-flow verifier (analysis/shard_flow.py, rule
# H013) walks each program pair's entry-parameter shardings against it in
# `graft_lint --shard-flow`.
KV_POOL_HEAD_DIM = 3

# the block's leaves that :func:`paged_block` casts to ``cfg.dtype`` at
# their use; ``ln1`` / ``ln2`` are multiplied in float32 (``rms_norm``)
BLOCK_MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _rope_rows(x, cos, sin):
    """RoPE where every row has its OWN positions: ``x [B, T, H, hd]``,
    ``cos/sin [B, T, hd/2]``.  Same arithmetic as
    :func:`~ddl25spring_tpu.models.llama.apply_rope` (which shares one
    position vector over the batch), so fp32 values match the dense
    decode bitwise."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    out = jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _rope_at(pos, head_dim: int):
    """``(cos, sin)``, each ``[B, T, hd/2]``, of absolute positions
    ``pos [B, T]``."""
    cos, sin = llama.rope_angles(
        1, head_dim, pos=pos.reshape(-1).astype(jnp.float32)
    )
    return cos.reshape(*pos.shape, -1), sin.reshape(*pos.shape, -1)


def paged_block(p, x, planes, layer, rows, pages, offs, pos, cos, sin,
                cfg: LlamaConfig, tp_axis: str | None):
    """One transformer block on ``T`` positions a row, ``x [B, T, D]`` at
    absolute positions ``pos [B, T]``, against the PAGE POOL — the paged
    twin of :func:`ddl25spring_tpu.models.decode._block_decode`, op for
    op (same einsums, same fp32 softmax, same ``-1e30`` mask fill).
    ``rows [B, P]`` is the clamped page table of the batch's sequences
    (any leading run of entries that covers every live position);
    ``pages``/``offs [B, T]`` are the write coordinates of each position
    (trash-routed where masked).  All ``T`` keys and values are written
    first, then the row's page view is gathered, so a query at ``pos``
    sees what earlier passes left in the pages, this pass's positions up
    to its own, and nothing later.  The decode tick, the drafter and the
    verify pass are the ``T = 1`` case; prefill runs a whole prompt
    batch.  Its parts are scoped ``attn`` / ``page_write`` /
    ``page_gather`` / ``mlp`` (``jax.named_scope``: names in the
    operations' metadata, no operation changes)."""
    dtype = jnp.dtype(cfg.dtype)
    B, T = x.shape[:2]
    hd = cfg.head_dim

    with jax.named_scope("attn"):
        h = llama.rms_norm(x, p["ln1"])
        q = (h @ p["wq"].astype(dtype)).reshape(B, T, -1, hd)
        k = (h @ p["wk"].astype(dtype)).reshape(B, T, -1, hd)
        v = (h @ p["wv"].astype(dtype)).reshape(B, T, -1, hd)
        q = _rope_rows(q, cos, sin)
        k = _rope_rows(k, cos, sin)

    with jax.named_scope("page_write"):
        planes = kv_pages.write_planes(
            planes, layer, pages, offs, {"k": k, "v": v}
        )
    with jax.named_scope("page_gather"):
        view = kv_pages.gather_planes(planes, layer, rows)
        ks, vs = view["k"], view["v"]  # [B, P * page_len, H, hd]
        M = ks.shape[1]

    with jax.named_scope("attn"):
        s = jnp.einsum("bqhd,bmhd->bhqm", q, ks).astype(jnp.float32)
        s = s / jnp.sqrt(jnp.float32(hd))
        live = jnp.arange(M)[None, None, :] <= pos[:, :, None]
        s = jnp.where(live[:, None, :, :], s, -1e30)
        probs = jax.nn.softmax(s, axis=-1).astype(dtype)
        attn = jnp.einsum("bhqm,bmhd->bqhd", probs, vs)
        attn_out = attn.reshape(B, T, -1) @ p["wo"].astype(dtype)
        if tp_axis is not None:
            attn_out = lax.psum(attn_out, tp_axis)
        x = x + attn_out

    with jax.named_scope("mlp"):
        h = llama.rms_norm(x, p["ln2"])
        gate = jax.nn.silu(h @ p["w_gate"].astype(dtype))
        up = h @ p["w_up"].astype(dtype)
        ffn_out = (gate * up) @ p["w_down"].astype(dtype)
        if tp_axis is not None:
            ffn_out = lax.psum(ffn_out, tp_axis)
        return x + ffn_out, planes


def resident(params, cfg: LlamaConfig):
    """``params`` as the serving programs read them: ``embed``,
    ``unembed`` and the block matrices in ``cfg.dtype``, the norm scales
    (``ln1``, ``ln2``, ``ln_f``) as they are.  One leaf at a time (the
    transient is one leaf); a leaf already in the type is returned
    itself, so a resident tree comes back as the same arrays."""
    dtype = jnp.dtype(cfg.dtype)

    def cast(x):
        return x if x.dtype == dtype else x.astype(dtype)

    return {
        **params,
        "embed": cast(params["embed"]),
        "unembed": cast(params["unembed"]),
        "blocks": {
            name: cast(w) if name in BLOCK_MATRICES else w
            for name, w in params["blocks"].items()
        },
    }


def paged_model(cfg: LlamaConfig) -> PagedModel | None:
    """``cfg``'s offer to the paged server; ``None`` for the switch-MoE
    FFN (``n_experts > 0``), whose capacity buckets drop tokens and which
    therefore trains only."""
    if cfg.n_experts > 0:
        return None

    def layers(params, slots, rows, pages, offs, pos, live, tp_axis):
        # every weight is scanned; nothing is counted or kept a slot
        del params, slots, live
        cos, sin = _rope_at(pos, cfg.head_dim)

        def run_layer(p, li, x, planes):
            x, planes = paged_block(
                p, x, planes, li, rows, pages, offs, pos, cos, sin, cfg,
                tp_axis,
            )
            return x, planes, None

        return run_layer

    kv = (cfg.num_heads, cfg.head_dim)
    return PagedModel(
        planes={"k": kv, "v": kv},
        n_layers=cfg.n_layers,
        dtype=cfg.dtype,
        embed=lambda params, tokens: llama.embed(params, tokens, cfg),
        unembed=lambda params, x: llama.unembed(params, x, cfg),
        layers=layers,
        tp_shard={"k": KV_POOL_HEAD_DIM, "v": KV_POOL_HEAD_DIM},
        resident=lambda params: resident(params, cfg),
    )
