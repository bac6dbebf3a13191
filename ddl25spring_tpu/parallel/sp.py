"""Sequence/context parallelism over a ``seq`` mesh axis: ring + Ulysses.

The reference caps context at 256 tokens with unsharded attention (SURVEY §5:
long-context absent) — this module is the TPU-native long-context extension.
Tokens shard over a ``seq`` axis: each device holds ``L/n`` positions of
every sequence and activations never materialize full length outside
attention.  Two strategies cover the two classic designs:

- **ring** (default): attention runs as a RING — each of ``n`` steps
  combines the local queries with one rotating KV block (online-softmax
  accumulation in fp32), then ``ppermute``s the KV block to the next
  neighbor over ICI.  Compute overlaps transfer by structure: the permute is
  inside the same scanned step XLA schedules around the matmuls.  Scales to
  any ``n``; O(L/n · d) resident per shard with the flash local step.
- **ulysses** (DeepSpeed-Ulysses style): one ``all_to_all`` re-shards
  q/k/v from sequence-sharded ``[B, L/n, H, hd]`` to head-sharded
  ``[B, L, H/n, hd]``, each device runs FULL-length causal attention over
  its head subset (the Pallas flash kernel at full L on TPU), and a second
  ``all_to_all`` restores sequence sharding.  Two collectives total per
  attention (vs ``n`` ring hops) at the price of ``H % n == 0`` and
  full-``L`` attention residency per device — the right trade when heads
  are plentiful and the per-device flash pass fits.

Causality is handled by GLOBAL positions: query at global position i attends
key at global position j iff j <= i, so rotated blocks are masked per
(q_pos, kv_pos) pair — no schedule-order assumptions.

The causal-LM loss needs one extra hop: the target of a shard's LAST token is
the NEXT shard's first token, fetched with a single ``ppermute`` of one token
per sequence (the only cross-shard data the loss requires).
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import optax
from jax import lax, shard_map
from jax.lax import pcast

from ddl25spring_tpu.parallel.bucketing import donate_argnums
from jax.sharding import Mesh, PartitionSpec as P

from ddl25spring_tpu.models import llama
from ddl25spring_tpu.utils.config import LlamaConfig

Params = dict[str, Any]


def ring_attention(q, k, v, axis: str, q_pos, kv_pos, dtype):
    """Causal ring attention inside ``shard_map``.

    ``q/k/v``: ``[B, Ll, H, hd]`` local shards; ``q_pos/kv_pos``: ``[Ll]``
    global positions of the local queries / of the CURRENT kv block (rotates
    with it).  Returns ``[B, Ll, H, hd]``.
    """
    n = lax.psum(1, axis)
    hd = q.shape[-1]
    B, Ll, H, _ = q.shape
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    perm = [(i, (i + 1) % n) for i in range(n)]

    q32 = q.astype(jnp.float32)

    def step(carry, _):
        k_blk, v_blk, pos_blk, m, l, o = carry
        s = jnp.einsum("blhd,bmhd->bhlm", q32, k_blk.astype(jnp.float32))
        s = s * scale
        causal = q_pos[:, None] >= pos_blk[None, :]  # [Ll, Lkv]
        s = jnp.where(causal[None, None], s, -jnp.inf)

        m_blk = s.max(-1)                      # [B, H, Ll]
        m_new = jnp.maximum(m, m_blk)
        # exp(-inf - -inf) guards: where a row has seen nothing yet, m_new
        # may still be -inf; make the correction factor 0, not nan
        corr = jnp.where(m == -jnp.inf, 0.0, jnp.exp(m - m_new))
        p = jnp.exp(jnp.where(s == -jnp.inf, -jnp.inf, s - m_new[..., None]))
        l_new = l * corr + p.sum(-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bhlm,bmhd->bhld", p, v_blk.astype(jnp.float32)
        )

        k_blk = lax.ppermute(k_blk, axis, perm)
        v_blk = lax.ppermute(v_blk, axis, perm)
        pos_blk = lax.ppermute(pos_blk, axis, perm)
        return (k_blk, v_blk, pos_blk, m_new, l_new, o_new), None

    # derive the accumulator inits from q (0*q keeps values exact) so they
    # carry q's varying-axes type — a plain jnp.zeros is axis-invariant and
    # shard_map's scan typing rejects the carry mismatch
    zero_blh = 0.0 * q32[..., 0].transpose(0, 2, 1)        # [B, H, Ll]
    init = (
        k, v, kv_pos,
        zero_blh - jnp.inf,
        zero_blh,
        0.0 * q32.transpose(0, 2, 1, 3),                   # [B, H, Ll, hd]
    )
    (_, _, _, _, l, o), _ = lax.scan(step, init, None, length=n)
    # every causal row has at least its own diagonal -> l > 0
    out = (o / l[..., None]).transpose(0, 2, 1, 3)  # [B, Ll, H, hd]
    return out.astype(dtype)


def _dense_attention_with_lse(q, k, v, causal: bool):
    """``[B, Lq, H, hd] x [B, Lk, H, hd] -> (o fp32 [B, Lq, H, hd],
    lse [B, H, Lq])`` — the off-TPU stand-in for
    ``flash_attention_with_lse`` inside ``shard_map`` (the Pallas
    interpreter cannot execute under VMA-checked shard_map off-TPU, cf.
    ``models/llama.py:block_forward``)."""
    hd = q.shape[-1]
    s = jnp.einsum(
        "blhd,bmhd->bhlm", q.astype(jnp.float32), k.astype(jnp.float32)
    ) / jnp.sqrt(jnp.float32(hd))
    if causal:
        mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    m = s.max(-1)
    p = jnp.exp(s - m[..., None])
    l = p.sum(-1)
    lse = m + jnp.log(l)
    o = jnp.einsum("bhlm,bmhd->bhld", p / l[..., None], v.astype(jnp.float32))
    return o.transpose(0, 2, 1, 3), lse


def ring_flash_attention(
    q, k, v, axis: str, dtype, block_q: int = 512, block_k: int = 512
):
    """Ring attention with a FLASH local step: SP x flash compose
    (VERDICT r3 directive #2), so per-shard attention memory is O(Ll·d)
    and the two long-context features multiply (n-device ``seq`` mesh x
    32k-per-shard flash = n*32k effective context).

    Requires what :func:`make_sp_loss` guarantees: shard ``s`` holds the
    CONTIGUOUS positions ``[s*Ll, (s+1)*Ll)``.  Block visibility is then
    structural, no per-pair masks: ring step 0 is the own block (causal
    flash); at step ``t > 0`` device ``s`` holds the block of shard
    ``s - t (mod n)`` — fully visible when ``s >= t``, fully masked
    otherwise.  Per-step outputs ``(o_t, lse_t)`` fold into the
    accumulator with the log-sum-exp merge
    (``o <- (o*e^{lse-m} + o_t*e^{lse_t-m}) / (e^{lse-m}+e^{lse_t-m})``);
    the lse cotangent this merge needs is exactly what
    ``flash_attention_with_lse``'s VJP provides.

    On TPU each local step is the fully-blocked Pallas kernel; off-TPU a
    dense-with-lse fallback keeps the same ring/merge math testable on
    the CPU mesh.
    """
    n = lax.psum(1, axis)
    s_idx = lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]

    from ddl25spring_tpu.ops.flash_attention import off_tpu

    on_tpu = not off_tpu("ring attention runs its DENSE-with-lse blocks")

    def attn(qq, kk, vv, causal):
        if on_tpu:
            from ddl25spring_tpu.ops.flash_attention import (
                flash_attention_with_lse,
            )

            o, lse = flash_attention_with_lse(
                qq, kk, vv, causal=causal, block_q=block_q, block_k=block_k
            )
            return o.astype(jnp.float32), lse.astype(jnp.float32)
        return _dense_attention_with_lse(qq, kk, vv, causal)

    o_acc, lse_acc = attn(q, k, v, True)  # own block: causal
    if n == 1:
        return o_acc.astype(dtype)

    def step(carry, t):
        k_blk, v_blk, o_acc, lse_acc = carry
        k_blk = lax.ppermute(k_blk, axis, perm)
        v_blk = lax.ppermute(v_blk, axis, perm)
        o_t, lse_t = attn(q, k_blk, v_blk, False)
        vis = s_idx >= t  # holding shard s-t's block: visible iff s >= t
        lse_t = jnp.where(vis, lse_t, -jnp.inf)  # masked -> zero weight
        m = jnp.maximum(lse_acc, lse_t)
        a = jnp.exp(lse_acc - m)
        b = jnp.exp(lse_t - m)  # exp(-inf - m) == 0 when masked
        denom = a + b
        aw = (a / denom).transpose(0, 2, 1)[..., None]  # [B, Ll, H, 1]
        bw = (b / denom).transpose(0, 2, 1)[..., None]
        o_acc = o_acc * aw + o_t * bw
        lse_acc = m + jnp.log(denom)
        return (k_blk, v_blk, o_acc, lse_acc), None

    (_, _, o_acc, _), _ = lax.scan(
        step, (k, v, o_acc, lse_acc), jnp.arange(1, n)
    )
    return o_acc.astype(dtype)


def ulysses_attention(q, k, v, axis: str, dtype, use_flash: bool = True):
    """All-to-all (DeepSpeed-Ulysses-style) sequence-parallel attention
    inside ``shard_map``.

    ``q/k/v``: ``[B, Ll, H, hd]`` sequence shards (RoPE already applied at
    GLOBAL positions by the caller, so the re-gathered sequence carries the
    right phases).  One tiled ``all_to_all`` turns the ``seq`` sharding into
    a head sharding ``[B, n*Ll, H/n, hd]`` — shard ``s`` holds contiguous
    positions ``[s*Ll, (s+1)*Ll)`` (the :func:`make_sp_loss` layout), so the
    index-ordered concat reassembles the true sequence — then full-length
    causal attention runs locally (Pallas flash on TPU when ``use_flash``,
    dense otherwise and off-TPU where the interpreter cannot run under
    VMA-checked shard_map), and the inverse ``all_to_all`` restores
    ``[B, Ll, H, hd]``.  ``use_flash`` mirrors the ring path's
    ``cfg.use_flash`` gating so ``--no-flash`` debugging degrades BOTH
    modes to dense attention.
    """
    n = lax.psum(1, axis)
    H = q.shape[2]
    if H % n:
        raise ValueError(
            f"ulysses needs heads divisible by the seq axis: H={H}, n={n}"
        )
    # one ingress collective: q/k/v stacked -> a single tiled all_to_all
    qkv = jnp.stack((q, k, v))  # [3, B, Ll, H, hd]
    qkv = lax.all_to_all(qkv, axis, split_axis=3, concat_axis=2, tiled=True)
    qg, kg, vg = qkv[0], qkv[1], qkv[2]
    from ddl25spring_tpu.ops.flash_attention import flash_attention, off_tpu

    if use_flash and not off_tpu("ulysses use_flash runs DENSE attention"):
        o = flash_attention(qg, kg, vg)
    else:
        o = llama.causal_attention(qg, kg, vg, dtype)
    return lax.all_to_all(
        o.astype(dtype), axis, split_axis=1, concat_axis=2, tiled=True
    )


def sp_shifted_targets(tokens: jax.Array, seq_axis: str):
    """``(targets, valid)`` for the sequence-sharded causal loss: the
    target of a shard's LAST token is the NEXT shard's first token — one
    single-token ``ppermute`` fetches it (the only cross-shard data the
    loss needs) — and the final shard's last position has no target
    (masked), matching the serial loss over ``L_global - 1`` positions.

    ``tokens`` may carry leading batch-like dims (``[..., B, Ll]``); the
    ppermute/concat/mask act on the last dim.  Collective-free consumers
    (the pipeline's per-tick loss, whose collectives must stay out of
    ``lax.cond``) call this ONCE up front and use
    :func:`sp_local_ce_sum` per tick."""
    n = lax.psum(1, seq_axis)
    Ll = tokens.shape[-1]
    nxt = lax.ppermute(
        tokens[..., :1], seq_axis, [((i + 1) % n, i) for i in range(n)]
    )
    targets = jnp.concatenate([tokens[..., 1:], nxt], axis=-1)
    is_last_shard = lax.axis_index(seq_axis) == n - 1
    valid = jnp.where(
        is_last_shard & (jnp.arange(Ll) == Ll - 1), 0.0, 1.0
    )
    return targets, valid


def sp_local_ce_sum(logits, targets, valid) -> jax.Array:
    """Collective-free local CE SUM over one shard's positions
    (``logits [B, Ll, V]``, ``targets [B, Ll]``, ``valid [Ll]`` from
    :func:`sp_shifted_targets`); callers psum/normalize across shards."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
    return -(picked * valid[None, :]).sum()


def sp_causal_lm_loss(
    logits: jax.Array, tokens: jax.Array, seq_axis: str
) -> jax.Array:
    """Causal-LM loss over sequence-sharded ``logits [B, Ll, V]`` /
    ``tokens [B, Ll]`` (inside ``shard_map``; shard ``s`` holds
    contiguous global positions ``[s*Ll, (s+1)*Ll)``).  Returns the
    seq-invariant global mean (one psum pair).  Shared by
    :func:`make_sp_loss` and the pipeline's ``seq_axis`` mode (which
    splits it into :func:`sp_shifted_targets` + :func:`sp_local_ce_sum`
    so no collective lands inside its tick cond)."""
    B, Ll = tokens.shape
    targets, valid = sp_shifted_targets(tokens, seq_axis)
    local_sum = sp_local_ce_sum(logits, targets, valid)
    local_cnt = (valid[None, :] * jnp.ones((B, 1))).sum()
    return lax.psum(local_sum, seq_axis) / lax.psum(local_cnt, seq_axis)


def make_sp_attn_fn(cfg: LlamaConfig, seq_axis: str, mode: str, pos):
    """The attention implementation a sequence-sharded forward injects
    into ``block_forward``: ring (dense or flash local step per
    ``cfg.use_flash``) or Ulysses all-to-all.  ``pos`` is the shard's
    global-position vector (ring mode's per-pair causal mask needs it).
    Shared by :func:`make_sp_loss` and the pipeline's ``seq_axis``
    mode."""
    if mode == "ulysses":
        def attn(q, k, v, dtype):
            return ulysses_attention(
                q, k, v, seq_axis, dtype, use_flash=cfg.use_flash
            )

        return attn
    if cfg.use_flash:
        def attn(q, k, v, dtype):
            return ring_flash_attention(q, k, v, seq_axis, dtype)

        return attn
    return partial(ring_attention, axis=seq_axis, q_pos=pos, kv_pos=pos)


def make_sp_loss(
    cfg: LlamaConfig,
    mesh: Mesh,
    seq_axis: str = "seq",
    data_axis: str | None = None,
    mode: str = "ring",
):
    """``loss(params, tokens) -> scalar``: full llama forward with tokens
    sharded ``[B, L/n]`` over ``seq_axis`` and ring attention in every block.
    Matches :func:`~ddl25spring_tpu.models.llama.llama_forward` + causal-LM
    loss on the unsharded model.

    Switch-MoE configs are supported: each shard's blocks dispatch over the
    LOCAL ``[B*L/n, D]`` token group and the weighted aux loss is the
    ``pmean`` of per-shard switch losses — the standard sharded-MoE
    estimator (same note as :mod:`ddl25spring_tpu.parallel.ep`), so it is
    not bitwise the unsharded aux under overflow.

    ``mode`` selects the attention strategy: ``"ring"`` (rotating KV blocks;
    flash local step when ``cfg.use_flash``) or ``"ulysses"`` (two
    all_to_alls re-shard seq -> heads; needs ``num_heads % n == 0``)."""
    n = mesh.shape[seq_axis]
    if mode not in ("ring", "ulysses"):
        raise ValueError(f"unknown SP mode {mode!r}")
    if mode == "ulysses" and cfg.num_heads % n:
        raise ValueError(
            f"ulysses SP needs num_heads ({cfg.num_heads}) divisible by "
            f"the {seq_axis!r} axis size ({n})"
        )

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), P(data_axis, seq_axis)),
        out_specs=P(),
    )
    def sp_loss(params: Params, tokens: jax.Array) -> jax.Array:
        axes = (seq_axis,) + ((data_axis,) if data_axis else ())
        vparams = pcast(params, axes, to="varying")
        B, Ll = tokens.shape
        offset = lax.axis_index(seq_axis) * Ll
        pos = offset + jnp.arange(Ll)

        attn = make_sp_attn_fn(cfg, seq_axis, mode, pos)
        x = llama.embed(vparams, tokens, cfg)
        x = llama.apply_blocks(
            vparams["blocks"], x, cfg,
            with_aux=cfg.n_experts > 0,
            pos=pos,
            attn_fn=lambda q, k, v, dtype: attn(q, k, v, dtype=dtype),
        )
        if cfg.n_experts > 0:
            x, moe_aux = x
        else:
            moe_aux = jnp.float32(0.0)
        logits = llama.unembed(vparams, x, cfg)  # [B, Ll, V] fp32
        total = sp_causal_lm_loss(logits, tokens, seq_axis)
        if cfg.n_experts > 0:
            total = total + jnp.float32(cfg.moe_aux_weight) * lax.pmean(
                moe_aux, seq_axis
            )
        if data_axis is not None:
            total = lax.pmean(total, data_axis)
        return total

    return sp_loss


def make_sp_train_step(
    cfg: LlamaConfig,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    seq_axis: str = "seq",
    data_axis: str | None = None,
    mode: str = "ring",
    donate: bool | None = None,
    sentinel: bool | None = None,
):
    """Jitted SP(xDP) train step (params replicated, tokens seq-sharded).
    ``donate`` (default on): params/opt-state buffers alias in place
    (:func:`~ddl25spring_tpu.parallel.dp.donate_argnums`); ``sentinel``
    opts into the in-step numerics sentinels
    (:mod:`ddl25spring_tpu.obs.sentinels`)."""
    from ddl25spring_tpu.obs import sentinels

    s_on, s_policy = sentinels.resolve(sentinel)
    loss_fn = make_sp_loss(cfg, mesh, seq_axis, data_axis, mode)

    @partial(jax.jit, donate_argnums=donate_argnums(donate))
    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        updates, new_state = tx.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        new_params, new_state = sentinels.guard(
            "sp", (new_params, new_state), loss=loss, grads=grads,
            params=params, updates=updates,
            fallback=(params, opt_state), enabled=s_on, policy=s_policy,
        )
        return new_params, new_state, loss

    return step


def describe(
    mesh: Mesh,
    seq_axis: str = "seq",
    data_axis: str | None = None,
    mode: str = "ring",
):
    """Registry hook for :mod:`ddl25spring_tpu.obs.xla_analytics`: the
    lowerable ring-SP train step + the analytic collective signature.

    Ring attention's compiled fingerprint is ``collective-permute``
    inside a while loop whose trip count is the seq-axis size — one KV
    rotation per ring step, per layer, forward and backward — plus the
    one boundary-token hop of the causal loss.  All permutes group over
    the seq axis; all-to-all appearing under ``mode="ring"`` means
    someone swapped in the Ulysses path without saying so.
    """
    if data_axis is None and "data" in mesh.axis_names:
        data_axis = "data"
    cfg = LlamaConfig(
        vocab_size=64, dmodel=16, num_heads=2, n_layers=2, ctx_size=16,
        dtype="float32",
    )
    n = mesh.shape[seq_axis]
    dp = mesh.shape[data_axis] if data_axis else 1
    tx = optax.sgd(1e-2)
    params = llama.init_llama_params(jax.random.PRNGKey(0), cfg)
    step = make_sp_train_step(
        cfg, tx, mesh, seq_axis, data_axis, mode, donate=True
    )
    tokens = jnp.zeros((4 * dp, cfg.ctx_size), jnp.int32)
    axes = [seq_axis] + ([data_axis] if data_axis else [])
    # fwd: n ring steps x (k, v, pos) rotations per layer + 1 targets hop;
    # bwd replays the ring (cotangent rotations) — floor at the fwd share
    min_hops = cfg.n_layers * n
    param_bytes = sum(
        l.size * l.dtype.itemsize for l in jax.tree.leaves(params)
    )
    return {
        "fn": step,
        "args": (params, tx.init(params), tokens),
        "lowered": "train_step",
        "meta": {
            "n_layers": cfg.n_layers,
            "seq_shards": n,
            "mode": mode,
            "local_len": cfg.ctx_size // n,
        },
        "expected": {
            "scalar_bytes": 64,
            "collective-permute": {
                "min_count": min_hops,
                "axes": axes,
            },
            # params are REPLICATED under SP, so the backward must sync
            # the full grad tree — exactly one param_bytes of all-reduce
            # (H011 surfaced this as real-but-undeclared traffic when
            # the sharding-flow verifier first ran; the tight band means
            # a second sync or a silent sharding collapse both trip)
            "all-reduce": {
                "min_bytes": param_bytes,
                "max_bytes": param_bytes + 256,
                "axes": axes,
            },
            **({"forbidden": ["all-to-all"]} if mode == "ring" else {}),
            "donation": {"min_saved_bytes": 1},
            "memory": {"max_peak_hbm_bytes": 2 * 1024 * 1024},
        },
    }
