"""Fused causal flash attention as a Pallas TPU kernel.

The hot op of the LLaMA workload.  The XLA path in
:func:`ddl25spring_tpu.models.llama.causal_attention` materializes the
``[B, H, L, L]`` score tensor in HBM; this kernel never does — blocks of
K/V stream through VMEM against an online-softmax running max/sum (the
flash-attention recurrence) so attention memory is O(L·d) instead of
O(L²).  That is the difference between HBM-bandwidth-bound and MXU-bound
attention on TPU, and it is what makes ctx >> the reference's 256
(``lab/s01_b1_microbatches.py:24``) trainable at all.

Layout: inputs ``[B, L, H, hd]`` are folded to ``[B*H, L, hd]``.  Every
kernel runs a **fully-blocked 3-D grid** — ``(B*H, L/bq, L/bk)`` with the
contraction dim innermost ("arbitrary" semantics) and the online state in
fp32 VMEM scratch that lives across the innermost grid walk.  No operand
is ever resident at full length L, so VMEM stays O(block) and long
contexts (8k/16k+) compile where a full-L layout blows the ~16 MB scoped
VMEM limit (double-buffered ``(1, L, hd)`` operands OOM at L=8192).
Causality skips the compute (``pl.when``) of blocks strictly above the
diagonal and finalizes each output row-block at its last contributing
KV block.  The backward is the standard two-kernel flash recomputation
from the saved ``(o, lse)`` residuals — no score tensor in either
direction; ``dq`` walks KV blocks innermost, ``dk/dv`` walks Q blocks
innermost, each accumulating into scratch.

All matmuls accumulate in fp32 (``preferred_element_type``); bf16 in/out.
``interpret=True`` runs the same kernels on CPU — used by the equivalence
tests against the dense reference implementation.
"""

from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

_DIMS3 = ("parallel", "parallel", "arbitrary")


def off_tpu(instead: str) -> bool:
    """True when the attached backend is not a TPU — and SAYS what runs
    ``instead`` of the compiled kernel there (a warning, once per call
    site under Python's default filter), so that no run takes the CPU
    branch in silence.  A chip run never gets here: ``chip_smoke.py``
    asserts the platform first and the kernel in the lowered step."""
    if jax.default_backend() == "tpu":
        return False
    warnings.warn(
        f"no TPU backend ({jax.default_backend()}): {instead}",
        stacklevel=3,
    )
    return True


def _sds(shape, dtype, *refs):
    """``ShapeDtypeStruct`` carrying the union of ``refs``' varying mesh
    axes (vma).  Under ``shard_map`` with VMA checking (JAX 0.9 default),
    ``pallas_call`` out_shapes must state how outputs vary across mesh axes
    — without this the kernel cannot be used inside the pipeline/DP
    shard_maps.  Outside shard_map every vma is empty and this degrades to
    a plain ShapeDtypeStruct."""
    vma: frozenset = frozenset()
    for r in refs:
        vma = vma | jax.typeof(r).vma
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _pos(base, n: int):
    # TPU needs >= 2-D iota; broadcasted_iota then squeeze
    return base + jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)[:, 0]


def _params3():
    # renamed TPUCompilerParams -> CompilerParams in newer pallas
    cls = getattr(pltpu, "CompilerParams", None) or pltpu.TPUCompilerParams
    return cls(dimension_semantics=_DIMS3)


# ------------------------------------------------------------------ forward


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
    *, block_q, block_k, nk, scale, causal,
):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: KV blocks strictly above the diagonal contribute nothing
    live = (j * block_k < (i + 1) * block_q) if causal else (j >= 0)

    @pl.when(live)
    def _tick():
        q = q_ref[0]                                   # [bq, hd]
        k_blk = k_ref[0]                               # [bk, hd]
        v_blk = v_ref[0]
        s = scale * jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                              # [bq, bk] fp32
        if causal:
            q_pos = _pos(i * block_q, block_q)
            kv_pos = _pos(j * block_k, block_k)
            s = jnp.where(q_pos[:, None] >= kv_pos[None, :], s, NEG_INF)
        m = m_ref[:, 0]
        m_new = jnp.maximum(m, s.max(-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])                # NEG_INF -> ~0
        l_ref[:, 0] = l_ref[:, 0] * corr + p.sum(-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:, 0] = m_new

    # last contributing KV block for this row-block
    j_last = (
        ((i + 1) * block_q - 1) // block_k if causal else nk - 1
    )

    @pl.when(j == j_last)
    def _finalize():
        l = l_ref[:, 0]
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        # lse is [BH, L, 1]: a (1, bq, 1) block satisfies the TPU tiling
        # rule (trailing dim equals the array dim) where (1, bq) cannot
        lse_ref[0, :, 0] = m_ref[:, 0] + jnp.log(l)


def _fwd(q3, k3, v3, block_q, block_k, scale, causal, interpret):
    BH, L, hd = q3.shape
    nq, nk = L // block_q, k3.shape[1] // block_k
    o, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, block_q=block_q, block_k=block_k, nk=nk,
            scale=scale, causal=causal,
        ),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            _sds(q3.shape, q3.dtype, q3, k3, v3),
            _sds((BH, L, 1), jnp.float32, q3, k3, v3),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum l
            pltpu.VMEM((block_q, hd), jnp.float32),  # output accumulator
        ],
        compiler_params=_params3(),
        interpret=interpret,
        name="flash_fwd",
    )(q3, k3, v3)
    return o, lse


# ----------------------------------------------------------------- backward


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc_ref,
    *, block_q, block_k, nk, scale, causal,
):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    live = (j * block_k < (i + 1) * block_q) if causal else (j >= 0)

    @pl.when(live)
    def _tick():
        q = q_ref[0]
        k_blk = k_ref[0]
        v_blk = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, :, 0]
        delta = delta_ref[0, :, 0]
        s = scale * jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if causal:
            q_pos = _pos(i * block_q, block_q)
            kv_pos = _pos(j * block_k, block_k)
            s = jnp.where(q_pos[:, None] >= kv_pos[None, :], s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[:, None]) * scale
        dq_acc_ref[...] += jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    j_last = (
        ((i + 1) * block_q - 1) // block_k if causal else nk - 1
    )

    @pl.when(j == j_last)
    def _finalize():
        dq_ref[0] = dq_acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc_ref, dv_acc_ref,
    *, block_q, block_k, nq, scale, causal,
):
    # grid (BH, nk, nq): KV block index is dim 1, Q walk is innermost
    j, i = pl.program_id(1), pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    # causal: Q blocks strictly below this KV block see none of it
    live = ((i + 1) * block_q > j * block_k) if causal else (i >= 0)

    @pl.when(live)
    def _tick():
        k = k_ref[0]
        v = v_ref[0]
        q_blk = q_ref[0]
        do_blk = do_ref[0]
        lse_blk = lse_ref[0, :, 0]
        delta_blk = delta_ref[0, :, 0]
        s = scale * jax.lax.dot_general(
            q_blk, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                              # [bq, bk] fp32
        if causal:
            q_pos = _pos(i * block_q, block_q)
            kv_pos = _pos(j * block_k, block_k)
            s = jnp.where(q_pos[:, None] >= kv_pos[None, :], s, NEG_INF)
        p = jnp.exp(s - lse_blk[:, None])
        dv_acc_ref[...] += jax.lax.dot_general(
            p.astype(do_blk.dtype), do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do_blk, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_blk[:, None]) * scale
        dk_acc_ref[...] += jax.lax.dot_general(
            ds.astype(q_blk.dtype), q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    # the last Q block always reaches the diagonal, so finalize at nq-1
    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _choose_block(L: int, want: int) -> int:
    """Largest block <= ``want`` that divides ``L`` and satisfies the TPU
    sublane rule (multiple of 8), falling back to the whole axis (a block
    equal to the array dim is always legal) — so any ctx_size works."""
    b = min(want, L)
    if L % b == 0 and (b % 8 == 0 or b == L):
        return b
    for c in range(b - b % 8, 7, -8):
        if L % c == 0:
            return c
    return L


# -------------------------------------------------------------- public API


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6)
)
def _flash(q3, k3, v3, block_q, block_k, causal, interpret):
    scale = 1.0 / (q3.shape[-1] ** 0.5)
    o, _ = _fwd(q3, k3, v3, block_q, block_k, scale, causal, interpret)
    return o


def _flash_fwd(q3, k3, v3, block_q, block_k, causal, interpret):
    scale = 1.0 / (q3.shape[-1] ** 0.5)
    o, lse = _fwd(q3, k3, v3, block_q, block_k, scale, causal, interpret)
    return o, (q3, k3, v3, o, lse)


def _bwd_pallas(q3, k3, v3, o, lse, do, delta, block_q, block_k, causal,
                interpret):
    """The two flash backward kernels, shared by the plain VJP and the
    lse-cotangent VJP (which only adjusts ``delta`` — see ``_flash_lse_bwd``)."""
    BH, L, hd = q3.shape
    nq, nk = L // block_q, k3.shape[1] // block_k
    scale = 1.0 / (hd ** 0.5)

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, block_q=block_q, block_k=block_k, nk=nk,
            scale=scale, causal=causal,
        ),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=_sds(q3.shape, q3.dtype, q3, k3, v3, do),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        compiler_params=_params3(),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q3, k3, v3, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, block_q=block_q, block_k=block_k, nq=nq,
            scale=scale, causal=causal,
        ),
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, hd), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, hd), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            _sds(k3.shape, k3.dtype, q3, k3, v3, do),
            _sds(v3.shape, v3.dtype, q3, k3, v3, do),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, hd), jnp.float32),
            pltpu.VMEM((block_k, hd), jnp.float32),
        ],
        compiler_params=_params3(),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q3, k3, v3, do, lse, delta)
    return dq, dk, dv


def _flash_bwd(block_q, block_k, causal, interpret, res, do):
    q3, k3, v3, o, lse = res
    # [BH, L, 1] like lse (TPU block-tiling rule, see _fwd_kernel)
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)[..., None]
    return _bwd_pallas(
        q3, k3, v3, o, lse, do, delta, block_q, block_k, causal, interpret
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


# ------------------------------------------------- lse-returning variant
# (the ring-SP composition needs per-block (o, lse) so ring steps can be
# merged with the log-sum-exp merge — parallel/sp.py:ring_flash_attention)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_lse(q3, k3, v3, block_q, block_k, causal, interpret):
    scale = 1.0 / (q3.shape[-1] ** 0.5)
    o, lse = _fwd(q3, k3, v3, block_q, block_k, scale, causal, interpret)
    return o, lse[..., 0]


def _flash_lse_fwd(q3, k3, v3, block_q, block_k, causal, interpret):
    scale = 1.0 / (q3.shape[-1] ** 0.5)
    o, lse = _fwd(q3, k3, v3, block_q, block_k, scale, causal, interpret)
    return (o, lse[..., 0]), (q3, k3, v3, o, lse)


def _flash_lse_bwd(block_q, block_k, causal, interpret, res, cts):
    """Backward with BOTH cotangents (do, dlse).

    ``ds_ij = p_ij * (dp_ij - delta_i + dlse_i)`` — the lse cotangent
    enters as ``d lse_i / d s_ij = p_ij``, so it folds into the ``delta``
    operand of the unchanged kernels (``delta' = delta - dlse``); ``dv``
    has no lse term (lse is v-independent).
    """
    do, dlse = cts
    q3, k3, v3, o, lse = res
    delta = (
        (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)
        - dlse.astype(jnp.float32)
    )[..., None]
    return _bwd_pallas(
        q3, k3, v3, o, lse, do, delta, block_q, block_k, causal, interpret
    )


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Causal flash attention.  ``q/k/v``: ``[B, L, H, hd]`` -> ``[B, L, H, hd]``.

    ``interpret=None`` selects interpreter mode off-TPU (and warns that it
    did) so the same call works in CPU tests and on the chip.  Block sizes are requests:
    ``_choose_block`` shrinks each to a legal divisor of ``L`` (TPU sublane
    rules), so any ctx works with the defaults.  The 512 default measured
    ~1.5-3x faster than 128 at ctx 2-4k on v5e (fewer grid ticks, same
    VMEM class — blocks are all that is resident).
    """
    B, L, H, hd = q.shape
    if interpret is None:
        interpret = off_tpu("flash attention runs in Pallas interpret mode")
    bq, bk = _choose_block(L, block_q), _choose_block(L, block_k)

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, L, hd)

    o3 = _flash(fold(q), fold(k), fold(v), bq, bk, causal, interpret)
    return o3.reshape(B, H, L, hd).transpose(0, 2, 1, 3)


def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """:func:`flash_attention` also returning the log-sum-exp.

    ``q/k/v``: ``[B, L, H, hd]`` -> ``(o [B, L, H, hd], lse [B, H, L])``.
    The VJP consumes cotangents for BOTH outputs, so downstream math that
    mixes o and lse — the ring-step log-sum-exp merge in
    :func:`ddl25spring_tpu.parallel.sp.ring_flash_attention` — back-
    propagates exactly.  KV length may differ from L only when
    ``causal=False`` (the causal finalize index assumes the square
    diagonal; rectangular-causal would silently never finalize, so it is
    rejected loudly)."""
    B, L, H, hd = q.shape
    Lk = k.shape[1]
    if causal and Lk != L:
        raise ValueError(
            f"causal flash requires square q/kv lengths, got L={L} Lk={Lk}"
        )
    if interpret is None:
        interpret = off_tpu("flash attention runs in Pallas interpret mode")
    bq, bk = _choose_block(L, block_q), _choose_block(Lk, block_k)

    def fold(x):
        n = x.shape[1]
        return x.transpose(0, 2, 1, 3).reshape(B * H, n, hd)

    o3, lse3 = _flash_lse(fold(q), fold(k), fold(v), bq, bk, causal, interpret)
    o = o3.reshape(B, H, L, hd).transpose(0, 2, 1, 3)
    return o, lse3.reshape(B, H, L)
