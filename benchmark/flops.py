"""Operations and bytes the ALGORITHM needs, computed from shapes.

Model FLOPs, not XLA's cost analysis: casts, recomputation and padding do
not count.  ``peaks.json`` holds the chip's published peaks; a
``device_kind`` that is not in it is an error, never a default.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def load_peaks(device_kind: str, path: str | None = None) -> dict:
    with open(path or os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"device_kind {device_kind!r} is not in peaks.json "
            f"({sorted(table)}): add it with its source, do not guess"
        )
    return table[device_kind]


def matmul_params(dmodel: int, ffn_dim: int, n_layers: int, vocab: int) -> int:
    """Parameters that take part in a matrix multiplication for every
    token: four attention projections and three SwiGLU matrices a layer,
    and ``unembed``.  The ``embed`` table is a gather and the norm scales
    are elementwise: neither counts."""
    per_layer = 4 * dmodel * dmodel + 3 * dmodel * ffn_dim
    return n_layers * per_layer + dmodel * vocab


def train_flops_per_token(
    dmodel: int, ffn_dim: int, n_layers: int, vocab: int, ctx: int
) -> float:
    """Forward + backward FLOPs of one token of a causal LM trained at
    context ``ctx``: ``6 x`` matmul parameters (2 forward, 4 backward),
    plus causal attention.  Attention forward is two matmuls (QK^T, PV)
    of ``2 * ctx * dmodel`` FLOPs a token each, halved by the causal
    mask: ``2 * ctx * dmodel`` a layer; backward is twice that."""
    attn = 6.0 * n_layers * ctx * dmodel
    return 6.0 * matmul_params(dmodel, ffn_dim, n_layers, vocab) + attn


def flash_flops_bytes(
    batch: int, ctx: int, heads: int, head_dim: int, *, backward: bool,
    bytes_per_el: int = 2,
) -> tuple[float, float]:
    """What causal flash attention over ``[batch, ctx, heads, head_dim]``
    needs.  Forward: QK^T and PV, ``4 * ctx^2 * head_dim`` FLOPs a head,
    halved by the mask; reads q, k, v and writes o once.  Backward (the
    dq and dkv kernels together): five matmuls of that size (S, dP, dV,
    dK, dQ; the recomputed S counted once, as the algorithm needs it),
    halved; reads q, k, v, o, do and writes dq, dk, dv once."""
    per_head = 4.0 * ctx * ctx * head_dim * 0.5
    tensor = float(batch * ctx * heads * head_dim * bytes_per_el)
    if backward:
        return 2.5 * per_head * batch * heads, 8.0 * tensor
    return per_head * batch * heads, 4.0 * tensor


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")
