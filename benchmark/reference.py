"""The plain reference: a LLaMA-style decoder written from its layer
equations in straightforward ``jax.numpy``, float32, matmul precision
``highest``; no kernel, no cache, no scan, no import of ``models/llama.py``.

Equations (pre-norm decoder, as the configuration files state):

- RMSNorm: ``x / sqrt(mean(x^2) + 1e-5) * scale``;
- RoPE over INTERLEAVED pairs ``(x[2i], x[2i+1])`` rotated by
  ``pos * 10000^(-2i/head_dim)``;
- causal softmax attention over all heads (no grouped KV), scores scaled
  by ``1/sqrt(head_dim)``;
- SwiGLU: ``(silu(x Wg) * (x Wu)) Wd``;
- final RMSNorm, untied output head;
- loss: mean next-token cross-entropy over every position but the last.

It reads the parameter pytree in the layout the program stores it
(``embed [V,D]``, per-layer stacks ``[L, ...]``, ``ln_f``, ``unembed
[D,V]``) because the comparison has to run on the SAME weights.

``correct`` rests on the two checks at the bottom; each tolerance stands
beside its reason.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

RMS_EPS = 1e-5
ROPE_BASE = 10_000.0

# Train cells: |system loss - reference loss| / reference loss.  The system
# computes in bfloat16 (8 bits of mantissa: 2^-9 = 2e-3 a rounding) with the
# flash kernel, the reference in float32.  On the chip at the published
# widths the difference read 4e-5 to 2.8e-4 over two seeds and both warm-up
# steps (my chip runs, PR 25), so 2e-3 is seven times the largest seen and
# still one rounding of the type.  What it catches: a wrong mask, shift,
# normalisation or microbatch mean, and a loss that is not finite.  It does
# NOT tell 3 layers from 2 at seeded weights (random layers move the loss by
# less than that); depth and gradients stay pinned by tier-1 on the CPU in
# float32, where this check is exact.
TRAIN_LOSS_RTOL = 2e-3

# Serve cells: the reference logit of every served token may lie at most
# this far below the reference's own maximum at that position.  Logits at
# seeded init have a standard deviation near 0.9, so a token picked for any
# other reason than the model's own top choice (a wrong page, a dropped
# layer, a wrong position) is a draw from 50k logits and sits ~3.5 below the
# top.  bfloat16 serving against the float32 reference flips near-ties: the
# worst margin read 0.046 to 0.124 over three seeds of ~150 tokens each (my
# chip runs, PR 25); 0.5 is four times the largest seen and a seventh of
# what a wrong token shows.
SERVE_LOGIT_EPS = 0.5


def rms_norm(x, scale):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + RMS_EPS) * scale


def rope(x, positions):
    """``x [B, T, H, hd]``; rotate interleaved pairs by position."""
    hd = x.shape[-1]
    inv = ROPE_BASE ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]  # [T, hd/2]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1)
    return out.reshape(x.shape)


def block(p, x, num_heads):
    B, T, D = x.shape
    hd = D // num_heads
    h = rms_norm(x, p["ln1"])
    pos = jnp.arange(T)
    q = rope((h @ p["wq"]).reshape(B, T, num_heads, hd), pos)
    k = rope((h @ p["wk"]).reshape(B, T, num_heads, hd), pos)
    v = (h @ p["wv"]).reshape(B, T, num_heads, hd)
    scores = jnp.einsum("bthd,bshd->bhts", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    attn = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, axis=-1), v)
    x = x + attn.reshape(B, T, D) @ p["wo"]
    h = rms_norm(x, p["ln2"])
    return x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]


@partial(jax.jit, static_argnames=("num_heads", "skip_layers"))
def forward(params, tokens, *, num_heads: int, skip_layers: tuple = ()):
    """Logits ``[B, T, V]`` in float32.  ``skip_layers`` exists for the
    negative control of the tests (a dropped layer must fail the check)."""
    with jax.default_matmul_precision("highest"):
        f32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x = f32["embed"][tokens]
        n_layers = f32["blocks"]["wq"].shape[0]
        for i in range(n_layers):
            if i in skip_layers:
                continue
            x = block(jax.tree.map(lambda a: a[i], f32["blocks"]), x, num_heads)
        return rms_norm(x, f32["ln_f"]) @ f32["unembed"]


@partial(jax.jit, static_argnames=("num_heads",))
def loss(params, tokens, *, num_heads: int) -> jax.Array:
    """Mean next-token cross-entropy of ``tokens [B, T]``."""
    logits = forward(params, tokens, num_heads=num_heads)[:, :-1]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)


def flat_blocks(params):
    """Undo a pipeline split ``[S, L/S, ...] -> [L, ...]`` (a reshape of
    the stored stacks; the arithmetic above never sees stages)."""
    wq = params["blocks"]["wq"]
    if wq.ndim == 3:
        return params
    out = dict(params)
    out["blocks"] = jax.tree.map(
        lambda a: a.reshape((-1,) + a.shape[2:]), params["blocks"]
    )
    return out


# ----------------------------------------------------------- the checks


def check_train_loss(system_loss: float, params, tokens, *, num_heads: int,
                     rtol: float = TRAIN_LOSS_RTOL) -> dict:
    """The system's loss on ``tokens`` against the reference's on the same
    parameters."""
    ref = float(loss(flat_blocks(params), tokens, num_heads=num_heads))
    rel = abs(system_loss - ref) / abs(ref)
    return {"ok": bool(rel <= rtol), "system_loss": system_loss,
            "reference_loss": ref, "rel": rel, "rtol": rtol}


@partial(jax.jit, static_argnames=("num_heads", "skip_layers"))
def _margins(params, tokens, *, num_heads: int, skip_layers: tuple = ()):
    """For every position ``t`` of ``tokens [1, T]``: the reference's
    maximum logit at ``t`` less its logit of token ``t + 1``."""
    logits = forward(params, tokens, num_heads=num_heads, skip_layers=skip_layers)[0]
    nxt = jnp.roll(tokens[0], -1)
    return logits.max(axis=-1) - jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]


def served_margins(params, prompt, served, *, num_heads: int, pad_to: int,
                   skip_layers: tuple = ()) -> list[float]:
    """For each served token, how far its reference logit lies below the
    reference's maximum at that position (0 = the reference's own argmax),
    teacher-forced on ``prompt + served``.  Padded to ``pad_to`` so that
    every request shares ONE compiled program whatever its lengths; padding
    follows the real tokens, and the model is causal, so it changes
    nothing."""
    seq = list(prompt) + list(served)
    n = len(seq)
    if n > pad_to:
        raise ValueError(f"sequence of {n} tokens exceeds pad_to={pad_to}")
    toks = jnp.asarray([seq + [0] * (pad_to - n)], jnp.int32)
    margins = np.asarray(_margins(
        params, toks, num_heads=num_heads, skip_layers=skip_layers
    ))
    # the logits at position t predict token t + 1
    return [float(m) for m in margins[len(prompt) - 1:n - 1]]


def check_served(params, done, *, num_heads: int, pad_to: int,
                 eps: float = SERVE_LOGIT_EPS, skip_layers: tuple = ()) -> dict:
    """Hold every token of the requests in ``done`` (``(prompt, tokens)``
    pairs) to the reference's logits."""
    worst, n = 0.0, 0
    for prompt, served in done:
        margins = served_margins(
            params, prompt, served, num_heads=num_heads, pad_to=pad_to,
            skip_layers=skip_layers,
        )
        worst, n = max([worst, *margins]), n + len(margins)
    return {"ok": bool(n > 0 and worst <= eps), "tokens_checked": n,
            "worst_margin": worst, "eps": eps}
