"""A second model family, for the benchmark's tests only: the same tiny
decoder reached through DIFFERENTLY NAMED configuration keys (``width``,
``heads``, ``depth``, ``tokens``, ``context``) and held to a plain
reference of its own, written apart from ``benchmark/reference.py`` (one
sequence at a time, RoPE as a complex rotation, float32 throughout).

``tests/test_harness.py`` copies this file into a temporary benchmark next
to a configuration and two cells that name it, and nothing that is there
is edited: what a PR that adds an architecture does.  A copy with
``DROP_LAYERS`` set is the negative control: its reference skips a layer
the program runs, and ``correct`` has to come out false.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

DROP_LAYERS: tuple = ()
SERVE_EPS = 1e-3    # float32 against float32: reduction order only
TRAIN_RTOL = 1e-4


def build(config, *, n_layers=None, use_flash=None):
    from ddl25spring_tpu.utils.config import LlamaConfig

    return LlamaConfig(
        vocab_size=config["tokens"], dmodel=config["width"],
        num_heads=config["heads"], ctx_size=config["context"],
        n_layers=config["depth"] if n_layers is None else n_layers,
        dtype="float32", use_flash=False,
    )


def init_params(cfg, seed: int):
    from ddl25spring_tpu.models import llama

    return jax.jit(lambda key: llama.init_llama_params(key, cfg))(
        jax.random.PRNGKey(seed)
    )


def init_staged_params(cfg, seed: int, stages: int):
    from ddl25spring_tpu.models import llama

    return llama.split_blocks_for_stages(init_params(cfg, seed), stages)


def vocab(cfg) -> int:
    return cfg.vocab_size


def seq_len(cfg) -> int:
    return cfg.ctx_size


# -------------------------------------------------- the plain reference


def _norm(x, gain):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-5) * gain


def _rotate(x):
    """``x [T, H, hd]``: each pair ``(x[2i], x[2i+1])`` as one complex
    number, turned by ``t * 10000^(-2i/hd)``."""
    T, H, hd = x.shape
    freq = 10000.0 ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    turn = jnp.exp(1j * jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :])
    z = (x[..., 0::2] + 1j * x[..., 1::2]) * turn[:, None, :]
    return jnp.stack([z.real, z.imag], axis=-1).reshape(T, H, hd)


def logits(params, tokens, heads: int):
    """``tokens [T]`` -> ``[T, V]``; ``params`` whole or split by stage."""
    blocks = params["blocks"]
    if blocks["wq"].ndim == 4:  # [S, L/S, ...] -> [L, ...]
        blocks = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), blocks)
    x = params["embed"][tokens].astype(jnp.float32)
    T, D = x.shape
    visible = jnp.tril(jnp.ones((T, T), bool))
    for i in range(blocks["wq"].shape[0]):
        if i in DROP_LAYERS:
            continue
        p = jax.tree.map(lambda a: a[i], blocks)
        h = _norm(x, p["ln1"])
        q, k, v = ((h @ p[w]).reshape(T, heads, D // heads) for w in ("wq", "wk", "wv"))
        score = jnp.einsum("thd,shd->hts", _rotate(q), _rotate(k)) / math.sqrt(D // heads)
        weight = jax.nn.softmax(jnp.where(visible, score, -jnp.inf), axis=-1)
        x = x + jnp.einsum("hts,shd->thd", weight, v).reshape(T, D) @ p["wo"]
        h = _norm(x, p["ln2"])
        x = x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]
    return _norm(x, params["ln_f"]) @ params["unembed"]


def check_served(cfg, params, done, *, pad_to: int) -> dict:
    worst, n = 0.0, 0
    for prompt, served in done:
        seq = jnp.asarray(list(prompt) + list(served), jnp.int32)
        lg = logits(params, seq, cfg.num_heads)[len(prompt) - 1:-1]
        gap = lg.max(axis=-1) - lg[jnp.arange(len(served)), seq[len(prompt):]]
        worst, n = max(worst, float(gap.max())), n + len(served)
    return {"ok": bool(n > 0 and worst <= SERVE_EPS), "tokens_checked": n,
            "worst_margin": worst, "eps": SERVE_EPS}


def reference_loss(cfg, params, tokens) -> float:
    total = 0.0
    for row in tokens:
        lg = logits(params, row, cfg.num_heads)[:-1]
        logp = jax.nn.log_softmax(lg, axis=-1)
        total += float(-logp[jnp.arange(len(row) - 1), row[1:]].mean())
    return total / len(tokens)


def check_train_loss(system_loss: float, reference_loss: float) -> dict:
    rel = abs(system_loss - reference_loss) / abs(reference_loss)
    return {"ok": bool(rel <= TRAIN_RTOL), "system_loss": system_loss,
            "reference_loss": reference_loss, "rel": rel, "rtol": TRAIN_RTOL}


# ------------------------------------------------------------ the counts


def train_flops_per_token(cfg) -> float:
    d, layers = cfg.dmodel, cfg.n_layers
    matmul = layers * 16 * d * d + d * cfg.vocab_size  # 4 d^2 + 3 d (4 d) a layer
    return 6.0 * matmul + 6.0 * layers * cfg.ctx_size * d


def flash_calls(cfg, batch: int) -> dict:
    half = 2.0 * cfg.ctx_size ** 2 * cfg.dmodel * batch  # QK^T and PV, masked
    moved = 2.0 * batch * cfg.ctx_size * cfg.dmodel
    return {"calls": cfg.n_layers, "forward": (half, 4 * moved),
            "backward": (2.5 * half, 8 * moved)}
