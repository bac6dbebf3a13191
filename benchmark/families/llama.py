"""The ``llama`` family: the dense LLaMA-style decoder of
``ddl25spring_tpu/models/llama.py`` (MHA, 4x SwiGLU, RMSNorm, RoPE base
10,000, untied head), the only model the harness knew until PR 28.

A configuration file names its family (``"family": "llama"``) and
``run.py`` hands this module to the runner as ``ctx["family"]``.  The
runners, the readers and the tools know a model only through these
functions, so another architecture lands as a new file beside this one
(and a configuration and cells that name it): it edits nothing here.

What a family file must offer:

- ``build(config, n_layers=, use_flash=)``: the program's configuration
  object from a parsed ``configs/<name>.json``, refusing what the program
  cannot state;
- ``init_params(cfg, seed)`` / ``init_staged_params(cfg, seed, stages)``:
  seeded weights, made on the device in one jitted call: whole for
  serving, split by pipeline stage for training;
- ``vocab(cfg)``: the traffic draws token ids from ``[1, vocab)`` (a model
  served on a slice of its vocabulary gives the slice);
- ``seq_len(cfg)``: the tokens of one training sequence;
- ``check_served(cfg, params, done, pad_to=)``: served requests against
  the plain reference -> ``ok``, ``tokens_checked``, ``worst_margin``,
  ``eps``;
- ``reference_loss(cfg, params, tokens)`` and ``check_train_loss(system,
  reference)`` -> ``ok``, ``rel``, ``rtol``: the training loss against the
  plain reference, in two calls because the step donates its parameters;
- ``train_flops_per_token(cfg)`` and ``flash_calls(cfg, batch)``: the
  operations and bytes the ALGORITHM needs, for ``mfu_pct.train`` and
  ``flash_roofline.train``.

The plain reference of this family, its equations and its two tolerances
with their reasons are ``benchmark/reference.py``.
"""

from __future__ import annotations

from typing import Any

from benchmark import reference

# ------------------------------------------------------------ the model


def build(config: dict[str, Any], *, n_layers: int | None = None,
          use_flash: bool | None = None):
    """``LlamaConfig`` for ``config`` (the source's own key names).

    ``LlamaConfig`` derives ``ffn_dim = 4 * dmodel`` and ``head_dim =
    dmodel // num_heads`` and has no KV-head count, so a source whose
    widths do not satisfy those is refused here rather than run at other
    widths under its name."""
    from ddl25spring_tpu.utils.config import LlamaConfig

    d, heads = config["hidden_size"], config["num_attention_heads"]
    if config["intermediate_size"] != 4 * d:
        raise ValueError("LlamaConfig fixes intermediate_size = 4 * hidden_size")
    if config.get("num_key_value_heads", heads) != heads:
        raise ValueError("LlamaConfig has no KV-head count (MHA only)")
    if config.get("rope_theta", 10000.0) != 10000.0:
        raise ValueError("models/llama.py fixes the RoPE base at 10,000")
    run = config.get("run", {})
    return LlamaConfig(
        vocab_size=config["vocab_size"], dmodel=d, num_heads=heads,
        n_layers=config["num_hidden_layers"] if n_layers is None else n_layers,
        ctx_size=config["max_position_embeddings"],
        dtype=run.get("dtype", "bfloat16"),
        use_flash=run.get("use_flash", False) if use_flash is None else use_flash,
    )


def init_params(cfg, seed: int):
    """The whole model: float32 masters, which the program casts to
    ``cfg.dtype`` at each use (its serving path, PERF.md section 7)."""
    import jax

    from ddl25spring_tpu.models import llama

    return jax.jit(lambda key: llama.init_llama_params(key, cfg))(
        jax.random.PRNGKey(seed)
    )


def init_staged_params(cfg, seed: int, stages: int):
    """The same weights with the layer stacks split ``[S, L/S, ...]``."""
    import jax

    from ddl25spring_tpu.models import llama

    return jax.jit(lambda key: llama.split_blocks_for_stages(
        llama.init_llama_params(key, cfg), stages
    ))(jax.random.PRNGKey(seed))


def vocab(cfg) -> int:
    return cfg.vocab_size


def seq_len(cfg) -> int:
    return cfg.ctx_size


# ------------------------------------------------------------ the checks


def check_served(cfg, params, done, *, pad_to: int) -> dict:
    """Every token of the requests in ``done`` (``(prompt, tokens)``
    pairs) held to the reference's logits on the same weights."""
    return reference.check_served(
        params, done, num_heads=cfg.num_heads, pad_to=pad_to
    )


def reference_loss(cfg, params, tokens) -> float:
    """The reference's loss on ``tokens`` at ``params`` (whole or staged)
    as they stand: taken BEFORE the step that donates them."""
    return float(reference.loss(
        reference.flat_blocks(params), tokens, num_heads=cfg.num_heads
    ))


def check_train_loss(system_loss: float, reference_loss: float) -> dict:
    rel = abs(system_loss - reference_loss) / abs(reference_loss)
    return {"ok": bool(rel <= reference.TRAIN_LOSS_RTOL),
            "system_loss": system_loss, "reference_loss": reference_loss,
            "rel": rel, "rtol": reference.TRAIN_LOSS_RTOL}


# ------------------------------------------------------------ the counts
# Model FLOPs, not XLA's cost analysis: casts, recomputation and padding
# do not count.


def matmul_params(dmodel: int, ffn_dim: int, n_layers: int, vocab: int) -> int:
    """Parameters that take part in a matrix multiplication for every
    token: four attention projections and three SwiGLU matrices a layer,
    and ``unembed``.  The ``embed`` table is a gather and the norm scales
    are elementwise: neither counts."""
    per_layer = 4 * dmodel * dmodel + 3 * dmodel * ffn_dim
    return n_layers * per_layer + dmodel * vocab


def flops_per_token(
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


def train_flops_per_token(cfg) -> float:
    return flops_per_token(
        cfg.dmodel, cfg.ffn_dim, cfg.n_layers, cfg.vocab_size, cfg.ctx_size
    )


def flash_calls(cfg, batch: int) -> dict:
    """The attention kernel's calls for one forward and backward pass of
    ``batch`` sequences: how many (one a layer), and the ``(FLOPs, bytes)``
    of one call in each direction."""
    def need(backward: bool):
        return flash_flops_bytes(
            batch, cfg.ctx_size, cfg.num_heads, cfg.head_dim, backward=backward
        )

    return {"calls": cfg.n_layers, "forward": need(False), "backward": need(True)}
