"""The ``qwen3next`` family: the hybrid decoder that ``model_type:
qwen3_next`` configures (``ddl25spring_tpu/models/qwen3_next.py``): gated
delta-rule layers that keep a fixed recurrent state a sequence, gated
grouped-query attention on every fourth layer, 512 routed experts of which a
position takes 10, one gated shared expert; served as ONE chip's share of a
deployment that divides each layer over several chips by expert parallelism.
Serving only: the training functions raise.

**The equations' source.** The keys of the configuration are those of the
published ``config.json`` (``full_attention_interval``, ``linear_*``,
``partial_rotary_factor``, ``num_experts``, ``num_experts_per_tok``,
``shared_expert_intermediate_size``, ``norm_topk_prob``), and the layer is
written as the architecture's public description and reference code define
it: the docstring of the model module gives it line by line, and
``benchmark/reference_qwen3next.py`` is the same in plain float32, its
recurrence token by token.  What no key settles is listed as ``assumed`` in
the configuration file, each with its reason.

**The weights** are drawn HERE from ``--seed`` (``init_params``), in the
served type, on the device, and handed to the program: the reference takes
nothing the program made.  Layout: the model module's.  Every matrix is
normal at ``fan_in^-0.5`` (the convolution's taps at ``kernel^-0.5``); the
zero-centred norm scales (``1 + w``) are zero and the gated norm's plain
scale one, as published; ``A_log = log A`` with ``A ~ U(0, 16)`` a value
head and ``dt_bias = 1``, the published initialisers: most heads forget
within a few tokens and a few hold for hundreds.

**``check_served``** holds the served requests to the reference's full
forward pass, and everything it reads is what the TIMED path produced: the
cell's engine runs with ``logit_probe`` set, so every pass (the prompt batch
of 8 rows x W positions through the chunked rule and the seated state; the
tick of 128 rows through the one-step kernel on the slot's state and through
the pages) hands the host, behind the tokens it sampled and in the same
fetch, 128 evenly strided logits of each row it sampled from.  (1) Every
served token's reference logit may lie only so far below the reference's
maximum there.  (2) The kept logits against the reference's at the same ids,
per served position, as ``|engine - reference|_2 / |reference - its
mean|_2``.  A position whose ``k``-th and ``k + 1``-th router logits lie
closer than ``NEAR_TIE_GAP`` in any layer (one of the two held here) is a
near-tie: bfloat16 may flip the choice there, which is no fault, so such
positions are counted, held to second limits, and their share printed (the
reasoning is PR 29's, ``families/mistral4.py``; at top-10 of 512 the gaps
are four times narrower and a flipped expert's weight a third as large).
"""

from __future__ import annotations

from functools import partial
from typing import Any

from benchmark import reference_qwen3next as reference

# ---------------------------------------------------------- the tolerances
# Each beside its reason.  The readings are my chip runs of PR 33 (PERF.md
# section 6), all on what the cell's engine kept of its own passes: 19 sound
# readings over 19 seeds (the cell's runs and the tool's), and
# benchmark/tools/qwen3next_tolerances.py on seeds 33002 and 33024 for the
# controls:
# the served requests held to a reference with 8-bit (3 mantissa bits)
# expert weights, an engine that SERVES such weights held to the true
# reference, a planted gross fault (a reference that lacks one linear
# layer's mixer), and a recurrent state rounded to bfloat16 (in the
# reference after every token; held so by the engine), which NO limit on
# logits tells from a sound run: it moves the median error by a tenth of
# what bfloat16 serving reads for every other reason.

# A position whose smallest router gap over the layers (10th logit less
# 11th, one of the two held here) lies under this is a near-tie.  bfloat16
# moves a router logit by ~1e-2, and at top-10 of 512 the 10th and 11th lie
# ~0.04 apart: 0.01 leaves about half of all positions on each side (read
# 0.52-0.56), so that both classes are always populated.
NEAR_TIE_GAP = 0.01
# MEDIAN relative logit error over the served positions with no near-tie.
# The engine in bfloat16 reads 0.055-0.067; the 8-bit controls read 0.154,
# 0.161 (reference rounded) and 0.156, 0.164 (engine rounded).  0.1 is the
# geometric middle: 1.50 times the largest sound reading, 1.54 times under
# the least control.  One of the two limits that refuse a lower precision.
# (A state in bfloat16 reads 0.058-0.061 in the reference, 0.062-0.064 in
# the engine: inside the sound readings.)
LOGIT_REL_ERR_P50 = 0.1
# MEAN over the served tokens of the reference's maximum less the served
# token's reference logit: what the lost precision cost in the tokens that
# went out.  Sound 0.014-0.022, the 8-bit controls 0.061-0.068, the planted
# fault 2.60-2.64.  0.037 is the geometric middle (1.67 times the largest
# sound reading, 1.64 times under the least control).  The other limit that
# refuses a lower precision, and it is on tokens, not logits.
SERVED_MARGIN_MEAN = 0.037
# LARGEST relative logit error at a position with no near-tie / at a
# near-tie, over the 128 kept ids.  Read 0.23-0.40 / 0.27-0.38: the flips of
# other positions, seen through attention and the recurrent state (the 8-bit
# controls read 0.31-0.37 / 0.30-0.35: a maximum tells no precision apart).
# These guard against gross faults: the planted fault reads 1.12 in the
# median and 1.42-1.50 / 1.41-1.50 at its worst.
LOGIT_REL_ERR = 0.7
LOGIT_REL_ERR_NEAR_TIE = 0.8
# Share of checked positions that are near-ties: a property of the seeded
# router (read 0.52-0.61), not of the program; far above that, the strict
# class would be too small for its median to mean anything.
NEAR_TIE_SHARE = 0.75
# The LARGEST such margin at a position with no near-tie / at a near-tie.
# Output logits are near N(0, 1) over 37,984 ids, so the maximum lies ~4.1
# above a token chosen for any other reason than the model's own scores.
# Read 0.28-0.89 / 0.40-0.94 (the 8-bit controls 0.53-0.82 / 0.65-0.83: the
# worst of a few hundred positions tells no precision apart either).  The
# limits guard against gross faults (PR 29's reckoning: the worst of a run's
# positions at a relative error of 0.3-0.4 reads ~0.9): above the readings by
# 2.2x / 3.2x, under the planted fault's 5.28-5.39 / 5.26-5.57.
SERVED_EPS = 2.0
SERVED_EPS_NEAR_TIE = 3.0


# ------------------------------------------------------------ the model


def widths(config: dict[str, Any], *, n_layers: int | None = None) -> dict:
    """The configuration's numbers under the program's field names,
    refusing what the program cannot state."""
    refusals = {
        "decoder_sparse_step": 1, "mlp_only_layers": [], "hidden_act": "silu",
        "rope_scaling": None, "tie_word_embeddings": False,
        "use_sliding_window": False,
    }
    for key, want in refusals.items():
        if config[key] != want:
            raise ValueError(
                f"{key}={config[key]!r}: models/qwen3_next.py states only {want!r}"
            )
    run = config.get("run", {})
    return dict(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_hidden_layers=(config["num_hidden_layers"] if n_layers is None
                           else n_layers),
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        linear_num_key_heads=config["linear_num_key_heads"],
        linear_num_value_heads=config["linear_num_value_heads"],
        linear_key_head_dim=config["linear_key_head_dim"],
        linear_value_head_dim=config["linear_value_head_dim"],
        linear_conv_kernel_dim=config["linear_conv_kernel_dim"],
        moe_intermediate_size=config["moe_intermediate_size"],
        shared_expert_intermediate_size=config["shared_expert_intermediate_size"],
        # the router keeps its published width; the key counts what is held
        num_experts=config["published"]["num_experts"],
        experts_held=config["num_experts"],
        expert_offset=config["deployment"]["expert_offset"],
        num_experts_per_tok=config["num_experts_per_tok"],
        full_attention_interval=config["full_attention_interval"],
        partial_rotary_factor=float(config["partial_rotary_factor"]),
        rope_theta=float(config["rope_theta"]),
        norm_topk_prob=config["norm_topk_prob"],
        rms_norm_eps=config["rms_norm_eps"],
        max_position_embeddings=config["max_position_embeddings"],
        dtype=run.get("dtype", "bfloat16"),
        state_dtype=run.get("state_dtype", "float32"),
    )


def build(config: dict[str, Any], *, n_layers: int | None = None,
          use_flash: bool | None = None):
    """``Qwen3NextConfig`` for ``config`` (the source's own key names)."""
    from ddl25spring_tpu.models.qwen3_next import Qwen3NextConfig

    del use_flash  # no flash kernel on this family's path
    return Qwen3NextConfig(**widths(config, n_layers=n_layers))


def _w(cfg) -> dict:
    """``cfg`` back as the plain dict the reference takes."""
    import dataclasses

    return dataclasses.asdict(cfg)


def _held(cfg) -> tuple[int, int]:
    return cfg.expert_offset, cfg.n_held


def init_params(cfg, seed: int):
    """Seeded weights on the device, in the model module's layout: matrices
    in ``cfg.dtype``, norm scales, ``A_log`` and ``dt_bias`` float32.  The
    three expert stacks are filled a layer at a time into a donated buffer
    by the chip's own bit generator (threefry takes a minute for the
    stacks' 3.2 G numbers)."""
    import jax
    import jax.numpy as jnp

    D, L, V = cfg.hidden_size, cfg.n_layers, cfg.vocab_size
    U, I, n_lin = cfg.n_units, cfg.full_attention_interval, cfg.n_linear
    H, KV, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    nv, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    K, C = cfg.linear_conv_kernel_dim, cfg.conv_channels
    F, Fs, E = (cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size,
                cfg.n_held)
    dtype = jnp.dtype(cfg.dtype)

    @partial(jax.jit, static_argnames=("shape", "fan_in"))
    def normal(key, *, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    @partial(jax.jit, static_argnames=("fan_in",), donate_argnums=(0,))
    def fill(stack, li, seed, *, fan_in):
        key = jax.random.fold_in(jax.random.key(seed, impl="rbg"), li)
        layer = (jax.random.normal(key, stack.shape[1:], jnp.float32)
                 * fan_in ** -0.5).astype(dtype)
        return stack.at[li].set(layer)

    lin = {"in_qkvz": ((U, D, C + nv * dv), D), "in_ba": ((U, D, 2 * nv), D),
           "conv_w": ((U, K, C), K), "out_proj": ((U, nv * dv, D), nv * dv)}
    full = {"wq": ((U, D, 2 * H * hd), D), "wk": ((U, D, KV * hd), D),
            "wv": ((U, D, KV * hd), D), "wo": ((U, H * hd, D), H * hd)}
    moe = {"router": ((U, D, cfg.num_experts), D), "ws_gate": ((U, D, Fs), D),
           "ws_up": ((U, D, Fs), D), "ws_down": ((U, Fs, D), Fs),
           "w_sg": ((U, D), D)}
    top = {"embed": ((V, D), D), "unembed": ((D, V), D)}
    stacks = {"w_gate": ((L, E, D, F), D), "w_up": ((L, E, D, F), D),
              "w_down": ((L, E, F, D), F)}
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))

    def draw(shapes: dict) -> dict:
        return {name: normal(next(keys), shape=shape, fan_in=fan_in)
                for name, (shape, fan_in) in shapes.items()}

    top = draw(top)
    blocks = {"lin": [draw(lin) for _ in range(n_lin)], "full": draw(full),
              "moe": [draw(moe) for _ in range(I)]}
    experts = {}
    for name, (shape, fan_in) in stacks.items():
        stack = jnp.zeros(shape, dtype)
        stack_seed = jax.random.bits(next(keys), (), jnp.uint32)
        for li in range(L):
            stack = fill(stack, li, stack_seed, fan_in=fan_in)
        experts[name] = stack

    def const(value, *shape):
        return jnp.full(shape, value, jnp.float32)

    decay = jax.random.uniform(next(keys), (n_lin, U, nv), jnp.float32, 1e-3, 16.0)
    for j, layer in enumerate(blocks["lin"]):
        layer.update(ln1=const(0.0, U, D), o_norm=const(1.0, U, dv),
                     A_log=jnp.log(decay[j]), dt_bias=const(1.0, U, nv))
    blocks["full"].update(
        ln1=const(0.0, U, D), q_norm=const(0.0, U, hd), k_norm=const(0.0, U, hd))
    for layer in blocks["moe"]:
        layer.update(ln2=const(0.0, U, D))
    return {**top, "blocks": blocks, "experts": experts, "ln_f": const(0.0, D)}


def vocab(cfg) -> int:
    """The slice: the traffic draws its ids from it, and the logits and
    the sampling are over it."""
    return cfg.vocab_size


def _serving_only(what: str):
    raise NotImplementedError(
        f"the qwen3next family is served only: {what} belongs to a training "
        "cell, and at 16 bytes a parameter a chip holds 32 of a layer's 512 "
        "experts (ISSUE 33); no training cell names this family"
    )


def init_staged_params(cfg, seed: int, stages: int):
    _serving_only("init_staged_params")


def seq_len(cfg) -> int:
    _serving_only("seq_len")


def reference_loss(cfg, params, tokens) -> float:
    _serving_only("reference_loss")


def check_train_loss(system_loss: float, reference_loss: float) -> dict:
    _serving_only("check_train_loss")


def train_flops_per_token(cfg) -> float:
    _serving_only("train_flops_per_token")


def flash_calls(cfg, batch: int) -> dict:
    _serving_only("flash_calls")


# ------------------------------------------------------------ the checks


def probe_ids(cfg, k: int):
    """The ids whose logits ``ServeEngine(logit_probe=k)`` keeps of every
    sampled row (``serve/engine.py`` ``_pack_pass``)."""
    import numpy as np

    return np.arange(k) * (cfg.vocab_size // k)


def compare(cfg, params, prompt, served, *, pad_to: int,
            reference_params=None, **reference_kw) -> dict:
    """One request against the reference: for every served token ``j`` (the
    reference's logits at position ``len(prompt) - 1 + j`` predict it) its
    margin, the relative error of the logits the ENGINE kept of the row it
    was sampled from (``served.probe``), and whether the position is a
    near-tie."""
    import numpy as np

    probe = np.asarray(getattr(served, "probe", ()), np.float32)
    if len(probe) != len(served) or not len(served):
        raise ValueError(
            f"{len(served)} served tokens with {len(probe)} probed rows: the "
            "qwen3next family checks the logits the engine's own passes "
            'computed; the cell\'s "engine" sets "logit_probe"'
        )
    seq = list(prompt) + list(served)
    if len(seq) > pad_to:
        raise ValueError(f"sequence of {len(seq)} tokens exceeds pad_to={pad_to}")
    tokens = np.asarray(seq + [0] * (pad_to - len(seq)), np.int32)
    ref, gap = reference.forward(
        params if reference_params is None else reference_params, tokens,
        _w(cfg), held=_held(cfg), **reference_kw,
    )
    at = len(prompt) - 1 + np.arange(len(served))
    ref, gap = np.asarray(ref)[at], np.asarray(gap)[at]
    kept = ref[:, probe_ids(cfg, probe.shape[1])]
    centre = ref.mean(axis=-1, keepdims=True)
    return {
        "margin": ref.max(axis=-1) - ref[np.arange(len(at)), np.asarray(served)],
        "rel_err": (np.linalg.norm(probe - kept, axis=-1)
                    / np.linalg.norm(kept - centre, axis=-1)),
        "near_tie": gap < NEAR_TIE_GAP,
    }


def check_served(cfg, params, done, *, pad_to: int, reference_params=None,
                 **reference_kw) -> dict:
    """Every request in ``done`` (``(prompt, tokens)`` pairs as the engine
    made them: ``tokens.probe`` holds what it kept of each sampled row) held
    to the reference: see the module's text.  ``reference_params`` and
    ``reference_kw`` (the controls) give the reference other weights than
    the program's, or ``skip_mixers`` / ``state_dtype``."""
    import numpy as np

    if not done:
        return {"ok": False, "tokens_checked": 0, "worst_margin": 0.0,
                "eps": SERVED_EPS}
    parts = [compare(cfg, params, p, s, pad_to=pad_to,
                     reference_params=reference_params, **reference_kw)
             for p, s in done]
    tie, rel, margin = (np.concatenate([c[k] for c in parts])
                        for k in ("near_tie", "rel_err", "margin"))

    def worst(values, mask) -> float:
        return float(values[mask].max()) if mask.any() else 0.0

    read = {
        "worst_margin": worst(margin, ~tie),
        "worst_margin_near_tie": worst(margin, tie),
        "margin_mean": float(margin.mean()),
        "logit_rel_err_p50": float(np.median(rel[~tie])) if (~tie).any() else 0.0,
        "logit_rel_err": worst(rel, ~tie),
        "logit_rel_err_near_tie": worst(rel, tie),
        "near_tie_share": float(tie.mean()),
    }
    limits = {
        "worst_margin": SERVED_EPS,
        "worst_margin_near_tie": SERVED_EPS_NEAR_TIE,
        "margin_mean": SERVED_MARGIN_MEAN,
        "logit_rel_err_p50": LOGIT_REL_ERR_P50,
        "logit_rel_err": LOGIT_REL_ERR,
        "logit_rel_err_near_tie": LOGIT_REL_ERR_NEAR_TIE,
        "near_tie_share": NEAR_TIE_SHARE,
    }
    ok = all(np.isfinite(read[k]) and read[k] <= limits[k] for k in limits)
    others = "; ".join(
        f"{k} {read[k]:.4g} <= {limits[k]}" for k in limits if k != "worst_margin"
    )
    return {
        "ok": bool(ok), "tokens_checked": int(len(margin)), **read,
        "limits": limits, "near_tie_gap": NEAR_TIE_GAP,
        "probe_ids": len(done[0][1].probe[0]),
        # the runner prints `worst_margin` beside `eps`: the other readings
        # that decided `ok` ride in the limit's text, each beside its own
        "eps": f"{SERVED_EPS} (no near-tie: gap >= {NEAR_TIE_GAP}); {others}",
    }


# ------------------------------------------------------------ the counts


def moe_gmm_flops_bytes(assignments_here: float, experts_hit: float, *,
                        hidden: int = 2048, width: int = 512,
                        bytes_per_el: int = 2) -> tuple[float, float]:
    """What the ALGORITHM needs for one layer's grouped expert products
    (gate, up and down over the held experts), given how many assignments
    reached held experts and how many of those experts were hit: two FLOPs
    a weight an assignment; every hit expert's three matrices read once,
    each assignment's row read once and its result written once.  The
    intermediate of width ``width`` need not leave the chip and is not
    counted, nor is padding, nor an expert read twice."""
    weights = 3.0 * hidden * width
    return (2.0 * weights * assignments_here,
            bytes_per_el * (weights * experts_hit + 2.0 * hidden * assignments_here))


def gdn_step_flops_bytes(live_slots: float, *, value_heads: int = 32,
                         key_heads: int = 16, key_dim: int = 128,
                         value_dim: int = 128, state_bytes: int = 4,
                         bytes_per_el: int = 2) -> tuple[float, float]:
    """What the ALGORITHM needs for one linear layer's state update of one
    tick, given how many slots are live: a live slot's ``value_heads`` states
    of ``key_dim x value_dim`` read once and written once, its ``q``, ``k``
    (a key head each), ``v`` read and ``o`` written in the served type, its
    two scalars a head; per state element one multiply for the decay and
    two FLOPs each for ``S^T k``, the rank-one update and ``S^T q``.  A dead
    slot needs nothing."""
    state = value_heads * key_dim * value_dim
    vectors = 2 * key_heads * key_dim + 2 * value_heads * value_dim
    return (7.0 * state * live_slots,
            live_slots * (2.0 * state * state_bytes + bytes_per_el * vectors
                          + 8.0 * value_heads))
