"""The ``zaya`` family: the decoder that ``model_type: zaya`` configures
(``ddl25spring_tpu/models/zaya.py``): compressed convolutional attention
(two causal convolutions over the query/key latents, half the values from
the previous token: a slot of state beside 1 kB pages in EVERY layer), a
float32 MLP router that hands its state to the next layer's, 16 routed
experts of which a position takes ONE, and one table that embeds and
unembeds over all 262,272 ids; served as the first of two pipeline stages.
Serving only: the training functions raise.

**The equations' source.** The keys of the configuration are those of the
published ``config.json`` (``cca_time0``, ``cca_time1``,
``router_hidden_size``, ``partial_rotary_factor``, ``rope_parameters``,
``num_experts``, ``num_experts_per_tok``, ``tie_word_embeddings``), and the
layer is written from them, the catalog's ``described_as`` and the public
descriptions of CCA and of the ZAYA1 router as far as known: the docstring
of the model module gives it line by line, and
``benchmark/reference_zaya.py`` is the same in plain float32 with explicit
shifts.  What no key settles is listed as ``assumed`` in the configuration
file, each with its reason.

**The weights** are drawn HERE from ``--seed`` (``init_params``), in the
served type, on the device, and handed to the program: the reference takes
nothing the program made.  Layout: the model module's.  Every matrix is
normal at ``fan_in^-0.5``; the convolutions' taps likewise (``fan_in`` =
taps x inputs a channel: 2 depthwise, 256 grouped) with the NEWEST tap's
diagonal at 1, so that a convolution is neither a no-op nor a blow-up;
every bias and the residual scales ``a``, ``b`` (``1 + 0.1 n``) are seeded
off their neutral values so that a test can tell them from none; ``gamma =
0.5 + 0.1 n``; norm scales and ``tau`` one, the balancing bias zero; the
router's LAST matrix at ``ROUTER_OUT_GAIN`` times ``fan_in^-0.5``, so that
the seeded router is as decisive as a trained top-1 router.  The router's
leaves, the scales and the biases are float32.

**``check_served``** holds the served requests to the reference's full
forward pass, and everything it reads is what the TIMED path produced: the
cell's engine runs with ``logit_probe`` set, so every pass (the prompt pass
through both convolutions and the seated state; the tick of 64 rows through
the slot's state and the pages) hands the host, behind the tokens it
sampled and in the same fetch, 128 evenly strided logits of each row it
sampled from.  (1) Every served token's reference logit may lie only so far
below the reference's maximum there.  (2) The kept logits against the
reference's at the same ids, per served position, as ``|engine -
reference|_2 / |reference - its mean|_2``.  TOP-1 ROUTING makes a rounding
flip of the router swap a token's WHOLE expert, so the reference reports, a
position, the smallest gap between the two largest ``p + bias`` over the
layers; a position whose gap lies under ``NEAR_TIE_GAP`` is a near-tie:
such positions are counted, held to second limits, and their share
bounded.  The served path's routing is NOT fed to the reference.  (3) THE
PRECISION.  Greedy decoding of seeded weights settles on one id repeated, so
a near-tie recurs along a whole sequence, its flip is seen by every later
position through attention, and the four checked sequences' error moves
threefold from seed to seed: as much as 8-bit experts move it.  No limit on
the error itself tells the two apart.  So the reference states the SAME
model twice more, on the same requests, at the next lower precision (its
experts' weights at 3 mantissa bits; its router and q/k normalisation in
bfloat16: ``LOWER``), and the check counts the positions whose kept logits
lie NEARER to a lower statement than to the reference: what the flips do
to the served logits they do to both distances, what the precision does
only to one.  A reference that is itself at the lower precision makes the
two passes one and reads 1.
"""

from __future__ import annotations

from functools import partial
from typing import Any

from benchmark import reference_zaya as reference

# ---------------------------------------------------------- the tolerances
# Each beside its reason.  The readings are my chip runs of PR 36 (PERF.md
# section 6), all on what the cell's engine kept of its own passes: runs of
# the cell (1,132 tokens of four requests each) and
# benchmark/tools/zaya_tolerances.py (548 tokens: the first four requests a
# full engine completes are its shortest), with its controls: a planted
# gross fault (a reference that lacks one layer's CCA), which every limit on
# the error itself refuses by a wide margin; and the nearest precisions
# below the stated ones (8-bit experts in the reference or served by the
# engine; the router and q/k normalisation in bfloat16 in the reference or
# run by the engine), which NO limit on the error itself tells from a sound
# run (a sound run's median moves 0.013-0.040 from seed to seed, 8-bit
# experts read 0.029-0.056) and `NEARER_SHARE` does.  So the limits on the
# error guard the ARITHMETIC (a dropped or mis-ordered step, a mis-seated
# state, a stale convolution tail, a dead slot written, a wrong carry: each
# reads like the planted fault) with room for fresh seeds, which read
# higher, and the limits on the shares guard the PRECISION.

# A position whose smallest top-2 gap of the router's p + bias over the 20
# layers lies under this is a near-tie.  The bfloat16 residual stream moves
# p by ~1e-3 at the deeper layers; the seeded router's gaps have their first
# decile at 0.01-0.025, so 0.004 leaves 28-40 % of all positions on the
# near-tie side (read 0.283-0.398) and both classes are always populated.
NEAR_TIE_GAP = 0.004
# MEDIAN relative logit error over the served positions with no near-tie.
# Sound 0.0125-0.0403 over 42 readings (the 8-bit controls 0.0292-0.0579,
# the bfloat16 router 0.0146-0.0444: inside or beside the sound range); the
# planted fault 1.06-1.23.  0.12 is three times the largest sound reading
# and nine times under the fault.
LOGIT_REL_ERR_P50 = 0.12
# MEAN over the served tokens of the reference's maximum less the served
# token's reference logit: what was lost in the tokens that went out.  Sound
# 0.0013-0.0107 (the controls 0.0019-0.0181), the planted fault 2.18-3.54.
# 0.03 is 2.8 times the largest sound reading.
SERVED_MARGIN_MEAN = 0.03
# LARGEST relative logit error at a position with no near-tie / at a
# near-tie, over the 128 kept ids.  Read 0.143-0.260 / 0.136-0.287: flipped
# choices of other positions and layers, seen through attention (the
# controls read 0.152-0.282 / 0.141-0.333: a maximum tells no precision
# apart); the planted fault 1.20-1.46 / 1.18-1.44.
LOGIT_REL_ERR = 0.6
LOGIT_REL_ERR_NEAR_TIE = 0.7
# Share of checked positions that are near-ties: a property of the seeded
# router (read 0.283-0.414; an engine whose router runs in bfloat16 0.412),
# not of the program; far above that, the strict class would be too small
# for its median to mean anything.
NEAR_TIE_SHARE = 0.7
# The LARGEST such margin at a position with no near-tie / at a near-tie.
# Output logits are near N(0, 1) over 262,272 ids, so the maximum lies ~4.6
# above a token chosen for any other reason than the model's own scores.
# Read 0.072-0.474 / 0.116-0.780 (the controls 0.085-0.711 / 0.130-0.802);
# the planted fault 4.48-6.52 / 4.24-6.20.
SERVED_EPS = 2.0
SERVED_EPS_NEAR_TIE = 3.0
# The SAME model stated at the next lower precision, twice: its experts'
# weights rounded to e4m3's 3 mantissa bits as they are read; its router and
# q/k normalisation in bfloat16.  The reference computes each beside its own
# pass, on the same requests, and the check counts, over the positions that
# are near-ties in neither pass, the share whose kept logits lie NEARER to
# the lower statement than to the reference (or as near: a reference that is
# itself at the lower precision makes the two passes one, and reads 1).
# 8-bit experts: a sound engine reads 0.015-0.054 in seven runs of the cell
# and 0.017-0.089 in the tool on six seeds (the final tree, chip call 10; the
# ten sets of four requests of call 7's dump, held to this rule offline,
# 0.011-0.089); an engine that SERVES 8-bit experts 0.918-0.988; the
# reference at 8 bits 1.  The limit is a half: nearer the lower statement at
# most positions; 5.6 times the largest sound reading, 0.54 of the smallest
# control.
# bfloat16 router: a sound engine reads 0.179-0.492 (the cell) and
# 0.084-0.416 (the tool): the two statements differ by WHICH near-ties turn,
# and a sound engine turns some of the same; the reference in bfloat16 1; an
# engine whose router RUNS in bfloat16 0.268-0.729, no nearer than a sound
# one, because rounding turns other choices in it than in the reference's
# bfloat16 pass: that control is NOT told apart (PERF.md section 6, PR 36).
# The limit refuses a reference at the lower precision and an engine that
# reproduces it, and leaves the sound runs 1.7 times their largest reading.
LOWER = {"8_bit_experts": {"expert_bits": 3}, "bf16_router": {"high_prec": "bfloat16"}}
NEARER_SHARE = {"8_bit_experts": 0.5, "bf16_router": 0.85}


# ------------------------------------------------------------ the model

# The router's last matrix is seeded at this many times fan_in^-0.5: an
# ASSUMPTION OF THE CHECK, with no public source, and no claim about trained
# routers.  At 1 a seeded router's softmax over 16 is nearly flat (the chosen
# expert's weight reads 0.06-0.09, every position's top two lie within 0.01
# of one another in some layer, and the expert step is a twentieth of the
# residual stream: my chip run and CPU readings at the published widths, PR
# 36), so nothing the experts do shows in the logits; at 4 the chosen weight
# reads 0.19-0.65 (median 0.26-0.36) and the experts are a part of the logits
# that the check of `correct` can see.  An argmax does not move with the
# gain, so the experts hit do not either: the seeded router is NOT balanced
# (74-77 % of the experts hit a tick, one expert with ~6 times the mean
# load), because greedy decoding of seeded weights settles on one id
# repeated (a served sequence of 128-201 tokens holds 1-22 distinct ids: my
# chip run, PR 36) and such rows route alike; the cell's `why` says so.
ROUTER_OUT_GAIN = 4.0


def widths(config: dict[str, Any], *, n_layers: int | None = None) -> dict:
    """The configuration's numbers under the program's field names,
    refusing what the program cannot state."""
    refusals = {
        "model_type": "zaya", "hidden_act": "silu", "attention_bias": False,
        "lm_head_bias": False, "tie_word_embeddings": True,
        "sliding_window": None, "num_experts_per_tok": 1,
    }
    for key, want in refusals.items():
        if config[key] != want:
            raise ValueError(
                f"{key}={config[key]!r}: models/zaya.py states only {want!r}"
            )
    L = config["num_hidden_layers"] if n_layers is None else n_layers
    kinds = set(config["layer_types"][:L])
    if kinds != {"hybrid"}:
        raise ValueError(f"layer_types {sorted(kinds)}: every layer is 'hybrid'")
    rope = config["rope_parameters"]["hybrid"]
    if rope["rope_type"] != "default":
        raise ValueError(f"rope_type={rope['rope_type']!r}: only 'default'")
    run = config.get("run", {})
    deployment = config["deployment"]
    return dict(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_hidden_layers=L,
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_experts=config["num_experts"],
        experts_held=deployment["experts_held"],
        expert_offset=deployment["expert_offset"],
        num_experts_per_tok=config["num_experts_per_tok"],
        router_hidden_size=config["router_hidden_size"],
        cca_time0=config["cca_time0"],
        cca_time1=config["cca_time1"],
        partial_rotary_factor=float(rope["partial_rotary_factor"]),
        rope_theta=float(rope["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"],
        max_position_embeddings=config["max_position_embeddings"],
        dtype=run.get("dtype", "bfloat16"),
        high_prec=run.get("high_prec", "float32"),
    )


def build(config: dict[str, Any], *, n_layers: int | None = None,
          use_flash: bool | None = None):
    """``ZayaConfig`` for ``config`` (the source's own key names)."""
    from ddl25spring_tpu.models.zaya import ZayaConfig

    del use_flash  # no flash kernel on this family's path
    return ZayaConfig(**widths(config, n_layers=n_layers))


def _w(cfg) -> dict:
    """``cfg`` back as the plain dict the reference takes."""
    import dataclasses

    return dataclasses.asdict(cfg)


def _held(cfg) -> tuple[int, int]:
    return cfg.expert_offset, cfg.n_held


def init_params(cfg, seed: int):
    """Seeded weights on the device, in the model module's layout: matrices
    in ``cfg.dtype``; norm scales, residual scales, biases, ``tau`` and the
    router float32.  The three expert stacks are filled a layer at a time
    into a donated buffer, and the table drawn, by the chip's own bit
    generator (threefry takes a minute for their 3 G numbers)."""
    import jax
    import jax.numpy as jnp

    D, L, V = cfg.hidden_size, cfg.n_layers, cfg.vocab_size
    H, KV, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    C, hv = cfg.conv_channels, cfg.value_half
    k0, k1 = cfg.cca_time0, cfg.cca_time1
    F, E, Er, R = (cfg.moe_intermediate_size, cfg.n_held, cfg.num_experts,
                   cfg.router_hidden_size)
    dtype = jnp.dtype(cfg.dtype)
    f32 = jnp.float32

    @partial(jax.jit, static_argnames=("shape", "fan_in", "kind"))
    def normal(key, *, shape, fan_in, kind=dtype):
        return (jax.random.normal(key, shape, f32) * fan_in ** -0.5).astype(kind)

    @partial(jax.jit, static_argnames=("fan_in",), donate_argnums=(0,))
    def fill(stack, li, seed, *, fan_in):
        key = jax.random.fold_in(jax.random.key(seed, impl="rbg"), li)
        layer = (jax.random.normal(key, stack.shape[1:], f32)
                 * fan_in ** -0.5).astype(dtype)
        return stack.at[li].set(layer)

    matrices = {"wq": ((L, D, H * hd), D), "wk": ((L, D, KV * hd), D),
                "wv1": ((L, D, hv), D), "wv2": ((L, D, hv), D),
                "wo": ((L, H * hd, D), H * hd),
                "conv_dw": ((L, k0, C), k0),
                "conv_g": ((L, k1, H + KV, hd, hd), k1 * hd)}
    router = {"r_down": ((L, D, R), D), "r_w1": ((L, R, R), R),
              "r_w2": ((L, R, R), R),
              "r_w3": ((L, R, Er), R / ROUTER_OUT_GAIN ** 2)}
    # seeded around `centre` at a tenth: told from none by any test
    near = {"a1": ((L, D), 1.0), "b1": ((L, D), 1.0), "a2": ((L, D), 1.0),
            "b2": ((L, D), 1.0), "r_gamma": ((L, R), 0.5),
            "conv_dw_b": ((L, C), 0.0), "conv_g_b": ((L, C), 0.0),
            "r_down_b": ((L, R), 0.0), "r_b1": ((L, R), 0.0),
            "r_b2": ((L, R), 0.0), "r_b3": ((L, Er), 0.0)}
    stacks = {"w_gate": ((L, E, D, F), D), "w_up": ((L, E, D, F), D),
              "w_down": ((L, E, F, D), F)}
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))

    blocks = {name: normal(next(keys), shape=shape, fan_in=fan_in)
              for name, (shape, fan_in) in matrices.items()}
    blocks.update({name: normal(next(keys), shape=shape, fan_in=fan_in, kind=f32)
                   for name, (shape, fan_in) in router.items()})
    blocks.update({name: centre + normal(next(keys), shape=shape, fan_in=100, kind=f32)
                   for name, (shape, centre) in near.items()})
    # the newest tap passes its own channel through at 1
    blocks["conv_dw"] = blocks["conv_dw"].at[:, -1].set(1.0)
    diag = jnp.arange(hd)
    blocks["conv_g"] = blocks["conv_g"].at[:, -1, :, diag, diag].set(1.0)
    blocks.update(
        ln1=jnp.ones((L, D), f32), ln2=jnp.ones((L, D), f32),
        r_ln=jnp.ones((L, R), f32), tau=jnp.ones((L, KV), f32),
        r_bias=jnp.zeros((L, Er), f32),
    )
    experts = {}
    for name, (shape, fan_in) in stacks.items():
        stack = jnp.zeros(shape, dtype)
        stack_seed = jax.random.bits(next(keys), (), jnp.uint32)
        for li in range(L):
            stack = fill(stack, li, stack_seed, fan_in=fan_in)
        experts[name] = stack
    table_key = jax.random.key(jax.random.bits(next(keys), (), jnp.uint32),
                               impl="rbg")
    return {"embed": normal(table_key, shape=(V, D), fan_in=D),
            "blocks": blocks, "experts": experts, "ln_f": jnp.ones((D,), f32)}


def vocab(cfg) -> int:
    """The whole vocabulary: the traffic draws its ids from it, and the
    logits and the sampling are over it."""
    return cfg.vocab_size


def _serving_only(what: str):
    raise NotImplementedError(
        f"the zaya family is served only: {what} belongs to a training cell; "
        "at 16 bytes a parameter the tied head would be three quarters of a "
        "chip's matmul work and the trainer has no expert layer without "
        "drops (ISSUE 36); no training cell names this family"
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
    was sampled from (``served.probe``), whether the position is a
    near-tie, and for each statement of the model at the next lower
    precision (``LOWER``) whether the kept logits lie NEARER to that
    statement than to the reference, with whether the position is a
    near-tie in either."""
    import numpy as np

    probe = np.asarray(getattr(served, "probe", ()), np.float32)
    if len(probe) != len(served) or not len(served):
        raise ValueError(
            f"{len(served)} served tokens with {len(probe)} probed rows: the "
            "zaya family checks the logits the engine's own passes "
            'computed; the cell\'s "engine" sets "logit_probe"'
        )
    seq = list(prompt) + list(served)
    if len(seq) > pad_to:
        raise ValueError(f"sequence of {len(seq)} tokens exceeds pad_to={pad_to}")
    tokens = np.asarray(seq + [0] * (pad_to - len(seq)), np.int32)
    ref_params = params if reference_params is None else reference_params
    at = len(prompt) - 1 + np.arange(len(served))
    ids = probe_ids(cfg, probe.shape[1])

    def stated(**how):
        """``(h, kept, gap)`` at the served positions of one pass."""
        h, gap, _ = reference.forward(ref_params, tokens, _w(cfg), held=_held(cfg),
                                      **{**reference_kw, **how})
        kept = reference.head_at(ref_params["embed"], h[at], ids)
        return h[at], np.asarray(kept), np.asarray(gap)[at]

    h, kept, gap = stated()
    _, top, mean, at_served = (np.asarray(a) for a in reference.head(
        ref_params["embed"], h, ids, np.asarray(served, np.int32)))
    off = np.linalg.norm(probe - kept, axis=-1)
    out = {"margin": top - at_served,
           "rel_err": off / np.linalg.norm(kept - mean[:, None], axis=-1),
           "near_tie": gap < NEAR_TIE_GAP}
    for name, how in LOWER.items():
        _, kept_low, gap_low = stated(**how)
        out[f"nearer_{name}"] = np.linalg.norm(probe - kept_low, axis=-1) <= off
        out[f"near_tie_{name}"] = np.minimum(gap, gap_low) < NEAR_TIE_GAP
    return out


def check_served(cfg, params, done, *, pad_to: int, reference_params=None,
                 **reference_kw) -> dict:
    """Every request in ``done`` (``(prompt, tokens)`` pairs as the engine
    made them: ``tokens.probe`` holds what it kept of each sampled row) held
    to the reference: see the module's text.  ``reference_params`` and
    ``reference_kw`` (the controls) give the reference other weights than
    the program's, or ``high_prec`` / ``expert_bits`` / ``skip_attention``."""
    import numpy as np

    if not done:
        return {"ok": False, "tokens_checked": 0, "worst_margin": 0.0,
                "eps": SERVED_EPS}
    parts = [compare(cfg, params, p, s, pad_to=pad_to,
                     reference_params=reference_params, **reference_kw)
             for p, s in done]
    got = {k: np.concatenate([c[k] for c in parts]) for k in parts[0]}
    tie, rel, margin = got["near_tie"], got["rel_err"], got["margin"]

    def worst(values, mask) -> float:
        return float(values[mask].max()) if mask.any() else 0.0

    def share(values, mask) -> float:
        return float(values[mask].mean()) if mask.any() else 1.0

    read = {
        "worst_margin": worst(margin, ~tie),
        "worst_margin_near_tie": worst(margin, tie),
        "margin_mean": float(margin.mean()),
        "logit_rel_err_p50": float(np.median(rel[~tie])) if (~tie).any() else 0.0,
        "logit_rel_err": worst(rel, ~tie),
        "logit_rel_err_near_tie": worst(rel, tie),
        "near_tie_share": float(tie.mean()),
        **{f"nearer_{name}_share": share(got[f"nearer_{name}"], ~got[f"near_tie_{name}"])
           for name in LOWER},
    }
    limits = {
        "worst_margin": SERVED_EPS,
        "worst_margin_near_tie": SERVED_EPS_NEAR_TIE,
        "margin_mean": SERVED_MARGIN_MEAN,
        "logit_rel_err_p50": LOGIT_REL_ERR_P50,
        "logit_rel_err": LOGIT_REL_ERR,
        "logit_rel_err_near_tie": LOGIT_REL_ERR_NEAR_TIE,
        "near_tie_share": NEAR_TIE_SHARE,
        **{f"nearer_{name}_share": NEARER_SHARE[name] for name in LOWER},
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
                        hidden: int = 2048, width: int = 2048,
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
