"""Continuous-batching LLaMA decode engine over the paged KV cache.

ROADMAP item 3's serving path: ``models/decode.py`` gives the framework
a *correct* cached decode loop, this module makes it *serve* —

- **prefill/decode disaggregation**: two separately compiled
  static-shape programs.  ``prefill`` runs every position of a padded
  prompt batch in one pass (as wide as the batch's longest prompt
  needs), writing KV pages and emitting each request's first sampled
  token; ``decode`` packs every active slot into ONE
  ``[max_slots]`` tick, each tick appending one token per live sequence
  (inactive slots ride along masked — the static-shape tax).
- **continuous batching**: a sequence that hits EOS / its length stop
  mid-flight releases its slot AND its pages; the very next scheduler
  iteration admits queued requests into the freed capacity (the dense
  ``[B, max_len]`` slab can't do this — capacity only returned when the
  whole batch drained).  ``admission="static"`` disables exactly that
  (a new batch forms only when ALL slots are idle) — the A/B
  ``bench.py --serve`` prices into the perf ledger.
- **admission control**: a bounded queue, a queued-token budget
  (backpressure under ramp overload), and reject-with-reason — every
  rejection is counted by cause (``queue_full`` / ``token_budget`` /
  ``too_long`` / ``pool_exhausted``), the serving telemetry's contract.

The PR-1..9 stacks carry over rather than being re-invented: decode
sentinels guard the logits numerics inside the compiled tick
(:mod:`ddl25spring_tpu.obs.sentinels`, same DDL25_SENTINELS gate and
policies as every train step), each scheduler iteration lands in the
flight-recorder ring so a dead server is post-mortemable, and the
``describe()`` hooks at the bottom register ``serve-decode`` /
``serve-prefill`` with the compile-analytics/graft-lint registry — the
TP decode signature (row-parallel all-reduces ONLY, everything else
forbidden) and HBM budgets pin in CI like every training strategy.
"""

from __future__ import annotations

import contextlib
import random
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ddl25spring_tpu.analysis import host_sanitizer as _sanitizer
from ddl25spring_tpu.models import decode as decode_mod, llama
from ddl25spring_tpu.models.llama_paged import KV_POOL_HEAD_DIM
from ddl25spring_tpu.obs import (
    memscope as _memscope,
    sentinels,
    spans as _spans,
    state as _obs_state,
)
from ddl25spring_tpu.obs.counters import counters as _counters
from ddl25spring_tpu.obs.timeline import timeline as _timeline
from ddl25spring_tpu.serve import kv_pages
from ddl25spring_tpu.serve.paged_model import (
    PagedModel,
    paged_model,
    refuse_with_state,
)
from ddl25spring_tpu.serve.prefix import Match, PrefixCache
from ddl25spring_tpu.utils.config import LlamaConfig

Params = dict[str, Any]

# submit()-time rejection reasons — the admission-control contract the
# serving telemetry counts by cause
REJECT_QUEUE_FULL = "queue_full"
REJECT_TOKEN_BUDGET = "token_budget"
REJECT_TOO_LONG = "too_long"
REJECT_POOL_EXHAUSTED = "pool_exhausted"
REJECT_BAD_REQUEST = "bad_request"  # empty prompt / non-positive max_new
REJECT_DRAINING = "draining"  # elastic scale-down: replica admits nothing


# ------------------------------------------------------ compiled programs


def _block_stack(model: PagedModel, params, x, cache, slots, rows, pages,
                 offs, pos, live, tp_axis: str | None, layer_stack=None):
    """Every block of ``model`` over ``x [B, T, D]`` at positions ``pos
    [B, T]`` against what the pool holds for it (``cache``: its page planes
    and, for rows seated at ``slots``, its slot state; the seam of
    :mod:`.paged_model`): the resident-weight scan over the model's equal
    units (a layer, or the model's period of layers), or ``layer_stack``'s
    own walk (:func:`make_decode_tick`).  What a model hands from unit to
    unit beside ``x`` (``PagedModel.carry``) rides the scan behind ``x``
    and ``cache``, unopened, and is dropped after the last unit; a model
    that declares none is scanned on ``(x, cache)`` alone; ``layer_stack``'s
    walk hands on no carry and refuses a model that declares one.  Returns
    ``(x, cache, aux)``: ``aux`` is what the model's blocks count of the
    pass, stacked over units, or ``None`` for a model that counts nothing."""
    run_layer = model.layers(
        params, slots, rows, pages, offs, pos, live, tp_axis
    )
    handed = () if model.carry is None else (model.carry(x),)

    if layer_stack is not None:
        if handed:
            raise NotImplementedError(
                "a custom walk over the block stack (layer_stack) hands "
                "(x, cache) from unit to unit and nothing else; this model "
                "declares a carry (PagedModel.carry)"
            )
        return (*layer_stack(params, run_layer, x, cache), None)

    def unit(carry, inp):
        x, cache, aux, *handed = run_layer(*inp, *carry)
        return (x, cache, *handed), aux

    with jax.named_scope("blocks"):
        (x, cache, *_), aux = lax.scan(
            unit, (x, cache, *handed),
            (params["blocks"], jnp.arange(model.n_units)),
        )
    return x, cache, aux


def _pack_pass(tokens, logits, aux, ok, logit_probe: int, table=None):
    """What a pass hands the host, as ONE int32 vector, one fetch:
    ``tokens [n]``; where the engine keeps a probe of the rows they were
    sampled from (``logit_probe > 0``), that many evenly strided logits of
    each row ``logits [n, V]`` as float32 bits; for a prompt pass, the
    table entries its rows' prompts can reach, ``table [n, E]``; where the
    model counts any, the pass's counts ``aux [L, c]``; last, the pool
    flag ``ok`` as 0 or 1 (:meth:`ServeEngine._split_pass` takes it
    apart)."""
    parts = [tokens]
    if logit_probe:
        stride = logits.shape[-1] // logit_probe
        kept = logits[:, : logit_probe * stride : stride].astype(jnp.float32)
        parts.append(lax.bitcast_convert_type(kept, jnp.int32).reshape(-1))
    if table is not None:
        parts.append(table.reshape(-1))
    if aux is not None:
        parts.append(aux.astype(jnp.int32).reshape(-1))
    parts.append(ok.astype(jnp.int32).reshape(1))
    return jnp.concatenate(parts)


def make_decode_tick(
    cfg: LlamaConfig,
    *,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    tp_axis: str | None = None,
    sentinel: bool | None = None,
    strategy: str = "serve-decode",
    layer_stack=None,
    logit_probe: int = 0,
):
    """Build the decode program body: one token for EVERY active slot.

    ``tick(params, pool, key) -> (pool, packed, key)`` — the tokens it
    appends at each slot's current position are the pool's ``last_tok``
    (the previous pass's samples, never uploaded), and the ones it
    samples replace them there at every active slot; ``packed`` is the
    int32 vector of :func:`_pack_pass`: the new tokens ``[max_slots]``
    first and the pool-exhaustion backstop flag ``ok`` last.  A program
    that samples at a temperature splits the key inside (``key, sub =
    split(key)``, ``sub`` draws) and returns the first half for the next
    program, so the engine's key sequence is the one a host-side split
    would make; a greedy one draws nothing and hands the key back as it
    came (a split it does not use would cost every program's lowering
    the threefry rounds on the chip).  Static shapes
    throughout: one compile serves the engine's whole lifetime.  The
    gate+policy of the logits sentinel resolve at BUILD time
    (:func:`ddl25spring_tpu.obs.sentinels.resolve`).

    The block, the page planes, ``embed`` and ``unembed`` are the
    model's (``cfg.paged_model()``, :mod:`.paged_model`); what a model's
    blocks count of a pass (``aux``) rides BEHIND the tokens in the same
    int32 vector, so that the host's one fetch of the sampled tokens
    brings it along; ``logit_probe`` strided logits of every sampled row
    ride there too (``ServeEngine(logit_probe=)``).

    ``layer_stack`` swaps the default resident-weight layer scan for a
    custom walk over the block stack — ``layer_stack(params, run_layer,
    x, planes) -> (x, planes)`` with ``run_layer(bp, li, x, planes)``
    one block's paged step.  The ZeRO-3 weight-streaming decode
    (:func:`_stream_layer_stack`) rides this hook; ``None`` keeps the
    original inline scan, byte-identical to every pre-streaming build
    (pinned in tests/test_serve_tp.py)."""
    model = paged_model(cfg)
    s_on, s_policy = sentinels.resolve(sentinel)

    def tick(params, pool, key):
        tokens = pool["last_tok"]  # [S] — what each slot appends
        active = pool["active"]
        pos = pool["seq_len"]  # [S] — position this tick writes
        page_len = kv_pages.page_len_of(pool)
        n_pages = pool["free"].shape[0]
        S = tokens.shape[0]
        slots = jnp.arange(S, dtype=jnp.int32)

        need = active & (pos % page_len == 0)
        with jax.named_scope("page_write"):
            pool, ok = kv_pages.reserve_pages(pool, slots, pos, need)
            pages, offs = kv_pages.write_page_ids(pool, slots, pos, active)
        rows = jnp.clip(pool["page_table"], 0, n_pages - 1)  # [S, P]

        x = model.embed(params, tokens[:, None])
        x, cache, aux = _block_stack(
            model, params, x, kv_pages.contents(pool), slots, rows,
            pages[:, None], offs[:, None], pos[:, None], active[:, None],
            tp_axis, layer_stack,
        )
        with jax.named_scope("head"):
            logits = model.unembed(params, x)[:, 0]  # [S, V] fp32
        with jax.named_scope("sample"):
            if temperature == 0.0:
                new_tok = logits.argmax(-1).astype(jnp.int32)
            else:
                key, sub = jax.random.split(key)
                new_tok = decode_mod.sample_logits(
                    logits, sub, temperature, top_k, top_p
                )
        pool = kv_pages.with_contents(
            pool, cache, seq_len=jnp.where(active, pos + 1, pos),
            last_tok=jnp.where(active, new_tok, tokens),
        )
        # decode-step sentinel: a non-finite logit on any ACTIVE slot is
        # the serving analogue of a NaN loss (inactive slots carry
        # garbage by construction — masked out of the check)
        new_tok, pool = sentinels.guard(
            strategy, (new_tok, pool),
            loss=jnp.max(jnp.where(active, jnp.max(
                jnp.abs(logits), axis=-1), 0.0)),
            updates={"logits": jnp.where(active[:, None], logits, 0.0)},
            fallback=(new_tok, pool),
            axis=tp_axis, enabled=s_on, policy=s_policy,
        )
        return pool, _pack_pass(new_tok, logits, aux, ok, logit_probe), key

    return tick


def prefill_widths(max_prompt_len: int) -> tuple[int, ...]:
    """The widths a prefill pass is padded to: half of ``max_prompt_len``
    and the whole of it (128, 256 at 256); the widths of
    :func:`pass_shapes`."""
    return tuple(sorted({-(-max_prompt_len // 2), max_prompt_len}))


def pass_shapes(
    prefill_batch: int, max_prompt_len: int
) -> tuple[tuple[int, int], ...]:
    """The ladder of shapes ``(rows, width)`` a prefill pass may take,
    cheapest first: a quarter of ``prefill_batch`` rows at each of
    :func:`prefill_widths`, then half of it and the whole of it at the
    full width ((2, 128) (2, 256) (4, 256) (8, 256) at 8 and 256).  A pass
    rides the first that holds its batch's rows and its longest unmatched
    suffix (:func:`shape_for`), so the one jitted prefill specialises into
    at most four programs, all of them run once by
    :meth:`ServeEngine.warmup`: a request admitted alone is not padded to
    ``prefill_batch`` rows.  The widths split where the rows are few
    because that is where the passes are: a closed loop admits one or two
    requests a pass after its opening, and its few batches of three or
    four set the tail of the time to a first token (``PERF.md`` section 6,
    PR 34, has the counts)."""
    narrow, wide = prefill_widths(max_prompt_len)[0], max_prompt_len
    quarter, half = max(1, prefill_batch // 4), max(1, prefill_batch // 2)
    return tuple(sorted(
        {(quarter, narrow), (quarter, wide), (half, wide),
         (prefill_batch, wide)},
        key=lambda shape: (shape[0] * shape[1], shape),
    ))


def shape_for(
    shapes: tuple[tuple[int, int], ...], rows: int, longest: int
) -> tuple[int, int]:
    """The first (cheapest) of ``shapes`` that holds ``rows`` rows of up
    to ``longest`` positions."""
    return next((r, w) for r, w in shapes if r >= rows and w >= longest)


def make_prefill(
    cfg: LlamaConfig,
    *,
    max_prompt_len: int,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    tp_axis: str | None = None,
    sentinel: bool | None = None,
    strategy: str = "serve-prefill",
    logit_probe: int = 0,
):
    """Build the prefill program body: write a padded prompt batch into
    the pool in ONE pass over all its positions and sample each
    request's FIRST generated token.

    ``prefill(params, pool, prompts, lens, starts, slot_ids, key) ->
    (pool, packed, key)`` — ``slot_ids [B]`` are the target slots
    (``-1`` = padding row, which writes only to the trash page), ``lens
    [B]`` the prompts' lengths (at most ``max_prompt_len``, the capacity
    pages are reserved and gathered for), ``starts [B]`` how many
    leading positions of each row already sit in pages
    ``kv_pages.adopt_prefix`` seated in its table (a radix hit; 0 cold).
    ``prompts [B, W]`` int32 holds each row's UNMATCHED suffix:
    ``prompts[b, j]`` is the token at position ``starts[b] + j``, and
    the row writes while that position is below ``lens[b]``.  ``B`` and
    ``W`` are the shape's (one compiled program a shape: the engine pads
    to :func:`pass_shapes`), so a request admitted alone rides a pass of
    few rows and a hit a narrower one.  All ``B x W`` positions run
    through the model's paged block at once — the block the decode tick
    runs with one position a row — after ONE all-or-nothing page
    reservation; the logits are taken at ``lens - 1`` only.  On exit the
    target slots are active with ``seq_len = lens`` — exactly the state
    the next decode tick expects;
    a model that keeps slot state has seated, at ``slot_ids``, each row's
    state as it stands after the row's last live position.
    The first tokens are also the target slots' ``last_tok``, which the
    next decode tick reads.  ``packed`` is :func:`_pack_pass`'s vector:
    the first tokens ``[B]``, the probe of the rows they were sampled
    from, each row's first ``E`` table entries (the pages its prompt
    reaches, which the prefix cache indexes), what the model's blocks
    count of the pass, and ``ok`` last; the key comes back as
    :func:`make_decode_tick` hands it back."""
    model = paged_model(cfg)
    s_on, s_policy = sentinels.resolve(sentinel)

    def prefill(params, pool, prompts, lens, starts, slot_ids, key):
        B, W = prompts.shape
        n_pages = pool["free"].shape[0]
        n_slots, P = pool["page_table"].shape
        page_len = kv_pages.page_len_of(pool)
        # table entries a prompt can reach: what is reserved and gathered
        E = min(P, -(-max_prompt_len // page_len))
        valid_row = slot_ids >= 0
        pool = kv_pages.activate_slots(pool, slot_ids, valid_row)

        pos = starts[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
        writing = valid_row[:, None] & (pos < lens[:, None])
        slots = jnp.broadcast_to(slot_ids[:, None], (B, W))
        with jax.named_scope("page_write"):
            # every table entry whose FIRST position this pass writes
            # opens a page; flattened entry-major, which is the order a
            # position-by-position walk would allocate in
            opens = jnp.arange(E, dtype=jnp.int32)[:, None] * page_len
            need = (valid_row[None, :] & (opens >= starts[None, :])
                    & (opens < lens[None, :]))  # [E, B]
            pool, ok = kv_pages.reserve_pages(
                pool, jnp.broadcast_to(slot_ids[None, :], (E, B)).reshape(-1),
                jnp.broadcast_to(opens, (E, B)).reshape(-1), need.reshape(-1),
            )
            pages, offs = kv_pages.write_page_ids(pool, slots, pos, writing)
        table = pool["page_table"][jnp.clip(slot_ids, 0, n_slots - 1), :E]
        rows = jnp.clip(table, 0, n_pages - 1)  # [B, E]

        x = model.embed(params, prompts)
        x, cache, aux = _block_stack(
            model, params, x, kv_pages.contents(pool), slot_ids, rows, pages,
            offs, pos, writing, tp_axis,
        )
        with jax.named_scope("head"):
            last = jnp.clip(lens - 1 - starts, 0, W - 1)
            x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)
            last_logits = model.unembed(params, x_last)[:, 0]
        with jax.named_scope("sample"):
            if temperature == 0.0:
                first = last_logits.argmax(-1).astype(jnp.int32)
            else:
                key, sub = jax.random.split(key)
                first = decode_mod.sample_logits(
                    last_logits, sub, temperature, top_k, top_p
                )
        sent = jnp.where(valid_row, slot_ids, n_slots)
        pool = kv_pages.with_contents(
            pool, cache,
            seq_len=pool["seq_len"].at[sent].set(lens, mode="drop"),
            last_tok=pool["last_tok"].at[sent].set(first, mode="drop"),
        )
        first, pool = sentinels.guard(
            strategy, (first, pool),
            loss=jnp.max(jnp.where(valid_row, jnp.max(
                jnp.abs(last_logits), axis=-1), 0.0)),
            updates={"logits": jnp.where(
                valid_row[:, None], last_logits, 0.0)},
            fallback=(first, pool),
            axis=tp_axis, enabled=s_on, policy=s_policy,
        )
        packed = _pack_pass(first, last_logits, aux, ok, logit_probe, table)
        return pool, packed, key

    return prefill


# A program that changes only the pool's accounting takes and returns only
# the pool's accounting (``kv_pages.accounting``: six small arrays), so no
# plane is a parameter or a result of these four and none is copied;
# ``ServeEngine._account`` is their one caller.  Each respecialises by the
# pool's geometry under jit and so serves every engine and both pools.
_release = jax.jit(kv_pages.release_slots)
_ref = jax.jit(kv_pages.ref_pages)
_unref = jax.jit(kv_pages.unref_pages)
_truncate = jax.jit(kv_pages.truncate_to, static_argnames="page_len")
# adopt_prefix WRITES planes (the copy-on-write page), so it takes the whole
# pool: donated where the engine's programs donate theirs, so that a radix
# hit costs a page's copy and not a pool's
_adopt = jax.jit(kv_pages.adopt_prefix)
_adopt_donating = jax.jit(kv_pages.adopt_prefix, donate_argnums=(0,))


# One compiled (tick, prefill, release) triple per build key: the ramp
# engine and both A/B engines of a `bench.py --serve` run (and every
# same-config test engine, and a drafter of that config for its
# prefill) reuse XLA programs instead of paying the compile bill per
# ServeEngine.  Keyed on everything that shapes the BUILT program — cfg
# (frozen dataclass), prompt capacity, sampling, the
# RESOLVED sentinel gate+policy (env is read at build time, so an env
# flip lands in the key), and donation.
_PROGRAM_CACHE: dict[tuple, tuple] = {}


def _compiled_programs(
    cfg: LlamaConfig, *, max_prompt_len: int, temperature: float,
    sentinel: bool | None, donate: bool, logit_probe: int = 0,
):
    key = (
        cfg, max_prompt_len, temperature, sentinels.resolve(sentinel),
        donate, logit_probe,
    )
    if key not in _PROGRAM_CACHE:
        tick = make_decode_tick(
            cfg, temperature=temperature, sentinel=sentinel,
            logit_probe=logit_probe,
        )
        # tick/prefill donate their POOL argument (position 1)
        pool_kw = {"donate_argnums": (1,)} if donate else {}
        _PROGRAM_CACHE[key] = (
            jax.jit(tick, **pool_kw),
            # one jitted function: it specialises by the prompts' width
            jax.jit(make_prefill(
                cfg, max_prompt_len=max_prompt_len,
                temperature=temperature, sentinel=sentinel,
                logit_probe=logit_probe,
            ), **pool_kw),
            _release,
        )
    return _PROGRAM_CACHE[key]


# speculative-decoding programs (PR 13): one compiled (draft-k,
# draft-k+1, verify) triple per (target cfg, draft cfg, k, sentinel,
# donate) — every same-config engine (the spec A/B's two arms, the
# test engines) shares the XLA programs.  The drafter's prefill is
# _compiled_programs' at the DRAFT cfg, and rollback rides the
# module-level _truncate program.
_SPEC_CACHE: dict[tuple, dict] = {}


def _spec_programs(
    cfg: LlamaConfig, draft_cfg: LlamaConfig, *, k: int,
    sentinel: bool | None, donate: bool,
):
    from ddl25spring_tpu.serve import spec as spec_mod

    key = (cfg, draft_cfg, k, sentinels.resolve(sentinel), donate)
    if key not in _SPEC_CACHE:
        pool_kw = {"donate_argnums": (1,)} if donate else {}
        _SPEC_CACHE[key] = {
            # steps=k serves rounds where every slot owes exactly one
            # catch-up token (the common case); steps=k+1 is the
            # post-full-accept variant — both pre-compiled by warmup()
            "draft_k": jax.jit(spec_mod.make_draft(
                draft_cfg, k=k, steps=k, sentinel=sentinel,
            ), **pool_kw),
            "draft_k1": jax.jit(spec_mod.make_draft(
                draft_cfg, k=k, steps=k + 1, sentinel=sentinel,
            ), **pool_kw),
            "verify": jax.jit(spec_mod.make_verify(
                cfg, k=k, sentinel=sentinel,
            ), **pool_kw),
        }
    return _SPEC_CACHE[key]


# ------------------------------------------------- TP-sharded programs
#
# The engine's tp>1 mode (PR 18) compiles the SAME program bodies under
# shard_map over a 1-D ``model`` mesh: params in the training-side TP
# layout (row-parallel blocks — exactly two psums per layer, the pinned
# serve-decode signature), the KV pool's HEAD dim sharded per the H013
# contract, and everything host-visible (page tables, refcounts, seq
# lens, admission masks) replicated so the scheduler never changes.
# ``weight_stream=True`` additionally stores the block weights ZeRO-3
# style — [L, n, k] rows over the same axis — and gathers ONE layer at
# a time inside the decode scan (parallel/zero.py's double-buffered
# prefetch), so per-chip param residency is blocks/n + one layer.


def _tp_pool_specs(cfg, model_axis: str = "model"):
    """PartitionSpecs for every pool buffer of ``cfg``'s model: each
    plane split on the pool axis its model names (``tp_shard``; the dense
    block's heads, :data:`KV_POOL_HEAD_DIM`), all accounting state
    replicated (the sharing ops stay layout-oblivious — pinned in
    tests)."""
    from jax.sharding import PartitionSpec as P

    model = paged_model(cfg)
    if model.tp_shard is None:
        raise ValueError(
            f"{type(cfg).__name__}'s paged model offers no tensor-parallel "
            "pool layout (tp_shard is None): serve it at tp=1"
        )
    return {
        **{
            name: P(*(
                model_axis if d == model.tp_shard[name] else None
                for d in range(3 + len(shape))
            ))
            for name, shape in model.planes.items()
        },
        **{name: P() for name in kv_pages.ACCOUNTING},
    }


def _resident_template(cfg: LlamaConfig):
    """The abstract parameter tree the TP programs are lowered with: the
    leaves as the engine holds them (``PagedModel.resident``), so that a
    streamed bucket plan counts the bytes that are streamed."""
    return jax.eval_shape(lambda: paged_model(cfg).resident(
        llama.init_llama_params(jax.random.PRNGKey(0), cfg)
    ))


def _tp_param_specs(cfg: LlamaConfig, model_axis: str,
                    weight_stream: bool):
    """Entry-param specs for the TP programs: Megatron column/row splits
    (vocab replicated) normally, the ZeRO-3 ``[L, n, k]`` row layout
    (outer leaves replicated) under weight streaming."""
    if not weight_stream:
        from ddl25spring_tpu.parallel.tp import tp_param_specs

        return tp_param_specs(model_axis, False, 0)
    from ddl25spring_tpu.parallel import zero

    return zero.stream_param_specs(_resident_template(cfg), model_axis)


def _tp_slice_block(p: dict, model_axis: str, t: int, *,
                    stacked: bool = False):
    """This chip's Megatron shard of a FULL block param dict: column
    leaves (wq/wk/wv/w_gate/w_up) slice their last dim, row leaves
    (wo/w_down) their input dim, norms stay whole — the exact chunks
    :func:`ddl25spring_tpu.parallel.tp.shard_tp_params` places, so the
    compute downstream of a streamed gather is bit-identical to the
    resident-TP program's.  ``stacked`` handles the ``[L, ...]`` block
    stack (row dims shift right by one)."""
    from ddl25spring_tpu.parallel.tp import _COL, _ROW

    i = lax.axis_index(model_axis)
    out = {}
    for name, w in p.items():
        if name in _COL:
            c = w.shape[-1] // t
            out[name] = lax.dynamic_slice_in_dim(w, i * c, c, w.ndim - 1)
        elif name in _ROW:
            ax = 1 if stacked else 0
            c = w.shape[ax] // t
            out[name] = lax.dynamic_slice_in_dim(w, i * c, c, ax)
        else:
            out[name] = w
    return out


def _stream_layer_stack(cfg: LlamaConfig, model_axis: str, n: int):
    """The ZeRO-3 streaming walk over the block stack, as a
    ``layer_stack`` hook for :func:`make_decode_tick`: layer ``i+1``'s
    bucketed all-gather is issued BEFORE layer ``i``'s compute (the
    double-buffered scan carry of ``zero3-prefetch``), each gathered
    layer is TP-sliced locally and run through the ordinary row-parallel
    paged block.  Returns ``(layer_stack, plan)`` — the plan's bucket
    count times ``n_layers`` is the program's pinned all-gather count."""
    from ddl25spring_tpu.parallel import zero

    plan = zero.stream_block_plan(_resident_template(cfg)["blocks"], n)
    L = cfg.n_layers

    def layer_stack(params, run_layer, x, planes):
        bufs = zero.stream_layer_bufs(plan, params["blocks"], L)

        def gather(i):
            rows = [
                lax.dynamic_index_in_dim(b, i, 0, keepdims=False)
                for b in bufs
            ]
            return zero.stream_gather_layer(plan, rows, model_axis, n)

        cur = gather(0)
        if L > 1:
            def body(carry, i):
                x, planes, cur = carry
                # issue layer i+1's gather BEFORE layer i's compute
                nxt = gather(i + 1)
                x, planes, _aux = run_layer(
                    _tp_slice_block(cur, model_axis, n), i, x, planes
                )
                return (x, planes, nxt), None

            (x, planes, cur), _ = lax.scan(
                body, (x, planes, cur), jnp.arange(L - 1)
            )
        # the last layer is peeled: nothing left to prefetch
        x, planes, _aux = run_layer(
            _tp_slice_block(cur, model_axis, n),
            jnp.int32(L - 1), x, planes,
        )
        return x, planes

    return layer_stack, plan


def _tp_jit(body, mesh, cfg, *, model_axis: str, n_extra: int, p_specs,
            donate: bool):
    """shard_map + jit one serve program body under the TP pool/param
    layout: pool k/v enter split over ``model_axis`` (so the in-spec
    already types them varying there — no cast; ``lax.pcast`` of an
    already-varying value raises), scalars/tables replicated, pool
    donated like the dense programs when asked."""
    from jax.sharding import PartitionSpec as P

    pool_specs = _tp_pool_specs(cfg, model_axis)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(p_specs, pool_specs) + (P(),) * n_extra,
        out_specs=(pool_specs, P(), P()),
        # on a one-device mesh the bodies carry no psum (tp_axis is
        # None) to re-type what the in-specs and the streamed gathers
        # mark varying, and one device has nothing that could vary
        check_vma=mesh.shape[model_axis] > 1,
    )
    pool_kw = {"donate_argnums": (1,)} if donate else {}
    return jax.jit(fn, **pool_kw)


# one compiled TP triple per (cfg, mesh, ...) build key — same reuse
# discipline as _PROGRAM_CACHE; the mesh object participates so two
# engines on different device subsets never share an executable
_TP_PROGRAM_CACHE: dict[tuple, tuple] = {}
_TP_SPEC_CACHE: dict[tuple, dict] = {}


def _tp_prefill_body(cfg: LlamaConfig, model_axis: str, t: int, *,
                     max_prompt_len: int, temperature: float,
                     sentinel: bool | None, weight_stream: bool):
    """The prefill body of a ``t``-way TP build; under weight streaming,
    inside a shell that gathers the block stack first."""
    body = make_prefill(
        cfg, max_prompt_len=max_prompt_len, temperature=temperature,
        tp_axis=model_axis if t > 1 else None, sentinel=sentinel,
    )
    if not weight_stream:
        return body
    # one pass reads every layer once, but the layer scan wants the
    # stack whole: streamed prefill gathers ALL blocks up front
    # (transient — dropped at program exit)
    from ddl25spring_tpu.parallel import zero

    plan = zero.stream_block_plan(_resident_template(cfg)["blocks"], t)

    def streamed(params, pool, *rest):
        blocks = zero.stream_gather_blocks(
            plan, params["blocks"], model_axis, t
        )
        full = {
            **{k: v for k, v in params.items() if k != "blocks"},
            "blocks": _tp_slice_block(blocks, model_axis, t, stacked=True),
        }
        return body(full, pool, *rest)

    return streamed


def _tp_compiled_programs(
    cfg: LlamaConfig, mesh, *, max_prompt_len: int, temperature: float,
    sentinel: bool | None, donate: bool, weight_stream: bool = False,
    model_axis: str = "model",
):
    key = (
        cfg, mesh, max_prompt_len, temperature,
        sentinels.resolve(sentinel), donate, weight_stream, model_axis,
    )
    if key not in _TP_PROGRAM_CACHE:
        t = int(mesh.shape[model_axis])
        tp_axis = model_axis if t > 1 else None
        stack = None
        if weight_stream:
            stack, _plan = _stream_layer_stack(cfg, model_axis, t)
        tick_body = make_decode_tick(
            cfg, temperature=temperature, tp_axis=tp_axis,
            sentinel=sentinel, layer_stack=stack,
        )
        _TP_PROGRAM_CACHE[key] = (
            _tp_jit(
                tick_body, mesh, cfg, model_axis=model_axis,
                n_extra=1,
                p_specs=_tp_param_specs(cfg, model_axis, weight_stream),
                donate=donate,
            ),
            _tp_jit(
                _tp_prefill_body(
                    cfg, model_axis, t, max_prompt_len=max_prompt_len,
                    temperature=temperature, sentinel=sentinel,
                    weight_stream=weight_stream,
                ),
                mesh, cfg, model_axis=model_axis, n_extra=5,
                p_specs=_tp_param_specs(cfg, model_axis, weight_stream),
                donate=donate,
            ),
            # release sees only the replicated accounting state
            _release,
        )
    return _TP_PROGRAM_CACHE[key]


def _tp_spec_programs(
    cfg: LlamaConfig, draft_cfg: LlamaConfig, mesh, *, k: int,
    sentinel: bool | None, donate: bool, model_axis: str = "model",
):
    from ddl25spring_tpu.serve import spec as spec_mod

    key = (
        cfg, draft_cfg, mesh, k, sentinels.resolve(sentinel), donate,
        model_axis,
    )
    if key not in _TP_SPEC_CACHE:
        t = int(mesh.shape[model_axis])
        tp_axis = model_axis if t > 1 else None

        def build(body, body_cfg, n_extra):
            return _tp_jit(
                body, mesh, body_cfg, model_axis=model_axis,
                n_extra=n_extra,
                p_specs=_tp_param_specs(body_cfg, model_axis, False),
                donate=donate,
            )

        _TP_SPEC_CACHE[key] = {
            "draft_k": build(spec_mod.make_draft(
                draft_cfg, k=k, steps=k, tp_axis=tp_axis,
                sentinel=sentinel,
            ), draft_cfg, 3),
            "draft_k1": build(spec_mod.make_draft(
                draft_cfg, k=k, steps=k + 1, tp_axis=tp_axis,
                sentinel=sentinel,
            ), draft_cfg, 3),
            "verify": build(spec_mod.make_verify(
                cfg, k=k, tp_axis=tp_axis, sentinel=sentinel,
            ), cfg, 2),
        }
    return _TP_SPEC_CACHE[key]


# ----------------------------------------------------------- host engine


def _pct(xs, q):
    """Nearest-rank percentile over any sample iterable (None when
    empty) — shared by :meth:`ServeEngine.metrics` and the TTFT
    decomposition cell."""
    xs = sorted(xs)
    if not xs:
        return None
    k = min(len(xs) - 1, max(0, round(q / 100 * (len(xs) - 1))))
    return xs[k]


class Generated(list):
    """A request's generated tokens.  Under ``ServeEngine(logit_probe=k)``
    ``probe[j]`` holds ``k`` evenly strided logits (float32, ids ``0,
    V // k, 2 V // k, ...``) of the row token ``j`` was sampled from, as
    the pass that sampled it computed them: what the engine's own
    compiled programs produced, for whoever holds them to a reference."""

    def __init__(self, *args):
        super().__init__(*args)
        self.probe: list = []


@dataclass
class Request:
    """One inference request (host side)."""

    rid: int
    prompt: Any  # 1-D int array/list of token ids
    max_new_tokens: int
    arrival_t: float = 0.0
    # filled by the engine
    admitted_t: float | None = None
    # TTFT decomposition stamps (engine clock): when the admitting
    # prefill dispatch began, and what that prefill pass cost — the
    # residual to first_token_t is the "first decode" component
    # (drafter prefill under spec, host overhead on the wall clock)
    prefill_start_t: float | None = None
    prefill_s: float | None = None
    first_token_t: float | None = None
    done_t: float | None = None
    tokens: Generated = field(default_factory=Generated)

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)


@dataclass
class _Tick:
    """A decode tick on the device whose tokens the host has not fetched:
    what it returns, the slots it serves, and what was known of it at its
    dispatch."""

    packed: Any  # its _pack_pass vector, on the device
    rows: list[tuple[int, Request]]  # (slot, request) it samples for
    counts: dict[str, int]  # its span stats (_tick_counts)
    t_dispatch: float  # perf_counter at its dispatch: its rings' stamp
    ahead: bool  # dispatched before the previous program was fetched
    # where its tick_wall_s sample starts: its dispatch, or for a tick
    # dispatched ahead the previous program's fetch (set then)
    t_start: float | None = None


# default sample cap for the engine's per-run host reservoirs: far
# above any smoke/test population (behavior identical below the cap),
# small enough that a week-long soak holds kilobytes, not gigabytes
RESERVOIR_CAP = 4096


class Reservoir:
    """Bounded uniform sample of a per-run series + exact summary.

    The engine's per-request host lists (``ttft_s``, ``queue_depths``,
    ``tick_wall_s``) previously grew linearly with requests — a slow
    OOM on soak runs.  This is classic Algorithm-R reservoir sampling
    with a dedicated seeded ``random.Random`` (the engine's jax key
    stream is never touched, so token streams stay bitwise identical),
    plus exact ``count``/``max``/``min``/``total`` maintained over the
    FULL series so occupancy peaks and counts never degrade to "of the
    sample".  Below ``cap`` it is exactly an insertion-ordered list —
    the regime every test and smoke run lives in."""

    __slots__ = ("cap", "count", "max", "min", "total", "_xs", "_rng",
                 "_seed")

    def __init__(self, cap: int = RESERVOIR_CAP, seed: int = 0):
        self.cap = int(cap)
        self._seed = int(seed)
        self._xs: list = []
        self._rng = random.Random(self._seed)
        self.count = 0
        self.max = None
        self.min = None
        self.total = 0.0

    def append(self, x) -> None:
        self.count += 1
        if isinstance(x, (int, float)) and not isinstance(x, bool):
            self.total += x
            if self.max is None or x > self.max:
                self.max = x
            if self.min is None or x < self.min:
                self.min = x
        if len(self._xs) < self.cap:
            self._xs.append(x)
        else:
            j = self._rng.randrange(self.count)
            if j < self.cap:
                self._xs[j] = x

    def clear(self) -> None:
        self._xs.clear()
        self._rng = random.Random(self._seed)
        self.count = 0
        self.max = None
        self.min = None
        self.total = 0.0

    def summary(self) -> dict:
        """The exact-count cell (telemetry): what the full series did,
        regardless of how much of it is still sampled."""
        return {
            "count": self.count,
            "sampled": len(self._xs),
            "cap": self.cap,
            "max": self.max,
            "min": self.min,
            "mean": (
                round(self.total / self.count, 6) if self.count else None
            ),
        }

    def __len__(self) -> int:
        return len(self._xs)

    def __bool__(self) -> bool:
        return bool(self._xs)

    def __iter__(self):
        return iter(self._xs)

    def __getitem__(self, i):
        return self._xs[i]

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"Reservoir(count={self.count}, sampled={len(self._xs)},"
                f" cap={self.cap})")


class ServeEngine:
    """The scheduler loop: admission -> prefill -> packed decode ticks.

    Host-side state (queue, per-slot request records, page accounting)
    stays in Python; everything per-token runs in the two compiled
    programs.  The page accounting is mirrored on the host — admission
    reserves each request's WORST-CASE page need
    (``ceil((prompt + max_new) / page_len)``), so a request admitted is
    a request that can always finish; the device-side ``ok`` flag is
    the backstop that this invariant held.

    ``clock="wall"`` uses real time (the bench path);
    ``clock="virtual"`` advances ``tick_s`` per program call — fully
    deterministic, which is what the continuous-vs-static equivalence
    and admission tests pin.

    **Running ahead.**  A tick's input tokens (the pool's ``last_tok``)
    and its key are what the previous pass left on the device, so on the
    wall clock the engine dispatches the next decode tick BEFORE it
    fetches the program in flight (the last tick, or this step's prompt
    pass) wherever the host can already tell that the next step would
    dispatch exactly that tick: no live slot reaches its
    ``max_new_tokens`` at the program in flight (a pass's rows count
    their first token), ``eos_id`` is None, nothing queued is admittable
    and speculation is off (:meth:`_runs_ahead`).  The device then runs
    the next tick while the host fetches and emits the last one's tokens,
    and at most one tick is left unfetched between two steps
    (:attr:`drained` is False while one is).  Where a condition fails
    the engine fetches first, as it always did: a finished slot is
    flushed, and a new arrival's pass goes to the device with no tick
    queued ahead of it.  With ``eos_id`` set, under speculation, and on
    the virtual clock (which charges no host time, so has none to hide,
    and whose pinned schedule is the fetch-first one) the engine runs
    exactly as before.  The ring ``serve.tick_ahead`` says of each tick
    whether it went ahead.
    """

    def __init__(
        self,
        params: Params,
        cfg: LlamaConfig,
        *,
        page_len: int = 16,
        n_pages: int = 64,
        max_slots: int = 4,
        pages_per_seq: int | None = None,
        prefill_batch: int = 2,
        max_prompt_len: int = 32,
        max_queue: int = 64,
        token_budget: int | None = None,
        temperature: float = 0.0,
        eos_id: int | None = None,
        admission: str = "continuous",
        sentinel: bool | None = None,
        donate: bool = True,
        clock: str = "wall",
        tick_s: float = 1e-3,
        seed: int = 0,
        prefix_cache: bool = False,
        spec_k: int = 0,
        draft_layers: int = 1,
        draft_params: Params | None = None,
        draft_cfg: LlamaConfig | None = None,
        tp: int = 1,
        weight_stream: bool = False,
        trace_label: str | None = "serve",
        logit_probe: int = 0,
    ):
        if admission not in ("continuous", "static"):
            raise ValueError(
                f"admission={admission!r} is not 'continuous' or 'static'"
            )
        if clock not in ("wall", "virtual"):
            raise ValueError(f"clock={clock!r} is not 'wall' or 'virtual'")
        if prefill_batch < 1:
            # a 0-width prefill admits nothing and the virtual clock
            # never advances — the run() loop would spin to max_steps
            raise ValueError(
                f"prefill_batch={prefill_batch} must be >= 1"
            )
        if spec_k < 0:
            raise ValueError(f"spec_k={spec_k} must be >= 0 (0 = off)")
        if spec_k and temperature != 0.0:
            # greedy speculation is exactly the target's own output (a
            # draft is accepted iff it equals the argmax); sampled
            # speculation needs the rejection-sampling correction —
            # future work, refuse rather than serve a skewed stream
            raise ValueError(
                "speculative decoding is greedy-only "
                f"(temperature={temperature} with spec_k={spec_k})"
            )
        if logit_probe and (spec_k or tp > 1):
            raise ValueError(
                "logit_probe is kept by the plain tick and prefill only "
                f"(spec_k={spec_k}, tp={tp})"
            )
        self.logit_probe = logit_probe
        self.cfg = cfg
        # what the model offers the server: planes, block, embed/unembed
        # (raises the seam's one error for a model that offers none)
        self._model = paged_model(cfg)
        # what would have to restore, split or ship a slot's state is
        # refused here, by name, for a model that keeps any
        for feature, asked in (
            ("the prefix cache (prefix_cache=True)", prefix_cache),
            ("a drafter (spec_k > 0)", spec_k),
            ("a tp_axis (tp > 1)", tp > 1),
        ):
            if asked:
                refuse_with_state(cfg, feature)
        self.page_len = page_len
        self.n_pages = n_pages
        self.max_slots = max_slots
        if pages_per_seq is None:  # explicit 0 must FAIL in the pool
            pages_per_seq = max(1, -(-cfg.ctx_size // page_len))
        self.pages_per_seq = pages_per_seq
        self.max_seq_len = self.pages_per_seq * page_len
        self.prefill_batch = prefill_batch
        self._pass_shapes = pass_shapes(prefill_batch, max_prompt_len)
        self.max_prompt_len = max_prompt_len
        self.max_queue = max_queue
        self.token_budget = token_budget
        self.eos_id = eos_id
        self.admission = admission
        self.clock = clock
        self.tick_s = tick_s
        # graft-trace identity (PR 16): which timeline track this
        # engine's request-lifecycle events land on.  ``None`` keeps an
        # engine off the timeline entirely — the driver's deterministic
        # A/B arms use it so replayed traffic doesn't shadow the live
        # run's story.  ``replica_id`` is STABLE for the engine's whole
        # life (the elastic driver assigns monotonically; list indices
        # shift when a drained replica leaves).
        self.trace_label = trace_label
        self.replica_id = 0
        # what tells this engine's spans and rings (obs.spans.span,
        # obs.counters) from another's in the same process: nothing for
        # an unlabelled engine (the benchmark's; the driver's A/B arms)
        # or the default label, ``@<label>`` after every name otherwise
        self._obs_key = (
            "" if trace_label in (None, "serve") else f"@{trace_label}"
        )
        self._key = jax.random.PRNGKey(seed)
        # the engine holds the parameters as its programs READ them (the
        # model says which leaves it casts at each use): rounded once
        # here, before any placement, and no reference to a master of
        # another type is kept.  A tree that arrives resident (another
        # engine's, a model served in its own type) is taken as it is
        params = self.params = self._make_resident(self._model, params)

        # TP-sharded serving (PR 18): tp > 1 runs every compiled
        # program under a 1-D ``model`` mesh — params row-parallel, the
        # pool's head dim split per the H013 contract, the host
        # scheduler untouched (all its state is replicated).  tp == 1
        # keeps the EXACT single-device build (same _PROGRAM_CACHE
        # entries — the byte-identical-HLO pin in tests/test_serve_tp).
        self.tp = int(tp)
        self.weight_stream = bool(weight_stream)
        self.mesh = None
        self._model_axis = "model"
        if self.tp < 1:
            raise ValueError(f"tp={tp} must be >= 1")
        if self.weight_stream and self.tp == 1:
            raise ValueError(
                "weight_stream streams ZeRO-3 rows over the model mesh "
                "axis — it requires tp > 1 (tp=1 holds the whole model "
                "per chip by construction)"
            )
        if self.weight_stream and spec_k:
            raise ValueError(
                "weight_stream serves the plain decode path only: the "
                "drafter's interleaved rounds would re-stream the "
                "target stack per round (spec_k must be 0)"
            )
        if self.tp > 1:
            from ddl25spring_tpu.utils.mesh import make_mesh

            _tp_pool_specs(cfg, self._model_axis)  # refuses a model without
            devs = jax.devices()
            if len(devs) < self.tp:
                raise ValueError(
                    f"tp={self.tp} needs {self.tp} devices; "
                    f"{len(devs)} visible"
                )
            if cfg.num_heads % self.tp:
                raise ValueError(
                    f"{cfg.num_heads} heads not divisible by tp={self.tp}"
                )
            self.mesh = make_mesh(devs[:self.tp], model=self.tp)
            if self.weight_stream:
                from ddl25spring_tpu.parallel import zero

                self.params = zero.zero_stream_llama_params(
                    params, self.mesh, self._model_axis
                )
            else:
                from ddl25spring_tpu.parallel.tp import shard_tp_params

                self.params = shard_tp_params(
                    params, self.mesh, self._model_axis,
                    shard_vocab=False,
                )

        self.pool = self._build_pool(cfg)

        def programs(cfg, temperature):
            """(tick, prefill, release) of ``cfg`` under this engine's
            placement — the drafter asks again for its own prefill."""
            if self.tp > 1:
                return _tp_compiled_programs(
                    cfg, self.mesh, max_prompt_len=max_prompt_len,
                    temperature=temperature, sentinel=sentinel,
                    donate=donate, weight_stream=self.weight_stream,
                    model_axis=self._model_axis,
                )
            return _compiled_programs(
                cfg, max_prompt_len=max_prompt_len,
                temperature=temperature, sentinel=sentinel, donate=donate,
                logit_probe=logit_probe,
            )

        self._tick, self._prefill, self._release = programs(
            cfg, temperature
        )
        self._adopt = _adopt_donating if donate else _adopt
        # accounting programs dispatched so far (`_account`): the spans
        # around them carry its growth as their stat `account_ops`
        self._account_ops = 0
        # radix prefix cache (opt-in): host index over cached prompt
        # pages; device sharing runs through kv_pages.adopt_prefix /
        # ref_pages / unref_pages, and prefill takes each row's start
        self.prefix: PrefixCache | None = (
            PrefixCache(page_len) if prefix_cache else None
        )
        # speculative decoding (opt-in, PR 13): a tiny drafter with its
        # OWN paged pool proposes spec_k tokens per round; one target
        # verify pass scores them all; truncate_to rolls both pools
        # back to the accepted prefix.  The default drafter is the
        # early-exit construction (serve/spec.py) — pass draft_params +
        # draft_cfg for a distilled one.
        self.spec_k = int(spec_k)
        self.draft_pool: dict | None = None
        if self.spec_k:
            from ddl25spring_tpu.serve import spec as spec_mod

            if draft_params is None:
                draft_params, draft_cfg = spec_mod.early_exit_drafter(
                    params, cfg, draft_layers
                )
            elif draft_cfg is None:
                raise ValueError(
                    "explicit draft_params need their draft_cfg"
                )
            else:
                draft_params = self._make_resident(
                    paged_model(draft_cfg), draft_params, "serve.draft_resident"
                )
            # the drafter derives from (and shards like) the target:
            # early_exit_drafter slices the unsharded RESIDENT params (so
            # speculation holds no second copy in another type), then tp>1
            # places the result in the same Megatron layout — its pool
            # shards the head dim under the identical H013 contract
            if self.tp > 1:
                from ddl25spring_tpu.parallel.tp import shard_tp_params

                if draft_cfg.num_heads % self.tp:
                    raise ValueError(
                        f"draft {draft_cfg.num_heads} heads not "
                        f"divisible by tp={self.tp}"
                    )
                self.draft_params = shard_tp_params(
                    draft_params, self.mesh, self._model_axis,
                    shard_vocab=False,
                )
            else:
                self.draft_params = draft_params
            self.draft_cfg = draft_cfg
            # what each drafter step costs on the deterministic virtual
            # clock, as a fraction of a target decode tick
            self.spec_flop_ratio = spec_mod.flop_ratio(draft_params, params)
            # the drafter pool mirrors the target pool's geometry and
            # shares NOTHING (no prefix cache claims drafter pages), so
            # spec-mode admission bills every request its FULL worst
            # case (no prefix discount — see _admittable) and both
            # pools are covered by the one bill; drafter writes are
            # bounded by the same per-row limits the verify honors
            self.draft_pool = self._build_pool(draft_cfg)
            if self.tp > 1:
                progs = _tp_spec_programs(
                    cfg, draft_cfg, self.mesh, k=self.spec_k,
                    sentinel=sentinel, donate=donate,
                    model_axis=self._model_axis,
                )
            else:
                progs = _spec_programs(
                    cfg, draft_cfg, k=self.spec_k, sentinel=sentinel,
                    donate=donate,
                )
            self._draft_k = progs["draft_k"]
            self._draft_k1 = progs["draft_k1"]
            self._verify = progs["verify"]
            self._draft_prefill = programs(draft_cfg, 0.0)[1]
            # greedy programs never consume randomness; the drafter
            # prefill still takes a key positionally
            self._zero_key = jax.random.PRNGKey(0)
        # analytic forward cost of one prompt token (the standard
        # 2·N_params estimate) — prices prefill_flops_saved
        self._flops_per_token = 2 * sum(
            int(np.prod(x.shape)) for x in jax.tree.leaves(params)
        )

        # elastic handoff state (PR 14): a draining replica admits
        # nothing new and runs its live slots to completion through the
        # ordinary release discipline; its unadmitted queue is handed
        # back to the replica set for re-admission elsewhere
        self.draining = False

        # host state
        self.queue: deque[Request] = deque()
        self.slots: list[Request | None] = [None] * max_slots
        # a host mirror of the pool's last_tok, filled from fetched
        # tokens; the speculative round uploads from it
        self._slot_last_tok: list[int] = [0] * max_slots
        # tokens each slot's dispatched passes have sampled that the host
        # has not fetched and emitted yet (0, 1 or, for the moment between
        # a tick's dispatch ahead and the fetch before it, 2)
        self._owed: list[int] = [0] * max_slots
        # the decode tick dispatched ahead and not fetched yet
        self._in_flight: _Tick | None = None
        # table entries a prompt can reach: what a pass hands back a row
        self._prompt_entries = min(
            self.pages_per_seq, -(-max_prompt_len // page_len)
        )
        self._reserved: list[int] = [0] * max_slots  # pages per slot
        self._release_mask: list[bool] = [False] * max_slots
        # pages a completed slot still holds on device until the next
        # release flush — part of the exact free-mask mirror
        self._pending_pages: list[int] = [0] * max_slots
        # prefix-cache mirrors: pages each live slot shares by
        # reference (adopted full prefix pages) and pages the cache
        # claimed OUT of the slot's own prompt at insert — both pin
        # their pages against eviction while the slot lives, and both
        # re-bucket the exact device-used mirror
        self._adopted_pages: list[list[int]] = [[] for _ in range(max_slots)]
        self._cached_pages: list[list[int]] = [[] for _ in range(max_slots)]
        # spec: committed tokens the drafter has not appended yet (the
        # last committed token; plus, after a fully-accepted round, the
        # final draft it sampled but never wrote) — at most 2
        self._pending: list[list[int]] = [[] for _ in range(max_slots)]
        self._t0 = time.perf_counter()
        self._vtime = 0.0
        self._ticks = 0
        self._prefills = 0
        self._spec_rounds = 0
        self._draft_steps = 0  # drafter scan steps actually charged
        self._next_rid = 0
        # telemetry
        self.admitted = 0
        self.completed = 0
        self.rejected: dict[str, int] = {}
        self.generated_tokens = 0
        self.pool_ok_failures = 0
        self.peak_pages = 0
        # prefill work a radix hit skipped (tokens of admitted prompts
        # not run through the model; FLOPs priced at 2·N_params/token)
        self.prefill_tokens_saved = 0
        self.prefill_flops_saved = 0
        # speculative counters: proposals = spec_k per live slot per
        # round; accepted = draft-origin tokens actually EMITTED
        self.draft_tokens_proposed = 0
        self.draft_tokens_accepted = 0
        # accepted-prefix length -> round count (k+2 keys at most) —
        # the accept histogram serve.json renders; coverage of 0 /
        # mid / k is what the bitwise pins assert they exercised
        self.spec_accept_counts: dict[int, int] = {}
        # bounded host series (PR 16): a soak run's memory no longer
        # grows with requests; counts/peaks stay exact via the summary
        self.queue_depths = Reservoir()
        self.ttft_s = Reservoir()
        self.tick_wall_s = Reservoir()
        # per-request (queue_wait, prefill, first_decode) triples on
        # the engine clock — the TTFT decomposition telemetry.serve
        # and serve_report render
        self.ttft_decomp = Reservoir()
        self.done: list[Request] = []
        # cumulative generated-token timeline [(t, tokens)], one point
        # per scheduler iteration — lets the continuous-vs-static A/B
        # evaluate "tokens delivered by time B" for ANY budget B from a
        # single drain run instead of re-running per candidate budget
        self.token_log: list[tuple[float, int]] = []
        # graft-mem (PR 17): the per-engine memory observatory.
        # Construction is free; sampling gates on memscope.enabled()
        # AND a trace label (A/B arms stay silent), so disabled runs
        # are bitwise identical (pinned in tests/test_memscope.py)
        self.memscope = _memscope.MemScope(
            label=trace_label or "serve"
        )
        # the last rid seated in each device slot — how a drain-time
        # pool residue is NAMED (memscope.pool_leak_check attribution)
        self._slot_last_rid: list[int | None] = [None] * max_slots
        self.mem_leak: dict[str, Any] | None = None
        # graft-race (PR 19): DDL25_SANITIZE=1 asserts the host<->
        # device page mirror at every step boundary (a device sync —
        # debug mode only).  Resolved once, through the sanctioned
        # boundary; off means not a single extra instruction on the
        # step path (pinned byte-identical in tests/test_host_safety).
        self._sanitize = _sanitizer.enabled()

    # ---- sharding ------------------------------------------------------

    def _build_pool(self, cfg) -> dict:
        """An empty pool of this engine's geometry for ``cfg``'s model (the
        target's or the drafter's), under the span ``serve.pool`` whose
        stats split its bytes: ``bytes_planes`` grow with the pages,
        ``bytes_state`` with the slots.  Placed on the engine's mesh (each
        plane split as its model says, accounting replicated) — identity
        at tp=1, so the single-device path never touches sharding APIs.
        The drafter's pool has the target's planes (an early exit of the
        same model), so one set of specs places both."""
        with self._span("serve.pool") as span:
            pool = kv_pages.init_page_pool(
                cfg, n_pages=self.n_pages, page_len=self.page_len,
                max_slots=self.max_slots, pages_per_seq=self.pages_per_seq,
            )
            span.add(**{
                f"bytes_{part}": sum(
                    self._leaf_bytes(x, False) for x in of(pool).values()
                )
                for part, of in (("planes", kv_pages.planes),
                                 ("state", kv_pages.slot_state))
            })
        if self.mesh is None:
            return pool
        from jax.sharding import NamedSharding

        specs = _tp_pool_specs(self.cfg, self._model_axis)
        return {
            k: jax.device_put(v, NamedSharding(self.mesh, specs[k]))
            for k, v in pool.items()
        }

    # ---- time ----------------------------------------------------------

    def now(self) -> float:
        if self.clock == "virtual":
            return self._vtime
        return time.perf_counter() - self._t0

    def _tl(self, kind: str, **fields) -> None:
        """One graft-trace timeline event on this engine's track.
        Host-side only — never consumes RNG, never advances a clock —
        and a no-op unless obs is enabled AND the engine is labelled,
        so disabled runs stay bitwise identical (pinned)."""
        if self.trace_label is None or not _obs_state.enabled():
            return
        _timeline.emit(
            kind, vt=self.now(), engine=self.trace_label,
            replica=self.replica_id, **fields,
        )

    def _span(self, name: str, **stats):
        """A scheduler span (``obs.spans.span``: always in a profiler
        trace and in the ring of its name), keyed by ``_obs_key``."""
        return _spans.span(name + self._obs_key, cat="serve", **stats)

    @contextlib.contextmanager
    def _accounting_span(self, name: str, **stats):
        """:meth:`_span` with the late stat ``account_ops``: how many
        accounting programs (:meth:`_account`) were dispatched inside."""
        before = self._account_ops
        with self._span(name, **stats) as span:
            yield span
            span.add(account_ops=self._account_ops - before)

    def _sample(self, name: str, value: float, t: float) -> None:
        _counters.sample(name + self._obs_key, value, t)

    def _split_pass(self, span, fetched, n: int, t: float, entries: int = 0):
        """Split a pass's fetched vector (:func:`_pack_pass`) into its
        ``n`` tokens, the probe ``[n, logit_probe]`` of the rows they were
        sampled from (``None`` where none is kept), the rows' first
        ``entries`` table entries (``None`` for none: a tick) and the pool
        flag, which are returned, and what the model's blocks counted of
        the pass (nothing for a model that counts nothing), which goes
        through the model's ``pass_stats``: each sampled number into the
        ring ``serve.<name>`` stamped at the pass's dispatch ``t``, and
        every number a stat of the pass's span under the name's last
        part."""
        k = self.logit_probe
        ok = bool(fetched[-1])
        tokens, rest = fetched[:n], fetched[n:-1]
        probe = rest[: n * k].view(np.float32).reshape(n, k) if k else None
        rest = rest[n * k:]
        table = rest[: n * entries].reshape(n, entries) if entries else None
        counts = rest[n * entries:]
        if counts.size:
            sampled, stats = self._model.pass_stats(
                counts.reshape(self._model.n_layers, -1)
            )
            for name, value in sampled.items():
                self._sample(f"serve.{name}", value, t)
            span.add(**{
                name.rsplit(".", 1)[-1]: v
                for name, v in {**stats, **sampled}.items()
            })
        return tokens, probe, table, ok

    def _tick_counts(self, t: float | None = None) -> dict[str, int]:
        """The counts a decode pass starts with, from host state alone
        (no device sync), as the pass's span stats.  The two that a
        reader windows (the benchmark's ``slot_occupancy_pct`` and
        ``kv_gather_live_pct``) are also sampled, under one stamp ``t``
        (now, unless given), into the rings ``serve.active_slots`` and
        ``serve.kv_live_positions``; a slot's live positions are the ones
        this pass attends to, its own write included: ``prompt +
        generated``, where generated counts the tokens a pass still in
        flight owes the slot.  ``pages_used`` walks every slot, so it is
        counted only where a trace will show it."""
        active = live = 0
        for slot, req in enumerate(self.slots):
            if req is not None:
                active += 1
                live += req.prompt_len + len(req.tokens) + self._owed[slot]
        if t is None:
            t = time.perf_counter()
        self._sample("serve.active_slots", active, t)
        self._sample("serve.kv_live_positions", live, t)
        counts = {
            "active": active, "queue": len(self.queue),
            "kv_live_positions": live,
            "kv_gathered_positions": self.max_slots * self.max_seq_len,
        }
        if _spans.watched():
            counts["pages_used"] = self._host_pages_used()
        return counts

    def warmup(self) -> None:
        """Compile all three programs (prefill at every shape of
        :func:`pass_shapes`, decode tick, release) before the clock
        starts, then reset every piece of host state
        and telemetry: a serving bench must not bill XLA compile time
        as the first requests' TTFT.  The jitted wrappers persist, so
        the warmed compiles are reused; the pool is rebuilt fresh.

        Admission knobs and EOS are suspended for the probe request:
        an ``eos_id`` that matches the probe's greedy sample (or a tiny
        ``token_budget``) would otherwise end the warmup before the
        decode tick ever compiled, silently putting XLA back on the
        first real request's TTFT clock."""
        saved_eos, saved_budget = self.eos_id, self.token_budget
        self.eos_id, self.token_budget = None, None
        # the compile probe is not traffic: keep it off the timeline
        saved_label, self.trace_label = self.trace_label, None
        try:
            req = self.make_request([1], 2)  # 2nd token needs a decode tick
            if self.submit(req) is not None:
                import warnings

                warnings.warn(
                    "serve warmup probe rejected "
                    f"({list(self.rejected)}); the first real request "
                    "will pay XLA compile time",
                    stacklevel=2,
                )
            for _ in range(8):
                if not self.step():
                    break
        finally:
            self.eos_id, self.token_budget = saved_eos, saved_budget
            self.trace_label = saved_label
        # the probe's pool goes before the fresh one is made, so that two
        # pools never stand side by side (the drafter's likewise, below)
        self.pool = None
        self.pool = self._build_pool(self.cfg)
        self.queue.clear()
        self.slots = [None] * self.max_slots
        self._slot_last_tok = [0] * self.max_slots
        self._owed = [0] * self.max_slots
        self._reserved = [0] * self.max_slots
        self._release_mask = [False] * self.max_slots
        self._pending_pages = [0] * self.max_slots
        self._adopted_pages = [[] for _ in range(self.max_slots)]
        self._cached_pages = [[] for _ in range(self.max_slots)]
        self._pending = [[] for _ in range(self.max_slots)]
        if self.spec_k:
            self.draft_pool = None
            # the probe round compiled the drafter prefill, the common
            # k-step draft variant, verify, and both pools' truncate;
            # the (k+1)-step catch-up variant only runs after a fully-
            # accepted round — warm it on a scratch pool (all-padding
            # args: active is all False, nothing mutates) so the first
            # full accept mid-run never pays XLA on the wall clock
            scratch = self._build_pool(self.draft_cfg)
            self._draft_k1(
                self.draft_params, scratch,
                jnp.zeros((self.max_slots, 2), jnp.int32),
                jnp.zeros((self.max_slots,), jnp.int32),
                jnp.zeros((self.max_slots,), jnp.int32),
            )
            self.draft_pool = self._build_pool(self.draft_cfg)
        if self.prefix is not None:  # drop the probe's cached prompt
            self.prefix = PrefixCache(self.page_len)
            # compile the sharing ops at the exact shapes the engine
            # calls them with (all-padding args: no state mutates) —
            # otherwise the FIRST radix hit pays the _adopt compile as
            # TTFT (observed: one 300 ms outlier in an all-4 ms run)
            self.pool = self._account(_ref, self.pool, jnp.full(
                (self.pages_per_seq * self.prefill_batch,), -1, jnp.int32
            ))
            self.pool = self._account(_unref, self.pool, jnp.full(
                (self.n_pages,), -1, jnp.int32
            ))
            self.pool, _ok = self._adopt(
                self.pool,
                jnp.full((self.prefill_batch,), -1, jnp.int32),
                jnp.full(
                    (self.prefill_batch, self.pages_per_seq), -1,
                    jnp.int32,
                ),
                jnp.full((self.prefill_batch,), -1, jnp.int32),
            )
        # run every pass shape once (all-padding batch: each write
        # trash-routes, the pool keeps its state), so that no pass
        # compiles on the clock
        for rows, width in self._pass_shapes:
            zeros = jnp.zeros((rows,), jnp.int32)
            pad = (jnp.zeros((rows, width), jnp.int32), zeros, zeros,
                   jnp.full((rows,), -1, jnp.int32), jax.random.PRNGKey(0))
            self.pool, _packed, _key = self._prefill(
                self.params, self.pool, *pad
            )
            if self.spec_k:
                self.draft_pool, _packed, _key = self._draft_prefill(
                    self.draft_params, self.draft_pool, *pad
                )
        jax.block_until_ready(self.pool["seq_len"])
        self._vtime = 0.0
        self._ticks = self._prefills = 0
        self._spec_rounds = self._draft_steps = 0
        self.draft_tokens_proposed = self.draft_tokens_accepted = 0
        self.spec_accept_counts = {}
        self.admitted = self.completed = self.generated_tokens = 0
        self.rejected = {}
        self.pool_ok_failures = 0
        self.peak_pages = 0
        self.prefill_tokens_saved = self.prefill_flops_saved = 0
        self.queue_depths.clear()
        self.ttft_s.clear()
        self.tick_wall_s.clear()
        self.ttft_decomp.clear()
        self.done, self.token_log = [], []
        self.memscope.reset()
        self._slot_last_rid = [None] * self.max_slots
        self.mem_leak = None
        self._t0 = time.perf_counter()

    def _advance(self, dt: float) -> None:
        if self.clock == "virtual":
            self._vtime += dt

    # ---- admission -----------------------------------------------------

    def _pages_needed(self, req: Request) -> int:
        return -(-(req.prompt_len + req.max_new_tokens) // self.page_len)

    def _reserved_total(self) -> int:
        return sum(self._reserved)

    def make_request(self, prompt, max_new_tokens: int,
                     arrival_t: float | None = None) -> Request:
        rid = self._next_rid
        self._next_rid += 1
        return Request(
            rid=rid, prompt=list(map(int, prompt)),
            max_new_tokens=int(max_new_tokens),
            arrival_t=self.now() if arrival_t is None else arrival_t,
        )

    def submit(self, req: Request) -> str | None:
        """Admission control at the door.  Returns None on acceptance
        (queued), else the rejection reason (also counted)."""
        self._tl(
            "serve_submit", rid=req.rid, prompt_len=req.prompt_len,
            max_new=req.max_new_tokens,
            arrival_t=round(req.arrival_t, 6),
        )
        reason = None
        total = req.prompt_len + req.max_new_tokens
        if self.draining:
            # a draining replica must never accumulate work it will
            # not admit — the replica set routes around it, and a
            # direct submit bounces with its own reason
            reason = REJECT_DRAINING
        elif req.prompt_len < 1 or req.max_new_tokens < 1:
            # an empty prompt would decode from the zero-initialized
            # logits buffer (a token the model never produced); reject
            # at the door rather than serve garbage
            reason = REJECT_BAD_REQUEST
        elif req.prompt_len > self.max_prompt_len:
            # over the prefill program's STATIC prompt capacity: no
            # compiled program of this engine can ever run it, so it is
            # a malformed request for this build — bad_request, not the
            # policy-capacity too_long it used to be conflated with
            # (too_long means "well-formed but over the context budget";
            # lumping shape-impossible prompts in skewed that counter)
            reason = REJECT_BAD_REQUEST
        elif total > self.max_seq_len:
            reason = REJECT_TOO_LONG
        elif self._pages_needed(req) > self.n_pages:
            reason = REJECT_POOL_EXHAUSTED
        elif len(self.queue) >= self.max_queue:
            reason = REJECT_QUEUE_FULL
        elif self.token_budget is not None and (
            sum(r.prompt_len + r.max_new_tokens for r in self.queue)
            + total > self.token_budget
        ):
            reason = REJECT_TOKEN_BUDGET
        if reason is not None:
            self.rejected[reason] = self.rejected.get(reason, 0) + 1
            self._tl("serve_reject", rid=req.rid, reason=reason)
            return reason
        self.queue.append(req)
        return None

    def _free_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.slots) if r is None]

    def _committed_pages(self) -> int:
        """Worst-case pages spoken for: live-slot reservations (fresh
        pages only — adopted prefix pages are billed once, under the
        cache) plus every page the prefix cache holds."""
        held = self.prefix.held_pages if self.prefix is not None else 0
        return self._reserved_total() + held

    def _pinned_pages(self) -> set[int]:
        """Cached pages eviction must not touch: shared into a live
        slot's table (adopted) or claimed out of one (own prompt pages
        the cache indexed).  Flush clears both lists, so a completed
        slot stops pinning exactly when the device release runs."""
        pinned: set[int] = set()
        for pages in self._adopted_pages:
            pinned.update(pages)
        for pages in self._cached_pages:
            pinned.update(pages)
        return pinned

    def _evict_for(self, shortfall: int, protect: set[int]) -> int:
        """LRU-evict cached pages to free ``shortfall`` pool pages.
        Returns how many were actually freed (0 when the evictable set
        is too small — the caller backpressures like any other
        page-short admission)."""
        assert self.prefix is not None
        pinned = self._pinned_pages() | protect
        if self.prefix.evictable_pages(pinned) < shortfall:
            return 0
        evicted = self.prefix.evict(shortfall, pinned)
        if evicted:
            pages = np.full((self.n_pages,), -1, np.int32)
            pages[: len(evicted)] = evicted
            self.pool = self._account(_unref, self.pool, jnp.asarray(pages))
        return len(evicted)

    def _match(self, req: Request) -> Match:
        if self.prefix is None:
            return Match()
        return self.prefix.match(req.prompt)

    def _may_admit(self) -> bool:
        """Whether :meth:`_admittable` could admit a request now, read
        from counts alone (no match, no eviction, no state touched).
        Exact without the prefix cache; with it, a queued request that
        finds a free slot counts as admittable whatever its pages, since
        a match or an eviction may make room."""
        if self.draining or not self.queue or not self._free_slots():
            return False  # draining: an elastic scale-down admits none
        if self.admission == "static" and any(
            r is not None for r in self.slots
        ):
            return False  # static batching: wait for the batch to drain
        if self.prefix is not None:
            return True
        return (self._pages_needed(self.queue[0])
                <= self.n_pages - self._committed_pages())

    def _admittable(self) -> list[tuple[int, Request, Match]]:
        """(slot, request, prefix-match) triples the scheduler can
        admit right now: bounded by free slots, the prefill batch
        width, and the pool's uncommitted pages (worst-case accounting
        counts only the SUFFIX pages of a matched request — the
        adopted prefix is already resident).  When the free set is
        short, LRU eviction of unpinned cached pages runs before
        backpressure."""
        if not self._may_admit():
            return []
        free = self._free_slots()
        budget = self.n_pages - self._committed_pages()
        out: list[tuple[int, Request, Match]] = []
        protect: set[int] = set()
        while (self.queue and free
               and len(out) < self.prefill_batch):
            m = self._match(self.queue[0])
            # with speculation on, the prefix discount is forfeit at
            # the ADMISSION bill (the adoption itself — and the prefill
            # compute it saves — still happens): the drafter pool has
            # the same n_pages but shares nothing, so a slot costs it
            # the FULL worst case; billing the target's discounted need
            # would admit loads the drafter pool cannot hold (observed:
            # drafter reserve_pages exhaustion under a tight pool with
            # repeated prompts).  Since the target's true commitment is
            # <= the full bill + the cache's held pages, one
            # conservative bill covers both pools.
            need = self._pages_needed(self.queue[0]) - (
                0 if self.spec_k else m.n_ref
            )
            if need > budget:
                if self.prefix is None:
                    break  # head-of-line blocks until pages free
                got = self._evict_for(
                    need - budget,
                    protect | set(m.pages)
                    | ({m.cow_src} if m.cow_src >= 0 else set()),
                )
                if got < need - budget:
                    break  # backpressure: nothing evictable enough
                budget += got
            req = self.queue.popleft()
            slot = free.pop(0)
            budget -= need
            protect.update(m.pages)
            if m.cow_src >= 0:
                protect.add(m.cow_src)
            out.append((slot, req, m))
        return out

    # ---- the scheduler iteration --------------------------------------

    def _runs_ahead(self) -> bool:
        """Whether the next decode tick may go to the device before the
        program in flight is fetched: the host can already tell that the
        next step would dispatch exactly it.  That holds on the wall clock
        with no speculation and no ``eos_id`` (a slot ends only at its
        ``max_new_tokens``, which the counts tell), when no live slot
        reaches that count with the tokens owed it and nothing queued is
        admittable.  See the class docstring."""
        if self.clock != "wall" or self.spec_k or self.eos_id is not None:
            return False
        if any(
            req is not None
            and len(req.tokens) + self._owed[slot] >= req.max_new_tokens
            for slot, req in enumerate(self.slots)
        ):
            return False
        return not self._may_admit()

    def _dispatch_tick(self, ahead: bool, t_start: float | None = None) -> _Tick:
        """Dispatch one decode tick over every live slot and return it
        unfetched.  Its counts are taken, and its rings sampled (the ring
        ``serve.tick_ahead`` among them: 1 where it goes ahead of the
        previous program's fetch), under one stamp, at its dispatch."""
        t = time.perf_counter()
        counts = self._tick_counts(t)
        self._sample("serve.tick_ahead", int(ahead), t)
        rows = [(slot, req) for slot, req in enumerate(self.slots)
                if req is not None]
        self.pool, packed, self._key = self._tick(
            self.params, self.pool, self._key
        )
        for slot, _req in rows:
            self._owed[slot] += 1
        return _Tick(packed, rows, counts, t, ahead, t_start)

    def _adopt_batch(self, batch: list[tuple[int, Request, Match]]) -> None:
        """Seat every matched prefix before the suffix prefill: full
        pages by reference, the partial tail page as a COW copy
        (``kv_pages.adopt_prefix``) — and bill the adopted pages to the
        host mirror in the same breath (graft-race S204: the device
        refcount bump and its host twin must not live in different
        methods)."""
        for slot, _req, m in batch:
            self._adopted_pages[slot] = list(m.pages)
        if not any(m.matched for _, _, m in batch):
            return
        B = self.prefill_batch
        slots = np.full((B,), -1, np.int32)
        adopt = np.full((B, self.pages_per_seq), -1, np.int32)
        cow = np.full((B,), -1, np.int32)
        for row, (slot, _req, m) in enumerate(batch):
            slots[row] = slot
            adopt[row, : m.n_ref] = m.pages
            cow[row] = m.cow_src
        self.pool, ok = self._adopt(
            self.pool, jnp.asarray(slots), jnp.asarray(adopt),
            jnp.asarray(cow),
        )
        if not bool(ok):
            self.pool_ok_failures += 1

    def _insert_prefixes(
        self, batch: list[tuple[int, Request, Match]], table
    ) -> None:
        """Index the just-prefilled prompts in the radix tree and take
        the cache's device references on every NEWLY claimed page.
        ``table [rows, E]`` is what the pass handed back of its rows'
        page tables, fetched with its tokens.  Pages the cache claims
        move from the slot's bill to the cache's (``_committed_pages``
        stays exact); a slot that completed during this very prefill
        re-buckets its pending mirror instead."""
        assert self.prefix is not None
        claimed: list[int] = []
        for row, (slot, req, _m) in enumerate(batch):
            new_pages = self.prefix.insert(req.prompt, table[row])
            claimed.extend(new_pages)
            self._cached_pages[slot] = new_pages
            n_new = len(new_pages)
            if self.slots[slot] is None:  # completed at its first token
                self._pending_pages[slot] = max(
                    0, self._pending_pages[slot] - n_new
                )
            else:
                self._reserved[slot] = max(0, self._reserved[slot] - n_new)
        if claimed:
            width = self.pages_per_seq * self.prefill_batch
            pages = np.full((width,), -1, np.int32)
            pages[: len(claimed)] = claimed
            self.pool = self._account(_ref, self.pool, jnp.asarray(pages))

    def _seat(self, batch: list[tuple[int, Request, Match]]) -> None:
        """The host's half of admitting ``batch``, which needs none of
        its tokens: each request takes its slot, its admission bill and
        the one token its prompt pass owes it, and the prefix counters
        count it.  Done as the pass is dispatched, so that a tick
        dispatched ahead of the pass's fetch sees the rows."""
        for _row, (slot, req, m) in enumerate(batch):
            self.slots[slot] = req
            self._owed[slot] = 1
            self._slot_last_rid[slot] = req.rid
            # _adopted_pages[slot] was billed by _adopt_batch (S204: same
            # method as the device refcount bump)
            self._cached_pages[slot] = []
            # mirror of the admission bill: full worst case under spec
            # (the drafter pool's share-less need), discounted otherwise
            self._reserved[slot] = self._pages_needed(req) - (
                0 if self.spec_k else m.n_ref
            )
            self.admitted += 1
            if self.prefix is not None:
                self.prefix.lookups += 1
                if m.matched > 0:
                    self.prefix.hits += 1
                    self.prefix.hit_tokens += m.matched
            # saved = the matched positions: the pass computes none
            self.prefill_tokens_saved += m.matched
            self.prefill_flops_saved += m.matched * self._flops_per_token

    def _run_prefill(self, batch: list[tuple[int, Request, Match]]) -> None:
        from ddl25spring_tpu.obs import flight

        # each row carries its UNMATCHED suffix from column 0, and the
        # pass takes the ladder's cheapest shape that holds the batch's
        # rows and its longest suffix: a request admitted alone is not
        # padded to prefill_batch rows, a radix hit rides a narrower pass
        B, width = shape_for(
            self._pass_shapes, len(batch),
            max(req.prompt_len - m.matched for _, req, m in batch),
        )
        prompts = np.zeros((B, width), np.int32)
        lens = np.zeros((B,), np.int32)
        starts = np.zeros((B,), np.int32)
        slot_ids = np.full((B,), -1, np.int32)
        for row, (slot, req, m) in enumerate(batch):
            prompts[row, : req.prompt_len - m.matched] = req.prompt[m.matched:]
            lens[row] = req.prompt_len
            starts[row] = m.matched
            slot_ids[row] = slot
        # TTFT decomposition stamp: the engine-clock moment this batch
        # left the queue for the device — everything before is
        # queue-wait, everything from here to the prefill cost is
        # prefill, the residual to first_token is first-decode
        t_pre = self.now()
        for slot, req, m in batch:
            self._tl("serve_admit", rid=req.rid, slot=slot)
        self._adopt_batch(batch)
        # what the pass does and what it could have done: the prompt
        # positions it writes against the positions it computes
        counts = {
            "rows": len(batch), "pass_rows": B, "width": width,
            "prompt_tokens": int(lens.sum() - starts.sum()),
            "scanned_positions": B * width,
        }
        # (the two that the benchmark's prefill_fill_pct reader sums)
        t0 = time.perf_counter()
        for key in ("prompt_tokens", "scanned_positions"):
            self._sample(f"serve.prefill.{key}", counts[key], t0)
        # what the model counts of the pass from the rows' lengths alone,
        # and the rows whose slot state the pass seats (late stats below)
        seated: dict[str, int] = {}
        if self._model.prompt_pass_counts is not None:
            seated = dict(self._model.prompt_pass_counts(
                lens[: len(batch)] - starts[: len(batch)], B, width
            ))
            for name, value in seated.items():
                self._sample(f"serve.{name}", value, t0)
        if self._model.slot_state:
            seated["state_rows"] = len(batch)
        with self._span(
            "serve.prefill", **counts,
            rids=" ".join(str(req.rid) for _, req, _ in batch),
        ) as span:
            self.pool, packed, self._key = self._prefill(
                self.params, self.pool, jnp.asarray(prompts),
                jnp.asarray(lens), jnp.asarray(starts),
                jnp.asarray(slot_ids), self._key,
            )
            self._seat(batch)
            if self._runs_ahead():
                # the first tokens are the tick's inputs, on the device
                self._in_flight = self._dispatch_tick(ahead=True)
            # one fetch: the sampled tokens and what rides behind them
            first, probe, table, ok = self._split_pass(
                span, jax.device_get(packed), B, t0, self._prompt_entries
            )
            span.add(**{
                name.rsplit(".", 1)[-1]: v for name, v in seated.items()
            })
        if self._in_flight is not None:
            self._in_flight.t_start = time.perf_counter()
        if not ok:
            self.pool_ok_failures += 1
        if self.spec_k:
            # the drafter prefills its OWN pool over the same batch,
            # whole prompts from position 0 (the radix cache shares
            # target pages only, so a matched prefix saves no drafter
            # work); its sampled token is discarded (the target's
            # `first` is the committed stream).  Greedy: the key is
            # never consumed, so the engine's key stream — and with it
            # the spec-off bitwise twin — is untouched.
            d_rows, d_width = shape_for(
                self._pass_shapes, len(batch), int(lens.max())
            )
            whole = np.zeros((d_rows, d_width), np.int32)
            for row, (_slot, req, _m) in enumerate(batch):
                whole[row, : req.prompt_len] = req.prompt
            with self._span("serve.draft_prefill", rows=len(batch),
                            pass_rows=d_rows, width=d_width):
                self.draft_pool, drafted, _key = self._draft_prefill(
                    self.draft_params, self.draft_pool,
                    jnp.asarray(whole),
                    jnp.asarray(lens), jnp.zeros((d_rows,), jnp.int32),
                    jnp.asarray(slot_ids), self._zero_key,
                )
                # its pool flag rides last in its vector
                if not np.asarray(jax.device_get(drafted))[-1]:
                    self.pool_ok_failures += 1
        wall = time.perf_counter() - t0
        self._prefills += 1
        # the virtual clock charges a pass by its width (a full-width
        # pass one tick): a batch of radix hits rides a narrower pass
        # and costs proportionally less — the deterministic half of the
        # cached-vs-cold A/B (the wall clock measures the same saving,
        # noisily)
        charge = self.tick_s * width / self.max_prompt_len
        self._advance(charge)
        if self.spec_k:
            # the drafter's full-prompt pass, at its FLOP ratio
            self._advance(
                self.tick_s * self.spec_flop_ratio
                * d_width / self.max_prompt_len
            )
        now = self.now()
        # what THIS prefill pass cost on the engine clock — the middle
        # term of the TTFT decomposition.  Virtual: the target pass's
        # deterministic charge (the drafter's charge lands in the
        # first-decode residual).  Wall: the measured device wall of
        # the pass (host overhead lands in the residual).
        prefill_cost = charge if self.clock == "virtual" else wall
        with self._accounting_span("serve.emit"):
            for row, (slot, req, m) in enumerate(batch):
                req.admitted_t = now
                req.prefill_start_t = t_pre
                req.prefill_s = prefill_cost
                # the drafter owes this first committed token its KV; a
                # request that completes at this very token is released by
                # the flush, which clears the pending list with the slot
                self._pending[slot] = [int(first[row])]
                req.first_token_t = now
                ttft = now - req.arrival_t
                self.ttft_s.append(ttft)
                # TTFT == queue_wait + prefill + first_decode by
                # construction: the residual definition makes the virtual
                # sum exact (pinned) and the wall sum exact up to float
                # re-association
                queue_wait = t_pre - req.arrival_t
                first_decode = now - t_pre - prefill_cost
                self.ttft_decomp.append((queue_wait, prefill_cost,
                                         first_decode))
                self._tl(
                    "serve_prefill", rid=req.rid, slot=slot, width=width,
                    prefix_hit_tokens=int(m.matched),
                    wall_s=round(wall, 6),
                )
                self._tl(
                    "serve_first_token", rid=req.rid,
                    ttft_s=round(ttft, 6),
                    queue_wait_s=round(queue_wait, 6),
                    prefill_s=round(prefill_cost, 6),
                    first_decode_s=round(first_decode, 6),
                )
                self._owed[slot] -= 1
                self._emit_token(slot, req, int(first[row]), now,
                                 None if probe is None else probe[row])
            if self.prefix is not None:
                self._insert_prefixes(batch, table)
            self._track_pages()
        flight.record(
            kind="serve_prefill", step=self._prefills, wall_s=round(wall, 6),
            admitted=len(batch), queue=len(self.queue), width=width,
        )

    def _emit_token(self, slot: int, req: Request, tok: int,
                    now: float, probe=None) -> None:
        req.tokens.append(tok)
        if probe is not None:
            req.tokens.probe.append(probe)
        self._slot_last_tok[slot] = tok
        self.generated_tokens += 1
        if (len(req.tokens) >= req.max_new_tokens
                or (self.eos_id is not None and tok == self.eos_id)):
            req.done_t = now
            self.completed += 1
            self.done.append(req)
            self._tl("serve_done", rid=req.rid, tokens=len(req.tokens))
            self.slots[slot] = None
            self._reserved[slot] = 0
            self._release_mask[slot] = True
            # the device keeps this sequence's pages until the release
            # flush; mirror them so peak accounting can't miss a
            # request that completed the same iteration it prefilled.
            # Only the slot's EXCLUSIVE pages count here — adopted and
            # cache-claimed pages are billed once, under the cache.
            written = req.prompt_len + len(req.tokens) - 1
            self._pending_pages[slot] = self._slot_fresh_pages(
                slot, written
            )

    def _run_decode_tick(self) -> None:
        """Land one decode tick: the one dispatched ahead, else a fresh
        one.  Before its fetch, the next tick goes to the device wherever
        :meth:`_runs_ahead` allows, so that the device runs it while the
        host fetches and emits this one's tokens.  A tick's wall sample
        runs from its dispatch to its fetch, or for a tick dispatched
        ahead from the previous program's fetch to its own: the interval
        between two results a client sees."""
        from ddl25spring_tpu.obs import flight

        t_enter = time.perf_counter()
        with self._span("serve.decode_tick") as span:
            tick = self._in_flight or self._dispatch_tick(
                ahead=False, t_start=t_enter
            )
            span.add(**tick.counts, ahead=int(tick.ahead))
            self._in_flight = None
            if self._runs_ahead():
                self._in_flight = self._dispatch_tick(ahead=True)
            new_tok, probe, _table, ok = self._split_pass(
                span, jax.device_get(tick.packed), self.max_slots,
                tick.t_dispatch,
            )
        t_fetch = time.perf_counter()
        if self._in_flight is not None:
            self._in_flight.t_start = t_fetch
        wall = t_fetch - tick.t_start
        if not ok:
            self.pool_ok_failures += 1
        self.tick_wall_s.append(wall)
        self._ticks += 1
        self._advance(self.tick_s)
        now = self.now()
        with self._span("serve.emit"):
            for slot, req in tick.rows:
                self._owed[slot] -= 1
                self._emit_token(slot, req, int(new_tok[slot]), now,
                                 None if probe is None else probe[slot])
            self._track_pages()
        if self._ticks % 8 == 0 or self._ticks <= 2:
            # active and queue: what the tick STARTED with, as its span
            flight.record(
                kind="serve_tick", step=self._ticks,
                wall_s=round(wall, 6), active=tick.counts["active"],
                queue=tick.counts["queue"],
                pages_used=self._host_pages_used(),
            )

    def _run_spec_round(self) -> None:
        """One speculative round over every active slot: the drafter
        proposes ``spec_k`` tokens (its own pool), ONE target verify
        pass scores all ``spec_k + 1`` positions, the accepted prefix
        commits — each accepted draft equals the target argmax, the
        first rejection is replaced by it, a full accept earns the
        bonus token — and both pools roll back to the committed
        frontier (``kv_pages.truncate_to``).  Greedy acceptance makes
        the emitted stream BITWISE the sequential engine's; the
        deterministic virtual clock charges 1 tick for the verify pass
        (one target weight stream) plus ``flop_ratio`` per drafter
        step, which is the whole speculative win."""
        from ddl25spring_tpu.obs import flight

        k = self.spec_k
        S = self.max_slots
        ctx = np.zeros((S, 2), np.int32)
        n_ctx = np.zeros((S,), np.int32)
        limits = np.zeros((S,), np.int32)
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            pend = self._pending[slot]
            assert 1 <= len(pend) <= 2, (slot, pend)
            ctx[slot, : len(pend)] = pend
            n_ctx[slot] = len(pend)
            # the last position a non-speculative decode would write
            # for this request — verify writes past it trash-route, so
            # speculation stays inside the admission-billed worst case
            limits[slot] = req.prompt_len + req.max_new_tokens - 1
        # the (k+1)-step draft variant only exists for 2-token catch-up
        # rounds (the round after a full accept); every other round
        # rides the cheaper k-step program — and the clock bills the
        # steps the chosen program actually ran
        steps = k + 1 if int(n_ctx.max(initial=0)) > 1 else k
        draft_fn = self._draft_k1 if steps == k + 1 else self._draft_k

        jlim = jnp.asarray(limits)
        t = time.perf_counter()
        counts = self._tick_counts(t)
        # a round is counted as a tick, and none goes ahead of a fetch
        self._sample("serve.tick_ahead", 0, t)
        t0 = time.perf_counter()
        with self._span("serve.draft", steps=steps, **counts):
            self.draft_pool, drafts_dev, ok_d = draft_fn(
                self.draft_params, self.draft_pool,
                jnp.asarray(ctx), jnp.asarray(n_ctx), jlim,
            )
        # assemble the verify window ON DEVICE: draft and verify queue
        # back to back with no host sync in between (one device_get of
        # the small draft/greedy arrays after both dispatched)
        toks = jnp.concatenate(
            [jnp.asarray(np.asarray(self._slot_last_tok, np.int32)
                         )[:, None], drafts_dev],
            axis=1,
        )
        with self._span("serve.verify"):
            self.pool, greedy_dev, ok_v = self._verify(
                self.params, self.pool, toks, jlim,
            )
            drafts = np.asarray(jax.device_get(drafts_dev))  # [S, k]
            greedy = np.asarray(jax.device_get(greedy_dev))  # [S, k+1]
        wall = time.perf_counter() - t0
        if not bool(ok_d):
            self.pool_ok_failures += 1
        if not bool(ok_v):
            self.pool_ok_failures += 1

        self.tick_wall_s.append(wall)
        self._spec_rounds += 1
        # a spec round IS the engine's decode-family pass: count it as
        # a tick (one target weight stream serving up to k+1 tokens) so
        # `ticks` and the virtual-clock per-pass latency stay defined on
        # speculative engines; the wall sample above likewise covers
        # the whole round — more tokens per sample, same pass
        self._ticks += 1
        self._draft_steps += steps
        self._advance(
            self.tick_s * (1.0 + steps * self.spec_flop_ratio)
        )
        now = self.now()

        with self._accounting_span("serve.emit"):
            new_lens = np.zeros((S,), np.int32)
            mask = np.zeros((S,), bool)
            for slot, req in enumerate(self.slots):
                if req is None:
                    continue
                mask[slot] = True
                self.draft_tokens_proposed += k
                # accepted prefix: draft i is the target's own choice iff
                # it equals greedy[i] (the argmax after consuming the
                # previous position)
                a = 0
                while a < k and drafts[slot, a] == greedy[slot, a]:
                    a += 1
                self.spec_accept_counts[a] = (
                    self.spec_accept_counts.get(a, 0) + 1
                )
                # committed token t0 sits at position p0; the round's
                # emissions extend the written frontier one position each
                p0 = req.prompt_len + len(req.tokens) - 1
                emitted = 0
                for j in range(a + 1):
                    self._emit_token(slot, req, int(greedy[slot, j]), now)
                    emitted += 1
                    if self.slots[slot] is None:
                        break  # max_new / EOS — inside the draft window
                # the first min(a, emitted) emissions are draft-origin
                self.draft_tokens_accepted += min(a, emitted)
                self._tl(
                    "serve_spec_round", rid=req.rid,
                    round=self._spec_rounds, accepted=a, rejected=k - a,
                    emitted=emitted,
                )
                new_lens[slot] = p0 + emitted
                if self.slots[slot] is not None:
                    if emitted == k + 1:
                        # full accept: the drafter never appended its own
                        # final draft, and the bonus token is new to it
                        self._pending[slot] = [
                            int(drafts[slot, k - 1]), int(greedy[slot, k]),
                        ]
                    else:
                        self._pending[slot] = [int(greedy[slot, emitted - 1])]
            # roll BOTH pools back to the committed frontier: rejected
            # positions' fresh pages return to the free set (refcount
            # decrement — the same discipline as release), stale values
            # inside kept pages are overwritten before they become readable
            jl = jnp.asarray(new_lens)
            jm = jnp.asarray(mask)
            self.pool = self._account(
                _truncate, self.pool, jl, jm, page_len=self.page_len
            )
            self.draft_pool = self._account(
                _truncate, self.draft_pool, jl, jm, page_len=self.page_len
            )
            self._track_pages()
        if self._spec_rounds % 8 == 0 or self._spec_rounds <= 2:
            flight.record(
                kind="serve_spec", step=self._spec_rounds,
                wall_s=round(wall, 6), active=counts["active"],
                draft_steps=steps,
                accepted=self.draft_tokens_accepted,
                proposed=self.draft_tokens_proposed,
                queue=counts["queue"], pages_used=self._host_pages_used(),
            )

    def _slot_fresh_pages(self, slot: int, written: int) -> int:
        """Pages slot ``slot`` holds EXCLUSIVELY after writing
        ``written`` positions: its table entries so far, minus the
        prefix pages it shares by reference and the own-prompt pages
        the cache claimed (both billed under the cache)."""
        entries = min(
            -(-written // self.page_len) if written > 0 else 0,
            self.pages_per_seq,
        )
        shared = len(self._adopted_pages[slot]) + len(
            self._cached_pages[slot]
        )
        return max(entries - shared, 0)

    def _host_pages_used(self) -> int:
        """Exact host mirror of the device free mask: pages a slot has
        actually allocated so far (grows lazily page by page) plus
        every page the prefix cache references.  The newest sampled
        token is NOT yet written — its KV lands during the next decode
        tick — so an active slot's written positions are
        ``prompt + generated - 1``; completed slots keep their pages
        until the release flush (``_pending_pages``)."""
        used = self.prefix.held_pages if self.prefix is not None else 0
        for slot, req in enumerate(self.slots):
            if req is None:
                used += self._pending_pages[slot]
                continue
            # a token a dispatched pass owes the slot counts as generated:
            # the device holds what that pass writes
            sampled = len(req.tokens) + self._owed[slot]
            written = req.prompt_len + max(sampled - 1, 0)
            used += self._slot_fresh_pages(slot, written)
        return used

    def _track_pages(self) -> None:
        self.peak_pages = max(self.peak_pages, self._host_pages_used())

    def _account(self, program, pool: dict, *args, **static) -> dict:
        """Dispatch an accounting ``program`` on ``pool``'s accounting
        arrays alone and merge what it returns into ``pool`` on the host:
        the planes of the result are the buffers they were."""
        self._account_ops += 1
        return {**pool, **program(kv_pages.accounting(pool), *args, **static)}

    def _flush_releases(self) -> None:
        if not any(self._release_mask):
            return
        with self._accounting_span(
            "serve.release", slots=sum(self._release_mask)
        ):
            mask = jnp.asarray(np.asarray(self._release_mask))
            self.pool = self._account(self._release, self.pool, mask)
            if self.spec_k:
                # the drafter's mirror slot returns its pages in the same
                # flush
                self.draft_pool = self._account(
                    self._release, self.draft_pool, mask
                )
            for slot, flushed in enumerate(self._release_mask):
                if flushed:  # the slot stops pinning its shared pages
                    self._adopted_pages[slot] = []
                    self._cached_pages[slot] = []
                    self._pending[slot] = []
            self._release_mask = [False] * self.max_slots
            self._pending_pages = [0] * self.max_slots

    # ---- elastic handoff (PR 14) ---------------------------------------

    def begin_drain(self) -> list[Request]:
        """Start an elastic scale-down of THIS replica: stop admitting
        (``_admittable`` returns nothing), pop every request still in
        the host queue and return it for re-admission on the surviving
        replicas.  Queued requests were never admitted — no tokens, no
        pages — so the handoff is a plain re-submit; the live slots
        keep decoding here until they complete through the ordinary
        release discipline (``drained`` flips true), at which point the
        replica's whole page pool goes away with it.  An
        accepted-then-lost request is therefore impossible by
        construction — the ``--check-reshape`` gate pins the count at
        zero anyway."""
        refuse_with_state(self.cfg, "the elastic hand-off (begin_drain)")
        self.draining = True
        handoff = list(self.queue)
        self.queue.clear()
        self._tl("serve_drain", requeued=len(handoff))
        return handoff

    @property
    def drained(self) -> bool:
        """True once a draining replica holds no live work: every slot
        released, nothing queued (the queue was handed off at
        ``begin_drain``; rejects-at-the-door keep it empty after) and no
        tick on the device whose tokens are not fetched."""
        return (all(r is None for r in self.slots) and not self.queue
                and self._in_flight is None)

    def step(self) -> bool:
        """One scheduler iteration: flush releases, admit + prefill,
        then one packed decode tick (the one a previous step dispatched
        ahead, where there is one: :meth:`_run_decode_tick`).  Returns
        True when any program ran (False = fully idle)."""
        with self._span("serve.step"):
            ran = False
            if self._in_flight is not None and self._may_admit():
                # an arrival met a tick dispatched ahead (an open loop):
                # land it (no other goes ahead while a request is
                # admittable) before the flush, which releases what it
                # completes, so that the pass has nothing unfetched ahead
                # of it and admission sees the pages as they are
                self._run_decode_tick()
                ran = True
            self._flush_releases()
            self.queue_depths.append(len(self.queue))
            with self._accounting_span("serve.admit", queue=len(self.queue)):
                batch = self._admittable()
            if batch:
                self._run_prefill(batch)
                ran = True
            # a request that completed DURING prefill (max_new=1 or an
            # eos first token) must not ride through the decode tick with
            # its device slot still active — it would write KV for a dead
            # sequence and could lazily allocate a page the admission
            # accounting and the host peak mirror never see
            self._flush_releases()
            if any(r is not None for r in self.slots):
                if self.spec_k:
                    self._run_spec_round()
                else:
                    self._run_decode_tick()
                ran = True
            self.token_log.append((self.now(), self.generated_tokens))
            self._mem_sample()
            if self._sanitize:  # graft-race: live S204 mirror assertion
                _sanitizer.check_serve_mirror(self)
        return ran

    # ---- graft-mem (PR 17) ---------------------------------------------

    def _mem_sample(self) -> None:
        """One memory observation per scheduler iteration: live bytes +
        host RSS into the scope's reservoirs, pool occupancy / queue
        depth / tokens-per-sec riding the timeline ``mem_sample`` event
        (the Perfetto counter tracks).  Pool occupancy reads the exact
        HOST mirror — no device sync on the tick path.  Gated exactly
        like :meth:`_tl`: no trace label (A/B arms) or obs off means
        nothing happens."""
        if self.trace_label is None or not _memscope.enabled():
            return
        wall = self.now()
        self.memscope.sample(
            self._ticks, vt=wall, engine=self.trace_label,
            replica=self.replica_id,
            pool_used=self._host_pages_used(),
            pool_pages=self.n_pages,
            queue_depth=len(self.queue),
            tokens_per_s=(
                round(self.generated_tokens / wall, 3) if wall > 0
                else 0.0
            ),
        )

    @staticmethod
    def _leaf_bytes(x, per_chip: bool) -> int:
        shape = x.shape
        if per_chip:
            try:  # one device's shard (== shape when replicated/tp=1)
                shape = x.sharding.shard_shape(x.shape)
            except Exception:  # noqa: BLE001 — uncommitted/host arrays
                pass
        return int(np.prod(shape)) * jnp.dtype(x.dtype).itemsize

    def _make_resident(self, model: PagedModel, params: Params,
                       name: str = "serve.resident") -> Params:
        """``model.resident(params)`` under the span ``name``, whose
        stats say whether anything was cast: ``leaves_cast`` (0 for a
        tree that arrives resident) and the bytes before and after."""
        def nbytes(tree) -> int:
            return sum(
                self._leaf_bytes(x, False) for x in jax.tree.leaves(tree)
            )

        with self._span(name) as span:
            out = model.resident(params)
            span.add(
                leaves_cast=sum(
                    a is not b for a, b in zip(
                        jax.tree.leaves(params), jax.tree.leaves(out)
                    )
                ),
                bytes_masters=nbytes(params), bytes_resident=nbytes(out),
            )
        return out

    def memory_bill(self, per_chip: bool = True) -> dict[str, Any]:
        """:meth:`mem_budget_bytes` by part: ``weights`` (the drafter's
        too under spec) as ``{dtype: bytes}`` of what the engine holds,
        the resident tree and no master, ``pool`` (``bytes_state`` of it
        the model's slot state) and ``total``."""
        weights: dict[str, int] = {}
        for t in [self.params] + ([self.draft_params] if self.spec_k else []):
            for x in jax.tree.leaves(t):
                name = jnp.dtype(x.dtype).name
                weights[name] = (
                    weights.get(name, 0) + self._leaf_bytes(x, per_chip)
                )
        pools = [self.pool] + ([self.draft_pool] if self.spec_k else [])
        pool = sum(
            self._leaf_bytes(x, per_chip)
            for t in pools for x in jax.tree.leaves(t)
        )
        return {"weights": weights, "pool": pool,
                # the part of ``pool`` that grows with the slots and not
                # with the pages: the model's slot state
                "bytes_state": sum(
                    self._leaf_bytes(x, per_chip)
                    for t in pools for x in kv_pages.slot_state(t).values()
                ),
                "total": sum(weights.values()) + pool}

    def mem_budget_bytes(self, per_chip: bool = True) -> int:
        """The engine's static memory bill: params + page pool (+ the
        drafter's params and pool under spec) — exact, from shapes,
        dtypes, and shardings.

        ``per_chip=True`` (the default, and the PR-18 gate) bills what
        ONE chip holds resident: sharded leaves count their shard
        (pool k/v and Megatron splits divide by tp; ZeRO-3 streamed
        block rows divide by tp), replicated leaves count whole.  At
        tp=1 the two modes are identical.  ``per_chip=False`` is the
        global-LOGICAL bill — the comparator for
        :func:`ddl25spring_tpu.obs.memscope.live_total_bytes`'s
        logical-bytes high-water (``mem_report --check``'s band), whose
        accounting is also logical-global.  The streamed one-layer
        working set is transient, not resident — it shows up in the
        compile-time peak-HBM budget the ``serve-decode-zero3stream``
        describe() pins, not here."""
        return self.memory_bill(per_chip)["total"]

    def mem_pool_snapshot(self) -> dict[str, Any]:
        """Device-mask pool telemetry (occupancy, cache-vs-table page
        split, refcount histogram, free-run fragmentation) — a small
        host transfer, for drain-time and report-time reads, not the
        tick path."""
        held = self.prefix.held_pages if self.prefix is not None else 0
        return _memscope.pool_snapshot(self.pool, cache_held=held)

    def mem_leak_check(self) -> dict[str, Any]:
        """The drain-time leak detector: flush any pending releases,
        then require the pool to hold EXACTLY its cache-held pages.
        Residue is attributed page by page (table row -> last rid) and
        fails ``mem_report --check``.  Meaningful when :attr:`drained`
        (or fully idle); the result is kept on :attr:`mem_leak` for the
        driver's mem record."""
        self._flush_releases()
        held = self.prefix.held_pages if self.prefix is not None else 0
        out = _memscope.pool_leak_check(
            self.pool, cache_held_pages=held,
            slot_rids=self._slot_last_rid,
        )
        if self.spec_k:
            draft = _memscope.pool_leak_check(
                self.draft_pool, cache_held_pages=0,
                slot_rids=self._slot_last_rid,
            )
            out["draft"] = draft
            out["ok"] = out["ok"] and draft["ok"]
            out["leaked_pages"] += draft["leaked_pages"]
        if not out["ok"]:
            # a leak is a flight violation too: post-mortems must see
            # it even when nothing reads mem.json
            from ddl25spring_tpu.obs.recorder import flight

            flight.record(
                kind="mem", source="kv_pool_leak",
                leaked_pages=out["leaked_pages"],
                leaks=out["leaks"][:8],
            )
        self.mem_leak = out
        return out

    def tokens_at(self, t: float) -> int:
        """Cumulative generated tokens delivered by time ``t`` (engine
        clock) — the A/B's fixed-budget readout."""
        out = 0
        for when, n in self.token_log:
            if when > t:
                break
            out = n
        return out

    # ---- open-loop run -------------------------------------------------

    def run(
        self,
        trace: list[dict],
        *,
        budget_s: float | None = None,
        max_steps: int | None = None,
    ) -> dict[str, Any]:
        """Drive the engine under an open-loop arrival trace (each entry
        ``{"t", "prompt", "max_new"}`` — :mod:`ddl25spring_tpu.serve.
        traffic`).  Arrivals are submitted when their time comes whether
        or not the engine kept up (that is what "open loop" means);
        the run ends at the wall/virtual ``budget_s``, after
        ``max_steps`` scheduler iterations, or when everything arrived,
        drained, and completed.  Returns :meth:`metrics`."""
        arrivals = sorted(trace, key=lambda r: r["t"])
        i = 0
        steps = 0
        while True:
            now = self.now()
            if budget_s is not None and now >= budget_s:
                break
            if max_steps is not None and steps >= max_steps:
                break
            while i < len(arrivals) and arrivals[i]["t"] <= now:
                a = arrivals[i]
                self.submit(self.make_request(
                    a["prompt"], a["max_new"], arrival_t=a["t"]
                ))
                i += 1
            idle = (not self.queue
                    and all(r is None for r in self.slots))
            if idle:
                if i >= len(arrivals):
                    break  # drained
                gap = arrivals[i]["t"] - now
                if self.clock == "virtual":
                    self._vtime = arrivals[i]["t"]
                else:
                    time.sleep(min(max(gap, 0.0), 0.05))
                continue
            self.step()
            steps += 1
        return self.metrics(budget_s=budget_s)

    # ---- telemetry -----------------------------------------------------

    def ttft_decomp_cell(self) -> dict[str, Any]:
        """Per-request TTFT decomposition, aggregated: TTFT ==
        queue_wait (arrival -> prefill dispatch) + prefill (the
        admitting pass's engine-clock cost) + first_decode (the
        residual to the first token: drafter prefill under spec, host
        overhead on the wall clock).  On the virtual clock the sum is
        exact (pinned), which is what turns "p95 regressed" into "p95
        regressed because queue-wait doubled" on deterministic A/Bs."""
        qs = [d[0] for d in self.ttft_decomp]
        ps = [d[1] for d in self.ttft_decomp]
        fs = [d[2] for d in self.ttft_decomp]

        def r(v):
            return None if v is None else round(v, 6)

        return {
            "clock": self.clock,
            "requests": self.ttft_decomp.count,
            "queue_wait_s_p50": r(_pct(qs, 50)),
            "queue_wait_s_p95": r(_pct(qs, 95)),
            "prefill_s_p50": r(_pct(ps, 50)),
            "prefill_s_p95": r(_pct(ps, 95)),
            "first_decode_s_p50": r(_pct(fs, 50)),
            "first_decode_s_p95": r(_pct(fs, 95)),
        }

    def metrics(self, budget_s: float | None = None) -> dict[str, Any]:
        """The ``telemetry.serve`` cell: throughput, tail latency,
        admission counters, and pool occupancy — every key the BENCH
        contract (and ``tools/serve_report.py``) reads."""

        pct = _pct
        wall = self.now()
        bill = self.memory_bill()
        try:  # the chips the pool actually lives on (1 off-mesh)
            n_chips = max(1, len(self.pool["seq_len"].devices()))
        except Exception:  # noqa: BLE001 — older array APIs
            n_chips = 1
        tok_lat = self.tick_wall_s if self.clock == "wall" else [
            self.tick_s
        ] * max(self._ticks, 0)
        return {
            "admission": self.admission,
            "wall_s": round(wall, 4),
            **({"budget_s": budget_s} if budget_s is not None else {}),
            "ticks": self._ticks,
            "prefills": self._prefills,
            "admitted": self.admitted,
            "rejected": sum(self.rejected.values()),
            "rejected_by_reason": dict(self.rejected),
            "completed": self.completed,
            "generated_tokens": self.generated_tokens,
            "tokens_per_sec": (
                round(self.generated_tokens / wall, 3) if wall > 0 else None
            ),
            "tokens_per_sec_per_chip": (
                round(self.generated_tokens / wall / n_chips, 3)
                if wall > 0 else None
            ),
            "n_chips": n_chips,
            "ttft_s_p50": pct(self.ttft_s, 50),
            "ttft_s_p95": pct(self.ttft_s, 95),
            "ttft_decomp": self.ttft_decomp_cell(),
            "tok_latency_s_p50": pct(tok_lat, 50),
            "tok_latency_s_p95": pct(tok_lat, 95),
            # exact over the FULL series (the reservoir keeps the peak
            # even after its samples rotate); p50 is of the sample
            "queue_depth_max": (
                self.queue_depths.max
                if self.queue_depths.count else 0
            ),
            "queue_depth_p50": pct(self.queue_depths, 50),
            # exact-count summaries of the bounded host series — what
            # a soak run's telemetry keeps when the samples rotate
            "host_samples": {
                "ttft_s": self.ttft_s.summary(),
                "queue_depths": self.queue_depths.summary(),
                "tick_wall_s": self.tick_wall_s.summary(),
            },
            "page_pool_pages": self.n_pages,
            "page_pool_peak_pages": self.peak_pages,
            "page_pool_peak_occupancy": round(
                self.peak_pages / self.n_pages, 4
            ),
            "pool_ok_failures": self.pool_ok_failures,
            # TP-sharded serving (PR 18): what ONE chip holds resident
            # — the per-chip halves of the mem_budget_bytes bill the
            # obs_report Serving section and --check-tp gates read
            "tp": self.tp,
            "weight_stream": self.weight_stream,
            "pool_bytes_per_chip": bill["pool"],
            "param_bytes_per_chip": sum(bill["weights"].values()),
            # radix prefix cache: the deterministic counters the
            # cached-vs-cold A/B and the serve_report gates read
            "prefix_hit_rate": (
                self.prefix.stats()["hit_rate"]
                if self.prefix is not None else None
            ),
            "prefill_tokens_saved": self.prefill_tokens_saved,
            "prefill_flops_saved": self.prefill_flops_saved,
            "prefix": (
                self.prefix.stats() if self.prefix is not None
                else {"enabled": False}
            ),
            # speculative decoding: the deterministic counters the
            # spec-on-vs-off A/B and serve_report --check-spec-ab read
            "acceptance_rate": (
                round(
                    self.draft_tokens_accepted
                    / self.draft_tokens_proposed, 4
                ) if self.draft_tokens_proposed else None
            ),
            "draft_tokens_accepted": self.draft_tokens_accepted,
            "draft_tokens_rejected": (
                self.draft_tokens_proposed - self.draft_tokens_accepted
            ),
            "spec": (
                {
                    "enabled": True,
                    "k": self.spec_k,
                    "draft_layers": self.draft_cfg.n_layers,
                    "draft_dim": self.draft_cfg.dmodel,
                    "flop_ratio": round(self.spec_flop_ratio, 4),
                    "rounds": self._spec_rounds,
                    "draft_steps": self._draft_steps,
                    "verify_steps": self._spec_rounds,
                    "draft_tokens_proposed": self.draft_tokens_proposed,
                    "draft_tokens_accepted": self.draft_tokens_accepted,
                    "accept_counts": {
                        str(a): n for a, n in
                        sorted(self.spec_accept_counts.items())
                    },
                } if self.spec_k else {"enabled": False}
            ),
            "config": {
                "page_len": self.page_len,
                "pages_per_seq": self.pages_per_seq,
                "max_slots": self.max_slots,
                "prefill_batch": self.prefill_batch,
                "max_prompt_len": self.max_prompt_len,
                "max_queue": self.max_queue,
                "token_budget": self.token_budget,
                "clock": self.clock,
                "prefix_cache": self.prefix is not None,
                "spec_k": self.spec_k,
                "tp": self.tp,
                "weight_stream": self.weight_stream,
            },
        }


# ------------------------------------------------------ registry hook


def make_tp_serve_program(
    cfg: LlamaConfig,
    mesh,
    program: str,
    *,
    page_len: int = 4,
    pages_per_seq: int = 4,
    max_slots: int = 4,
    max_prompt_len: int = 8,
    model_axis: str = "model",
    temperature: float = 0.0,
    sentinel: bool | None = False,
    spec_k: int = 2,
    weight_stream: bool = False,
):
    """The TP-sharded serving program: ``(fn, pool, pool_specs)``.

    Params carry the training-side TP layout (:func:`ddl25spring_tpu.
    parallel.tp.tp_param_specs`, ``shard_vocab=False`` — embed/unembed
    replicated: sampling is a global decision and decode-shape logits
    are tiny), the page pool's HEAD dim shards over ``model_axis`` (each
    shard caches its local ``H/t`` heads), and the per-token
    communication is exactly the two row-parallel psums per block.
    ``pool`` is the freshly-initialized GLOBAL pool placed on the mesh;
    thread it through calls like the single-device engine does.

    ``program`` may also be the speculative pair (PR 13): ``"draft"``
    (pass the DRAFT cfg — the pool is built from it) or ``"verify"``,
    both shaped by ``spec_k``.

    ``weight_stream=True`` (decode/prefill) swaps the resident Megatron
    params for the ZeRO-3 ``[L, n, k]`` row layout
    (:func:`ddl25spring_tpu.parallel.zero.zero_stream_llama_params`):
    decode gathers one layer per position (double-buffered), prefill
    reconstructs the stack transiently, once a pass — the
    ``serve-decode-zero3stream`` registry entry."""
    from jax.sharding import NamedSharding

    if program not in ("decode", "prefill", "draft", "verify"):
        raise ValueError(
            f"program={program!r} is not one of "
            "'decode'/'prefill'/'draft'/'verify'"
        )
    if weight_stream and program not in ("decode", "prefill"):
        raise ValueError(
            "weight_stream builds the plain decode/prefill pair only "
            f"(program={program!r})"
        )
    t = int(mesh.shape[model_axis])
    if cfg.num_heads % t:
        raise ValueError(f"{cfg.num_heads} heads not divisible by t={t}")
    n_pages = max_slots * pages_per_seq
    pool = kv_pages.init_page_pool(
        cfg, n_pages=n_pages, page_len=page_len, max_slots=max_slots,
        pages_per_seq=pages_per_seq,
    )
    # heads sharded, everything else replicated — the spec keeps the
    # split on KV_POOL_HEAD_DIM of the rank-5 buffer (_tp_pool_specs)
    pool_specs = _tp_pool_specs(cfg, model_axis)
    pool = {
        k: jax.device_put(v, NamedSharding(mesh, pool_specs[k]))
        for k, v in pool.items()
    }
    tp_axis = model_axis if t > 1 else None

    if program in ("decode", "prefill"):
        tick, prefill, _release_fn = _tp_compiled_programs(
            cfg, mesh, max_prompt_len=max_prompt_len,
            temperature=temperature, sentinel=sentinel, donate=False,
            weight_stream=weight_stream, model_axis=model_axis,
        )
        fn = tick if program == "decode" else prefill
    else:
        # the speculative pair rides the same sharded pool contract;
        # late import — spec.py needs this module's block body
        from ddl25spring_tpu.serve import spec as spec_mod

        p_specs = _tp_param_specs(cfg, model_axis, False)
        if program == "draft":
            body = spec_mod.make_draft(
                cfg, k=spec_k, steps=spec_k + 1, tp_axis=tp_axis,
                sentinel=sentinel,
            )
            n_extra = 3
        else:
            body = spec_mod.make_verify(
                cfg, k=spec_k, tp_axis=tp_axis, sentinel=sentinel,
            )
            n_extra = 2
        fn = _tp_jit(
            body, mesh, cfg, model_axis=model_axis,
            n_extra=n_extra, p_specs=p_specs, donate=False,
        )
    return fn, pool, pool_specs


def describe(mesh, program: str = "decode", model_axis: str = "model",
             per_chip: bool = False, weight_stream: bool = False):
    """Compile-analytics/graft-lint hook for the serving programs
    (:data:`ddl25spring_tpu.obs.xla_analytics.STRATEGIES` entries
    ``serve-decode`` / ``serve-prefill`` and the PR-18 trio
    ``serve-decode-tp`` / ``serve-prefill-tp`` /
    ``serve-decode-zero3stream``): the TP-sharded decode tick / prefill
    lowered exactly as the engine builds them, the prefill at its
    widest (``max_prompt_len``: the cold pass).

    The load-bearing signature: TP serving traffic is the row-parallel
    **all-reduce ONLY** — 2 psums per block per pass (a decode tick; a
    prefill of any width), every group strictly over the model axis;
    permutes / all-gathers /
    reduce-scatters / all-to-alls are forbidden outright (serve keeps
    embed/unembed replicated — ``shard_vocab=False`` — so not even the
    logits assembly gather exists).  Peak-HBM budgets ride along like
    every training strategy's.

    ``per_chip=True`` (the ``-tp`` entries) tightens the screws to the
    sharded-engine claim itself: the peak-HBM budget drops to 64 KiB —
    strictly BELOW the ~75 KiB the same program measures on one chip,
    so the budget only holds because per-chip KV pages and Megatron
    params divided by ``tp`` — and the all-reduce payload is pinned
    byte-exact (activation-sized: positions x dmodel x 4, UNCHANGED by
    tp — the wire carries partial sums, never KV).  Meta carries the
    measured per-chip pool/param residency for the report tooling.

    ``weight_stream=True`` (``serve-decode-zero3stream``) swaps
    resident Megatron params for ZeRO-3 ``[L, n, k]`` rows: the decode
    scan all-gathers exactly ``n_layers x n_buckets`` times (the
    double-buffered prefetch — all-gather leaves the forbidden list,
    count-pinned instead), under the same 64 KiB budget: params/n
    resident + ONE gathered layer transient, still under the one-chip
    dense peak."""
    from ddl25spring_tpu.parallel.tp import shard_tp_params

    cfg = LlamaConfig(
        vocab_size=64, dmodel=16, num_heads=2, n_layers=2, ctx_size=16,
        dtype="float32",
    )
    t = int(mesh.shape[model_axis])
    page_len, pages_per_seq, max_slots = 4, 4, 4
    max_prompt_len = 8
    prefill_batch = 2

    # as the engine holds them: rounded to cfg.dtype once, then placed
    raw = paged_model(cfg).resident(
        llama.init_llama_params(jax.random.PRNGKey(0), cfg)
    )
    n_buckets = 0
    if weight_stream:
        from ddl25spring_tpu.parallel import zero

        params = zero.zero_stream_llama_params(raw, mesh, model_axis)
        n_buckets = len(zero.stream_block_plan(
            _resident_template(cfg)["blocks"], t
        ).buckets)
    else:
        params = shard_tp_params(raw, mesh, model_axis, shard_vocab=False)
    fn, pool, _specs = make_tp_serve_program(
        cfg, mesh, program, page_len=page_len,
        pages_per_seq=pages_per_seq, max_slots=max_slots,
        max_prompt_len=max_prompt_len,
        model_axis=model_axis, sentinel=False,
        weight_stream=weight_stream,
    )
    # one pass, of any width: 2 row-parallel psums per block
    ar_count = 2 * cfg.n_layers
    if program == "decode":
        args = (params, pool, jax.random.PRNGKey(1))
        ar_positions = max_slots
        lowered = "decode_step"
    else:
        args = (
            params, pool,
            jnp.ones((prefill_batch, max_prompt_len), jnp.int32),
            jnp.full((prefill_batch,), max_prompt_len, jnp.int32),
            jnp.zeros((prefill_batch,), jnp.int32),
            jnp.arange(prefill_batch, dtype=jnp.int32),
            jax.random.PRNGKey(1),
        )
        ar_positions = prefill_batch * max_prompt_len
        lowered = "prefill_step"

    expected: dict[str, Any] = {
        "scalar_bytes": 64,
        "forbidden": [
            "collective-permute", "all-gather", "reduce-scatter",
            "all-to-all", "collective-broadcast",
        ],
        # measured ~47 KiB on this jax/XLA (tiny cfg); generous headroom
        # for layout churn while still catching a duplicated pool or a
        # densified gather (the pool alone would blow 256 KiB many times
        # over if double-buffered at real sizes)
        "memory": {"max_peak_hbm_bytes": 256 * 1024},
    }
    if per_chip and t > 1:
        # the PR-18 shrink gate: the SAME program measures ~75 KiB on
        # one chip (pool 58 KiB + params 25 KiB all resident), so a
        # 64 KiB budget can only hold with the head dim and the
        # Megatron splits genuinely dividing residency by tp (measured
        # ~47 KiB at tp=2)
        expected["memory"] = {"max_peak_hbm_bytes": 64 * 1024}
    if weight_stream:
        # the streaming walk gathers even on one chip (trivially) —
        # all-gather leaves the forbidden list unconditionally
        expected["forbidden"].remove("all-gather")
    if weight_stream and t > 1:
        # params/n resident + one gathered layer in flight: jax 0.9.0's
        # CPU backend measures ~42 KiB at tp=2 against ~75 KiB for the
        # same program on one chip, so the streamed program holds the
        # resident-weight entries' 64 KiB shrink budget too
        expected["memory"] = {"max_peak_hbm_bytes": 64 * 1024}
        # the double-buffered prefetch is count-exact: one bucketed
        # gather per layer (decode streams per position; prefill
        # reconstructs the stack once, transiently)
        expected["all-gather"] = {
            "count": (cfg.n_layers if program == "decode" else 1)
            * n_buckets,
            "axes": [model_axis],
        }
    if t > 1:
        expected["all-reduce"] = {
            "count": ar_count,
            "axes": [model_axis],
        }
        if per_chip or weight_stream:
            # byte-exact: every psum carries activation-sized partial
            # sums (positions x dmodel x fp32) — tp divides KV bytes
            # and FLOPs, NEVER the per-op wire payload
            payload = ar_count * ar_positions * cfg.dmodel * 4
            expected["all-reduce"]["min_bytes"] = payload
            expected["all-reduce"]["max_bytes"] = payload
    else:
        expected["forbidden"].append("all-reduce")
    meta = {
        "program": program,
        "page_len": page_len,
        "pages_per_seq": pages_per_seq,
        "max_slots": max_slots,
        "n_pages": max_slots * pages_per_seq,
        "tp": t,
        # the declared pool split the H013 pair check holds every
        # compiled serve program to (see KV_POOL_HEAD_DIM)
        "kv_sharded_dim": KV_POOL_HEAD_DIM,
        **({"max_prompt_len": max_prompt_len,
            "prefill_batch": prefill_batch}
           if program == "prefill" else {}),
    }
    if per_chip or weight_stream:
        # measured per-chip residency (shard_shape x itemsize) — the
        # quantity mem_report's --check gate and the budget-shrink pins
        # divide by tp
        meta["pool_bytes_per_chip"] = sum(
            ServeEngine._leaf_bytes(x, True) for x in jax.tree.leaves(pool)
        )
        meta["param_bytes_per_chip"] = sum(
            ServeEngine._leaf_bytes(x, True)
            for x in jax.tree.leaves(params)
        )
    if weight_stream:
        # the H013 stream-rows contract (analysis/shard_flow.py): every
        # params['blocks'] entry arg must shard exactly this dim
        meta["stream_rows_dim"] = 1
        meta["stream_buckets"] = n_buckets
    return {
        "fn": fn,
        "args": args,
        "lowered": lowered,
        "meta": meta,
        "expected": expected,
    }
