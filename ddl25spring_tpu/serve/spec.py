"""Speculative decoding: tiny-LLaMA drafter + single-pass verification.

Decode is memory-bandwidth-bound: every generated token streams the
whole model's weights through the chip for one token of work.  A cheap
drafter that proposes ``k`` tokens which the target model scores in ONE
verify pass turns ``k`` sequential weight streams into one — the third
serving multiplier after continuous batching (PR 10) and the radix
prefix cache (PR 11), ROADMAP item 2(c).  Greedy speculative decoding
is *exactly equivalent* to the target model's own greedy output — a
draft token is accepted iff it equals the target's argmax at that
position, and the first rejection is replaced by that argmax — so the
whole optimization is gated the way this repo gates everything: a
bitwise tokens-match pin plus a deterministic virtual-clock A/B
(``serve_report --check-spec-ab``).

Per-round observability: the engine emits one ``serve_spec_round``
timeline event per slot per round (accepted/rejected counts, the
request's rid — :mod:`ddl25spring_tpu.obs.timeline`), so acceptance
behavior is inspectable per request in ``trace_merged.json``, not just
as the run-level ``acceptance_rate``.

The pieces:

- **drafter** — a tiny LLaMA (same architecture, ``draft_layers`` /
  ``draft_dim`` scaled down) with its OWN paged KV pool (same
  refcounted :mod:`.kv_pages` machinery, drafter-sized buffers).  The
  built-in construction is the *early-exit* drafter
  (:func:`early_exit_drafter`): the target's first ``draft_layers``
  blocks with the target's own embed/ln_f/unembed — self-drafting needs
  no training and keeps real argmax agreement (LayerSkip-style;
  a distilled drafter drops in through the same ``draft_params`` /
  ``draft_cfg`` engine knobs).
- **draft program** (:func:`make_draft`) — ``k`` static single-token
  drafter steps over the drafter pool, scan-shaped exactly like the
  engine's decode tick (one compiled program per static step count; the
  engine picks the ``k`` or ``k+1``-step variant per round depending on
  whether any slot owes the drafter a catch-up token from a previous
  fully-accepted round).
- **verify program** (:func:`make_verify`) — the target model scores
  all ``k+1`` positions (the committed last token + the ``k`` drafts)
  in one program: a width-``(k+1)`` prefill-shaped scan over the paged
  KV (the model's paged block, as the decode tick runs it, so fp32 logits are
  bitwise those of ``k+1`` sequential ticks), writing KV optimistically
  and masking writes past each row's admission limit so the page
  accounting never exceeds the non-speculative worst case.
- **rollback** — the engine commits the accepted prefix and calls
  :func:`.kv_pages.truncate_to` on BOTH pools: rejected positions'
  pages return to the free set under the refcount invariant, jit-safe
  (trash-page masked writes, no ``lax.cond``).

The virtual-clock cost model the deterministic A/B prices (the 2-core
CPU sandbox wall clock cannot see a bandwidth win, so it must not be
the judge): one verify pass = 1 tick (one weight stream, exactly like
one decode tick), each drafter step = :func:`flop_ratio` ticks (the
drafter's per-token matmul FLOPs as a fraction of the target's).

``serve-draft`` / ``serve-verify`` join the describe() registry at the
bottom: TP-sharded lowerings of both programs with declared collective
signatures (row-parallel all-reduce ONLY, like every serve program) and
peak-HBM budgets, so graft-lint / graft-sched / comms-report and the
H011–H013 sharding-flow contracts cover speculative serving for free.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ddl25spring_tpu.models import llama
from ddl25spring_tpu.obs import sentinels
from ddl25spring_tpu.serve import kv_pages
from ddl25spring_tpu.serve.paged_model import paged_model
from ddl25spring_tpu.utils.config import LlamaConfig, replace

Params = dict[str, Any]

__all__ = [
    "early_exit_drafter", "flop_ratio", "matmul_param_count",
    "make_draft", "make_verify", "describe",
]


# ------------------------------------------------------------ the drafter


def early_exit_drafter(
    params: Params,
    cfg: LlamaConfig,
    draft_layers: int,
    draft_dim: int | None = None,
) -> tuple[Params, LlamaConfig]:
    """Build the self-drafting tiny LLaMA: the target's first
    ``draft_layers`` blocks under the target's own embed/ln_f/unembed.

    Early exit is the one drafter construction that works with no
    training: the truncated residual stream still points near the full
    model's, so greedy argmax agreement is real (measured ~0.9 at
    exit 1-of-2 and ~0.77 at 1-of-6 on the serve test configs) — a
    drafter with independent random weights would agree ~1/vocab and
    speculation would only ever cost.  ``draft_dim`` additionally
    slices the model dimension to the leading ``draft_dim`` channels
    (projections, embed and unembed all sliced consistently) — the
    shape knob a *distilled* drafter would occupy; channel slicing cuts
    agreement hard at random init, so the default keeps the full width.

    Returns ``(draft_params, draft_cfg)`` — views of the target leaves
    (no copy), sized for ``init_page_pool``'s drafter pool."""
    if not 1 <= draft_layers <= cfg.n_layers:
        raise ValueError(
            f"draft_layers={draft_layers} must sit in [1, "
            f"n_layers={cfg.n_layers}]"
        )
    d = cfg.dmodel if draft_dim is None else int(draft_dim)
    if not 1 <= d <= cfg.dmodel:
        raise ValueError(
            f"draft_dim={draft_dim} must sit in [1, dmodel={cfg.dmodel}]"
        )
    if d % cfg.num_heads or (d // cfg.num_heads) % 2:
        raise ValueError(
            f"draft_dim={d} must keep an even head_dim over "
            f"{cfg.num_heads} heads (RoPE rotates channel pairs)"
        )
    draft_cfg = replace(cfg, n_layers=draft_layers, dmodel=d)
    blocks = jax.tree.map(lambda x: x[:draft_layers], params["blocks"])
    if d == cfg.dmodel:
        return {
            "embed": params["embed"],
            "blocks": blocks,
            "ln_f": params["ln_f"],
            "unembed": params["unembed"],
        }, draft_cfg
    f = draft_cfg.ffn_dim

    def slice_block(name, x):
        if name in ("ln1", "ln2"):
            return x[:, :d]
        if name in ("wq", "wk", "wv", "wo"):
            return x[:, :d, :d]
        if name in ("w_gate", "w_up"):
            return x[:, :d, :f]
        if name == "w_down":
            return x[:, :f, :d]
        raise KeyError(name)

    return {
        "embed": params["embed"][:, :d],
        "blocks": {k: slice_block(k, v) for k, v in blocks.items()},
        "ln_f": params["ln_f"][:d],
        "unembed": params["unembed"][:d, :],
    }, draft_cfg


def matmul_param_count(params: Params) -> int:
    """Parameters a decode step actually streams through matmuls —
    everything except the embedding table (a gather, not a matmul;
    unembed IS counted).  ``2 *`` this is the standard per-token decode
    FLOP estimate, the numerator/denominator of :func:`flop_ratio`."""
    return sum(
        int(np.prod(x.shape))
        for k, v in params.items() if k != "embed"
        for x in jax.tree.leaves(v)
    )


def flop_ratio(draft_params: Params, params: Params) -> float:
    """Drafter per-token decode FLOPs as a fraction of the target's —
    what the deterministic virtual clock charges each drafter step
    (the verify pass is charged one full tick: one target weight
    stream, exactly like one decode tick)."""
    return matmul_param_count(draft_params) / matmul_param_count(params)


# ------------------------------------------------------ compiled programs


def _position_step(cfg: LlamaConfig, tp_axis: str | None):
    """One single-token step over a paged pool, shared op for op by the
    draft and verify scans (and therefore bitwise-identical to the
    engine's decode tick, which runs the same sequence): reserve a page
    when the position opens one, write the token's KV (masked rows
    trash-route), run the block stack, return the greedy argmax.  The
    builders differ only in where the token comes from and what bounds
    the write mask — keeping this body single is what makes 'draft and
    verify agree with the tick' a structural fact instead of a
    three-way copy to hand-maintain."""
    from ddl25spring_tpu.serve.engine import _block_stack

    model = paged_model(cfg)

    def step(params, pool, tok, pos, writing, active):
        page_len = kv_pages.page_len_of(pool)
        n_pages = pool["free"].shape[0]
        S = pos.shape[0]
        slots = jnp.arange(S, dtype=jnp.int32)
        need = writing & (pos % page_len == 0)
        pool, ok = kv_pages.reserve_pages(pool, slots, pos, need)
        pages, offs = kv_pages.write_page_ids(pool, slots, pos, writing)
        rows = jnp.clip(pool["page_table"], 0, n_pages - 1)

        x = model.embed(params, tok[:, None])
        x, planes, _aux = _block_stack(
            model, params, x, kv_pages.planes(pool), slots, rows,
            pages[:, None], offs[:, None], pos[:, None], writing[:, None],
            tp_axis,
        )
        with jax.named_scope("head"):
            logits = model.unembed(params, x)[:, 0]  # [S, V] fp32
        with jax.named_scope("sample"):
            g = logits.argmax(-1).astype(jnp.int32)
        absmax = jnp.max(jnp.where(active, jnp.max(
            jnp.abs(logits), axis=-1), 0.0))
        return kv_pages.with_planes(pool, planes), g, absmax, ok

    return step


def make_draft(
    cfg: LlamaConfig,
    *,
    k: int,
    steps: int | None = None,
    tp_axis: str | None = None,
    sentinel: bool | None = None,
    strategy: str = "serve-draft",
):
    """Build the draft program: ``k`` greedy drafter tokens for every
    active slot, over the drafter's own paged KV pool.

    ``draft(params, pool, ctx, n_ctx, limits) -> (pool, drafts, ok)``
    — ``ctx [max_slots, 2]`` int32 holds each slot's catch-up tokens
    (committed tokens whose KV the drafter has not written yet: always
    the last committed token; plus, after a fully-accepted round, the
    final draft token the drafter sampled but never appended),
    ``n_ctx [max_slots]`` how many are valid (1 or 2; 0 marks an idle
    slot), ``limits [max_slots]`` each slot's write bound (the same
    ``prompt_len + max_new - 1`` the verify pass honors: a drafter
    write past it would open a page the admission accounting never
    billed — and near the table's end could fail the WHOLE batched
    reserve, dropping other slots' legitimate pages; drafts at masked
    positions are garbage, which is fine — the host never emits past a
    request's remaining budget, and rejection is always safe).  The
    scan runs ``steps`` single-token drafter steps (default ``k + 1``
    — enough for ``n_ctx = 2``; the engine compiles a ``steps = k``
    variant too and picks per round, so the common all-slots-caught-up
    round never pays the extra step): step ``j`` consumes the slot's
    ``j``-th catch-up token while ``j < n_ctx``, its own previous
    sample after, each step appending its token's KV at ``seq_len + j``
    (masked past ``n_ctx + k - 1``: the final draft token is sampled
    but never written, mirroring the engine's last-token convention)
    and sampling the next greedy token.  Slot ``s``'s proposals are the
    samples at steps ``n_ctx[s]-1 .. n_ctx[s]+k-2``, gathered into
    ``drafts [max_slots, k]``.

    Greedy only: speculative acceptance below compares exact argmaxes —
    the regime where spec output is bitwise the target's own."""
    if k < 1:
        raise ValueError(f"k={k} draft tokens must be >= 1")
    if steps is None:
        steps = k + 1
    if not k <= steps <= k + 1:
        # steps = k serves n_ctx <= 1 rounds; steps = k + 1 is the
        # 2-token catch-up variant — anything else mis-windows drafts
        raise ValueError(f"steps={steps} must be k={k} or k+1")
    s_on, s_policy = sentinels.resolve(sentinel)
    step = _position_step(cfg, tp_axis)

    def draft(params, pool, ctx, n_ctx, limits):
        active = pool["active"]
        base = pool["seq_len"]  # [S] — frontier at round start
        write_upto = n_ctx + (k - 1)  # positions this slot writes

        def body(carry, j):
            pool, cur = carry
            tok_ctx = lax.dynamic_index_in_dim(
                ctx, jnp.clip(j, 0, ctx.shape[1] - 1), axis=1,
                keepdims=False,
            )
            tok = jnp.where(j < n_ctx, tok_ctx, cur)
            pos = base + j
            writing = active & (j < write_upto) & (pos < limits)
            pool, samp, absmax, ok = step(
                params, pool, tok, pos, writing, active
            )
            return (pool, samp), (samp, absmax, ok)

        (pool, _), (samps, absmax, oks) = lax.scan(
            body, (pool, jnp.zeros_like(base)),
            jnp.arange(steps),
        )
        # slot s proposed the samples at steps n_ctx-1 .. n_ctx+k-2
        idx = jnp.clip(
            (n_ctx - 1)[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :],
            0, steps - 1,
        )
        drafts = jnp.take_along_axis(samps.T, idx, axis=1)  # [S, k]
        pool = {
            **pool,
            "seq_len": jnp.where(active, base + write_upto, base),
        }
        # drafter sentinel: a non-finite drafter logit poisons every
        # proposal this round (same decode-logits guard class)
        drafts, pool = sentinels.guard(
            strategy, (drafts, pool),
            loss=jnp.max(absmax),
            updates={"logits_absmax": absmax},
            fallback=(drafts, pool),
            axis=tp_axis, enabled=s_on, policy=s_policy,
        )
        return pool, drafts, jnp.all(oks)

    return draft


def make_verify(
    cfg: LlamaConfig,
    *,
    k: int,
    tp_axis: str | None = None,
    sentinel: bool | None = None,
    strategy: str = "serve-verify",
):
    """Build the verify program: the target model scores all ``k + 1``
    positions of a draft window in ONE pass over the paged KV.

    ``verify(params, pool, toks, limits) -> (pool, greedy, ok)`` —
    ``toks [max_slots, k+1]`` is each slot's committed last token
    followed by its ``k`` drafts, ``limits [max_slots]`` each slot's
    write bound (``prompt_len + max_new - 1``, the last position a
    non-speculative decode would ever write: junk positions past a
    request's own worst case trash-route, so speculation never
    allocates a page the admission accounting didn't bill).
    ``greedy [max_slots, k+1]`` carries the target's argmax after each
    consumed position — ``greedy[:, j]`` is exactly the token a decode
    tick would emit given the same committed context, computed by the
    same scan body op for op, so acceptance/rejection against it keeps
    speculative output bitwise equal to the sequential engine.

    The scan writes KV optimistically at ``seq_len + j`` and advances
    ``seq_len`` to the full window; the engine rolls both pools back to
    the accepted prefix with :func:`.kv_pages.truncate_to` — stale
    values inside the kept frontier page are overwritten before the
    monotone frontier makes them readable, so the optimistic writes are
    invisible to every later logit."""
    if k < 1:
        raise ValueError(f"k={k} draft tokens must be >= 1")
    s_on, s_policy = sentinels.resolve(sentinel)
    step = _position_step(cfg, tp_axis)

    def verify(params, pool, toks, limits):
        active = pool["active"]
        base = pool["seq_len"]

        def body(pool, j):
            tok = lax.dynamic_index_in_dim(toks, j, axis=1, keepdims=False)
            pos = base + j
            writing = active & (pos < limits)
            pool, g, absmax, ok = step(
                params, pool, tok, pos, writing, active
            )
            return pool, (g, absmax, ok)

        pool, (gs, absmax, oks) = lax.scan(
            body, pool, jnp.arange(k + 1)
        )
        pool = {
            **pool,
            # optimistic frontier, clamped to the write bound; the
            # engine truncates to the accepted prefix right after
            "seq_len": jnp.where(
                active,
                jnp.minimum(base + k + 1, jnp.maximum(limits, base)),
                base,
            ),
        }
        greedy = gs.T  # [S, k+1]
        greedy, pool = sentinels.guard(
            strategy, (greedy, pool),
            loss=jnp.max(absmax),
            updates={"logits_absmax": absmax},
            fallback=(greedy, pool),
            axis=tp_axis, enabled=s_on, policy=s_policy,
        )
        return pool, greedy, jnp.all(oks)

    return verify


# ------------------------------------------------------ registry hook


def describe(mesh, program: str = "verify", model_axis: str = "model",
             k: int = 2, draft_layers: int = 1):
    """Compile-analytics/graft-lint hook for the speculative programs
    (registry entries ``serve-draft`` / ``serve-verify``): the
    TP-sharded draft / verify programs lowered exactly as the engine
    builds them, over the same head-dim-sharded paged pools as
    serve-decode/serve-prefill (``meta["kv_sharded_dim"]`` joins the
    H013 cross-program layout contract, so a drafter pool silently
    sharded differently from the target pool fails CI).

    The load-bearing signatures: speculative TP traffic is the
    row-parallel **all-reduce ONLY**, 2 psums per block per scanned
    position — verify runs ``k + 1`` positions through the full target
    depth, draft runs its ``k + 1``-step variant through
    ``draft_layers`` only.  The two counts differing by exactly the
    depth ratio is the compile-time half of the drafter's FLOP-ratio
    pricing (the virtual clock's ``flop_ratio`` is the runtime half)."""
    from ddl25spring_tpu.serve.engine import (
        KV_POOL_HEAD_DIM,
        make_tp_serve_program,
    )

    if program not in ("draft", "verify"):
        raise ValueError(f"program={program!r} is not 'draft'/'verify'")
    cfg = LlamaConfig(
        vocab_size=64, dmodel=16, num_heads=2, n_layers=2, ctx_size=16,
        dtype="float32",
    )
    t = int(mesh.shape[model_axis])
    page_len, pages_per_seq, max_slots = 4, 4, 4

    from ddl25spring_tpu.parallel.tp import shard_tp_params

    params = paged_model(cfg).resident(
        llama.init_llama_params(jax.random.PRNGKey(0), cfg)
    )
    if program == "draft":
        draft_params, run_cfg = early_exit_drafter(params, cfg, draft_layers)
        run_params = shard_tp_params(
            draft_params, mesh, model_axis, shard_vocab=False,
        )
        n_layers = draft_layers
    else:
        run_cfg = cfg
        run_params = shard_tp_params(
            params, mesh, model_axis, shard_vocab=False,
        )
        n_layers = cfg.n_layers

    fn, pool, _specs = make_tp_serve_program(
        run_cfg, mesh, program, page_len=page_len,
        pages_per_seq=pages_per_seq, max_slots=max_slots,
        model_axis=model_axis, sentinel=False, spec_k=k,
    )
    if program == "draft":
        args = (
            run_params, pool,
            jnp.ones((max_slots, 2), jnp.int32),
            jnp.ones((max_slots,), jnp.int32),
            jnp.full((max_slots,), pages_per_seq * page_len, jnp.int32),
        )
        lowered = "draft_step"
    else:
        args = (
            run_params, pool,
            jnp.ones((max_slots, k + 1), jnp.int32),
            jnp.full((max_slots,), pages_per_seq * page_len, jnp.int32),
        )
        lowered = "verify_step"
    # every scanned position runs the program's block stack: 2
    # row-parallel psums per block x depth x (k+1) scan steps
    ar_count = 2 * n_layers * (k + 1)

    expected: dict[str, Any] = {
        "scalar_bytes": 64,
        "forbidden": [
            "collective-permute", "all-gather", "reduce-scatter",
            "all-to-all", "collective-broadcast",
        ],
        # measured ~50 KiB on this jax/XLA (tiny cfg) — same generous
        # headroom discipline as serve-decode/serve-prefill
        "memory": {"max_peak_hbm_bytes": 256 * 1024},
    }
    if t > 1:
        expected["all-reduce"] = {
            "count": ar_count,
            "axes": [model_axis],
        }
    else:
        expected["forbidden"].append("all-reduce")
    return {
        "fn": fn,
        "args": args,
        "lowered": lowered,
        "meta": {
            "program": program,
            "page_len": page_len,
            "pages_per_seq": pages_per_seq,
            "max_slots": max_slots,
            "n_pages": max_slots * pages_per_seq,
            "tp": t,
            "kv_sharded_dim": KV_POOL_HEAD_DIM,
            "spec_k": k,
            "n_layers": n_layers,
            **({"draft_layers": draft_layers}
               if program == "draft" else {}),
        },
        "expected": expected,
    }
