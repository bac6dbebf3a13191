"""Pipeline parallelism (GPipe-style microbatching) and DPxPP hybrids.

What the reference does with 3 (or 6) OS processes — ``isend/irecv`` chains
with per-microbatch tags, activation stacks drained LIFO for backward, and
per-stage-group ``all_reduce`` (``lab/s01_b1_microbatches.py:66-178``,
``lab/s01_b2_dp_pp.py:93-227``) — is here ONE jitted SPMD program:

- the pipeline is a ``lax.scan`` over ``T = M + S - 1`` ticks inside a
  ``shard_map`` over the mesh ``stage`` axis; each tick every stage applies
  its layer slice and hands its activation to the next stage via
  ``lax.ppermute`` (an XLA collective-permute riding ICI — the tag/FIFO
  machinery of gloo send/recv is replaced by program order, SURVEY §5);
- backward is NOT hand-written: ``jax.grad`` differentiates through the
  scanned ppermute schedule, which *is* the reverse pipeline with LIFO
  activation consumption (XLA rematerializes/buffers activations; the
  reference's ``acc_outs.pop().backward(g)`` drain falls out of the scan
  transpose);
- microbatch gradient accumulation (the ``.grad`` accumulation across
  microbatches, ``s01_b1_microbatches.py:148-177``) falls out of summing the
  per-microbatch losses in the scan carry;
- the DP dimension of the hybrid (per-stage-group all_reduce, flatten/
  unflatten at ``s01_b2_dp_pp.py:205-224``) is the automatic psum of
  cotangents over the ``data`` axis for data-invariant params, scaled by the
  ``pmean`` in the loss.

The schedule computed is exactly GPipe: all forwards stream through, then
all backwards (the transpose drains in reverse) — matching the homework B1
solution's schedule, with the bubble fraction (S-1)/(M+S-1).

Beside the model's own scopes (``models/llama.py``) the step's parts carry
``jax.named_scope`` names in every operation's ``op_name``:
``schedule`` (what the tick scan adds around the parts: carries, stacked
residuals, summed weight gradients), ``stage_permute`` (the hand-off
between stages), ``grad_allreduce`` (the cast whose transpose all-reduces
the head's gradients), ``head_loss`` (the last stage's unembed and loss:
one scope around their ``cond``), ``optimizer``.  Names are metadata and
change no operation.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax import lax, shard_map
from jax.lax import pcast

from ddl25spring_tpu.parallel.bucketing import donate_argnums
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ddl25spring_tpu.models import llama
from ddl25spring_tpu.ops.losses import causal_lm_loss
from ddl25spring_tpu.utils.config import LlamaConfig

Params = dict[str, Any]

# PartitionSpec prefix for staged llama params: blocks carry a leading
# [num_stages] dim sharded over the stage axis; embed/unembed replicated
# (cheap relative to blocks; the FLOPs live in the MXU matmuls).
def staged_param_specs(
    stage_axis: str = "stage",
    ep_axis: str | None = None,
    tp_axis: str | None = None,
    chunked: bool = False,
    n_experts: int = 0,
) -> Params:
    """``ep_axis``: additionally shard the switch-MoE expert stacks over
    that axis (dim 2 of the ``[S, L/S, E, ...]`` stacks) — expert
    parallelism riding the pipeline's data axis, so each device holds
    ``E/n`` experts per stage instead of all ``E`` (see
    :func:`make_pipeline_loss`).

    ``tp_axis``: additionally Megatron-shard each block's matmuls over
    that axis — wq/wk/wv/w_gate/w_up column-split (last dim), wo/w_down
    row-split (the d_in dim) — the layout
    :mod:`ddl25spring_tpu.parallel.tp` uses, lifted onto staged blocks
    for the 3-D DP x PP x TP composition.  ``chunked=True`` targets the
    interleaved ``[S, V, Lc, d, d]`` stacks (one more leading dim before
    the matmul dims).

    ``n_experts > 0`` with ``tp_axis`` selects the switch-MoE block
    schema: attention matmuls column/row-split as above, and the expert
    stacks ``[S,(V,)Lc, E, ...]`` sharded on their EXPERT dim over the
    tp axis (the :func:`~ddl25spring_tpu.parallel.tp.make_tp_moe_fn`
    layout lifted onto staged stacks); the router stays replicated
    across tp like the norms.  Without it, TP specs assume the dense
    block key set — pass the config's expert count so MoE params don't
    fail with an opaque tree-map KeyError."""
    if ep_axis is not None and tp_axis is not None:
        raise NotImplementedError("ep_axis and tp_axis are exclusive")
    blocks: Any = P(stage_axis)
    if ep_axis is not None:
        # expert stacks: [S, (V,) Lc, E, ...] — the expert dim sits one
        # deeper under the interleaved chunk layout
        pad = (None,) * (2 if chunked else 1)
        blocks = {k: P(stage_axis) for k in llama.ATTN_BLOCK_KEYS}
        blocks["moe"] = {
            "router": P(stage_axis),
            "w_gate": P(stage_axis, *pad, ep_axis),
            "w_up": P(stage_axis, *pad, ep_axis),
            "w_down": P(stage_axis, *pad, ep_axis),
        }
    elif tp_axis is not None:
        # single source of which weights are column- vs row-parallel:
        # parallel.tp's constants, lifted onto the stacked block dims
        from ddl25spring_tpu.parallel.tp import _COL, _ROW

        pad = (None,) * (2 if chunked else 1)  # [S,(V,)Lc] leading dims
        if n_experts > 0:
            blocks = {
                "ln1": P(stage_axis), "ln2": P(stage_axis),
                **{k: P(stage_axis, *pad, None, tp_axis)
                   for k in ("wq", "wk", "wv")},
                "wo": P(stage_axis, *pad, tp_axis, None),
                "moe": {
                    "router": P(stage_axis),
                    "w_gate": P(stage_axis, *pad, tp_axis),
                    "w_up": P(stage_axis, *pad, tp_axis),
                    "w_down": P(stage_axis, *pad, tp_axis),
                },
            }
        else:
            blocks = {
                "ln1": P(stage_axis), "ln2": P(stage_axis),
                **{k: P(stage_axis, *pad, None, tp_axis) for k in _COL},
                **{k: P(stage_axis, *pad, tp_axis, None) for k in _ROW},
            }
    return {
        "embed": P(),
        "blocks": blocks,
        "ln_f": P(),
        "unembed": P(),
    }


def _check_tp(cfg: LlamaConfig, mesh: Mesh, tp_axis: str) -> None:
    """Shared TP preconditions for the pipeline schedules."""
    t = mesh.shape[tp_axis]
    if cfg.num_heads % t:
        raise ValueError(
            f"num_heads ({cfg.num_heads}) not divisible by {tp_axis}={t}"
        )
    if cfg.n_experts > 0 and cfg.n_experts % t:
        raise ValueError(
            f"n_experts ({cfg.n_experts}) not divisible by {tp_axis}={t}"
        )


def _ep_moe_fn(
    cfg: LlamaConfig,
    mesh: Mesh,
    ep_axis: str,
    data_axis: str | None,
    vary_axes: tuple[str, ...],
):
    """EP validation + the ``ep_moe_local`` closure shared by the GPipe
    and 1F1B schedules.  They differ only in ``vary_axes``: the GPipe path
    keeps blocks data-invariant so the router is pcast inside
    ``ep_moe_local``; the 1F1B path pcasts the router itself (with the
    other invariant block leaves) and passes ``()``."""
    if cfg.n_experts <= 0:
        raise ValueError("ep_axis given but cfg.n_experts == 0")
    if ep_axis != data_axis:
        # tokens shard over data only; an EP axis the tokens are
        # replicated over would all_to_all duplicate work
        raise ValueError(
            f"ep_axis {ep_axis!r} must be the data axis {data_axis!r}"
        )
    ep_n = mesh.shape[ep_axis]
    if cfg.n_experts % ep_n:
        raise ValueError(
            f"{cfg.n_experts} experts not divisible by {ep_axis}={ep_n}"
        )
    from ddl25spring_tpu.parallel.ep import ep_moe_local

    def moe_fn(mp, flat):
        return ep_moe_local(
            mp, flat, axis=ep_axis, ep=ep_n,
            capacity_factor=cfg.capacity_factor,
            vary_axes=vary_axes, top_k=cfg.moe_top_k,
        )

    return moe_fn


def _tp_moe_fn(cfg: LlamaConfig, tp_axis: str):
    """The expert-sharded MoE FFN the pipeline schedules inject under
    ``tp_axis`` when ``cfg.n_experts > 0``: global routing replicated
    across tp (tokens already are), each member applying its ``E/t``
    expert slice, the block's row-parallel psum completing the combine —
    :func:`~ddl25spring_tpu.parallel.tp.make_tp_moe_fn` riding the staged
    stacks, so pipeline-TP-MoE keeps exact drop parity with the serial
    ``moe_ffn``."""
    from ddl25spring_tpu.parallel.tp import make_tp_moe_fn

    return make_tp_moe_fn(tp_axis, cfg.capacity_factor, cfg.moe_top_k)


def _check_sp(cfg, mesh, seq_axis, sp_mode, tp_axis):
    """Shared SP preconditions for the pipeline schedules.  The ulysses
    head check accounts for TP: the per-device head count is already
    ``H/t`` before the seq all_to_all splits it further."""
    if sp_mode not in ("ring", "ulysses"):
        raise ValueError(f"unknown SP mode {sp_mode!r}")
    n_seq = mesh.shape[seq_axis]
    local_heads = cfg.num_heads // (
        mesh.shape[tp_axis] if tp_axis is not None else 1
    )
    if sp_mode == "ulysses" and local_heads % n_seq:
        raise ValueError(
            f"ulysses SP needs local heads ({local_heads}) divisible "
            f"by the {seq_axis!r} axis size ({n_seq})"
        )


def _sp_block_kw(cfg, seq_axis, sp_mode, L, tokens_mb):
    """The per-trace SP setup shared by the GPipe and 1F1B schedules
    (called INSIDE their shard_maps): global RoPE positions + the SP
    attention fn for every block, and the causal targets from ONE
    pre-scan boundary ppermute — so the per-tick loss stays
    collective-free (a collective inside the stage-varying finish cond
    deadlocks the matcher).  Returns ``(block_kw, targets_mb,
    valid_row)``; with ``seq_axis=None`` the no-SP identity
    ``({}, tokens_mb, None)``, so call sites need no branch."""
    if seq_axis is None:
        return {}, tokens_mb, None
    from ddl25spring_tpu.parallel.sp import (
        make_sp_attn_fn, sp_shifted_targets,
    )

    pos = lax.axis_index(seq_axis) * L + jnp.arange(L)
    sp_attn = make_sp_attn_fn(cfg, seq_axis, sp_mode, pos)
    block_kw = {
        "pos": pos,
        "attn_fn": lambda q, k, v, dtype: sp_attn(q, k, v, dtype=dtype),
    }
    targets_mb, valid_row = sp_shifted_targets(tokens_mb, seq_axis)
    return block_kw, targets_mb, valid_row


def _slot_map(k, V: int, S: int, M: int):
    """Megatron's interleaved slot grouping — THE single source of the
    schedule: slot ``k`` maps to chunk ``v`` and microbatch ``m`` by
    ``g, j = divmod(k, V*S); v, r = divmod(j, S); m = g*S + r`` (each
    device runs chunk 0 for a group of S microbatches, then chunk 1 for
    the same group, ...).  Returns ``(v, m, r, g)`` with ``k`` clamped
    into range (drain ticks); the interleaved-1F1B backward derives its
    mirrored stream (chunk reversal + forward-slot reconstruction) from
    the same quadruple.  See :func:`make_interleaved_pipeline_loss` for
    the timing proof."""
    g, j = jnp.divmod(jnp.clip(k, 0, M * V - 1), V * S)
    v, r = jnp.divmod(j, S)
    return v, g * S + r, r, g


def make_pipeline_loss(
    cfg: LlamaConfig,
    mesh: Mesh,
    num_microbatches: int,
    stage_axis: str = "stage",
    data_axis: str | None = None,
    remat: bool = False,
    ep_axis: str | None = None,
    num_chunks: int = 1,
    tp_axis: str | None = None,
    seq_axis: str | None = None,
    sp_mode: str = "ring",
    instrument: bool | None = None,
):
    """Build ``loss(params, tokens) -> scalar`` running the GPipe schedule.

    ``instrument`` (None = follow the global :mod:`ddl25spring_tpu.obs`
    flag at build time; True/False hard-enable/-disable): every scan tick marks its host arrival time, and
    switch-MoE configs additionally emit each tick's router load-balance
    aux term (the ``f·P`` load/importance product the aux loss measures) —
    all via ``jax.debug.callback``, usable where the XLA profiler is not.
    Note the counters fire during the FORWARD pass; under ``remat=True``
    the backward's recompute fires them again (counter means are unbiased,
    counts double).  Disabled, the lowered HLO is identical to an
    uninstrumented build.

    ``params`` is a llama pytree with blocks pre-split by
    :func:`~ddl25spring_tpu.models.llama.split_blocks_for_stages` into
    ``[S, L/S, ...]``.  ``tokens`` is ``[B, L]`` with
    ``B = num_microbatches * microbatch_size`` (times the data-axis size
    when ``data_axis`` is given — the global batch, like the reference's
    disjoint per-pipeline streams at ``s01_b2_dp_pp.py:60,78``).

    ``remat=True`` wraps each tick in ``jax.checkpoint``: the scan saves
    only per-tick carries ([mb, L, d] activations) and recomputes block
    internals in the backward — a middle point between plain GPipe (all
    residuals live) and the 1F1B schedule (M-invariant stash,
    :func:`make_1f1b_value_and_grad`).

    Switch-MoE configs (``cfg.n_experts > 0``) ride the pipeline: each
    stage accumulates its layers' load-balancing aux loss for its ACTIVE
    forward ticks into the scan carry, weighted by ``cfg.moe_aux_weight``
    and folded into the returned scalar.  MoE dispatch groups are
    per-microbatch-per-stage (the flattened ``[mb*L, D]`` the stage sees),
    so the oracle is the mean over microbatches of
    ``causal_lm_loss + w * aux`` from
    :func:`~ddl25spring_tpu.models.llama.llama_forward_with_aux` — asserted
    in ``tests/test_pipeline.py``.

    ``ep_axis`` (must be the data axis): EP x DP x PP — the expert stacks
    shard over the data axis too, so each device holds ``E/n`` experts per
    stage, with :func:`~ddl25spring_tpu.parallel.ep.ep_moe_local` moving
    capacity buckets between data rows via ``all_to_all`` each tick.
    Routing/capacity stay per-data-shard (decided before the a2a), so the
    loss is EXACTLY the dense replicated-expert pipeline's — drops
    included — while per-device expert memory falls from ``E`` to
    ``E/n`` stacks (pinned in ``tests/test_pipeline.py``).

    ``num_chunks > 1`` selects the INTERLEAVED virtual-stage schedule —
    see :func:`make_interleaved_pipeline_loss` for the schedule design;
    this function is the single implementation of both (``V == 1``
    reduces the slot map to plain GPipe).

    ``tp_axis``: Megatron tensor parallelism INSIDE each stage — the
    full 3-D DP x PP x TP composition.  Block matmuls are column/row
    sharded over the axis (``staged_param_specs(tp_axis=...)``) and each
    block pays the two psums of :func:`~ddl25spring_tpu.models.llama.
    block_forward`; embed/unembed stay replicated (cheap at the workload
    dmodel; the vocab-sharded head lives in :mod:`parallel.tp`).  Every
    TP member computes the identical loss (psums complete each matmul),
    so the final ``pmean`` over the axis only normalizes the varying
    type — and its transpose restores each member's full cotangent,
    making sharded-weight grads exact (pinned vs serial in tests).

    ``seq_axis``: sequence parallelism INSIDE each stage — long-context
    x staged model (SP x (DP x) PP).  Tokens shard their LENGTH dim over
    the axis (each device holds ``[mb, L/n]`` of every microbatch);
    every block runs ring attention (``sp_mode="ring"``; flash local
    step per ``cfg.use_flash``) or Ulysses all-to-all attention at
    global RoPE positions, and the finishing stage takes the
    sequence-sharded causal loss (one boundary-token ppermute + psum
    pair — :func:`~ddl25spring_tpu.parallel.sp.sp_causal_lm_loss`).
    Activations crossing stage boundaries stay sequence-sharded, so the
    per-device boundary traffic ALSO falls by ``n``.  Composes with
    ``tp_axis`` (PP x SP x TP: the attention fns operate on the local
    head subset the TP column slices produce) and with switch-MoE
    blocks (``cfg.n_experts > 0``: per-seq-shard dispatch groups, the
    aux term on its own scan carry — equal to ``make_sp_loss`` per
    microbatch).  Plain schedule only; ``ep_axis``/``num_chunks``
    compositions with SP are guarded off.
    """
    from ddl25spring_tpu import obs

    S = mesh.shape[stage_axis]
    M = num_microbatches
    V = num_chunks
    dtype = jnp.dtype(cfg.dtype)
    instr = obs.enabled() if instrument is None else bool(instrument)
    if instr:
        obs.counters.add_static("pipeline.num_stages", S)
        obs.counters.add_static("pipeline.num_microbatches", M)
        obs.counters.add_static("pipeline.num_chunks", V)
        obs.counters.add_static(
            "pipeline.bubble_fraction_gpipe",
            obs.gpipe_bubble_fraction(S, M * V),
        )
    if seq_axis is not None:
        if ep_axis is not None:
            raise NotImplementedError(
                "seq_axis with ep_axis is not wired (the EP a2a over "
                "data and the ring over seq are untested together)"
            )
        if V > 1:
            raise NotImplementedError(
                "seq_axis rides the plain (num_chunks=1) gpipe schedule"
            )
        _check_sp(cfg, mesh, seq_axis, sp_mode, tp_axis)
    if V > 1 and M % S:
        raise ValueError(
            f"interleaved schedule needs microbatches ({M}) divisible "
            f"by stages ({S})"
        )
    if tp_axis is not None:
        _check_tp(cfg, mesh, tp_axis)

    moe_fn = None
    if tp_axis is not None and cfg.n_experts > 0:
        moe_fn = _tp_moe_fn(cfg, tp_axis)
    if ep_axis is not None:
        # router is stage-varying but data-invariant inside this
        # shard_map; ep_moe_local pcasts it over the EP(=data) axis
        moe_fn = _ep_moe_fn(cfg, mesh, ep_axis, data_axis, (ep_axis,))

    # [M, mb, L]: microbatch dim shards over data, length over seq
    tok_spec = P(None, data_axis, seq_axis)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            staged_param_specs(
                stage_axis, ep_axis, tp_axis, chunked=V > 1,
                n_experts=cfg.n_experts,
            ),
            tok_spec,
        ),
        out_specs=P(),
    )
    def pipelined(params: Params, tokens_mb: jax.Array) -> jax.Array:
        local_blocks = jax.tree.map(lambda x: x[0], params["blocks"])
        s = lax.axis_index(stage_axis)
        mb, L = tokens_mb.shape[1], tokens_mb.shape[2]
        axes = (
            (stage_axis,)
            + ((data_axis,) if data_axis else ())
            + ((tp_axis,) if tp_axis else ())
            + ((seq_axis,) if seq_axis else ())
        )

        # L is the LOCAL shard length; see _sp_block_kw for why the
        # targets precompute keeps the tick collective-free
        block_kw, targets_mb, valid_row = _sp_block_kw(
            cfg, seq_axis, sp_mode, L, tokens_mb
        )

        # Varying copies of the embed/unembed params, cast OUTSIDE the scan:
        # their cotangent psum (the transpose of this pcast) then executes
        # uniformly on every device.  Using the invariant originals inside
        # ``lax.cond`` would put that psum inside a branch only the last
        # stage takes — a collective in non-uniform control flow.
        # (scoped: that psum is the head's gradient all-reduce)
        with jax.named_scope("grad_allreduce"):
            head = pcast(
                {k: params[k] for k in ("embed", "ln_f", "unembed")},
                axes,
                to="varying",
            )

        def tick(carry, t):
            incoming, loss_sum, aux_sum = carry
            if instr:
                # host arrival time per tick — the cadence estimator for
                # the realized bubble (vs the analytic (S-1)/(M+S-1))
                obs.counters.mark("pipeline.tick", t, force=True)
            # forward slot k = t - s; the slot -> (chunk v, microbatch m)
            # map is Megatron's interleaved grouping (see
            # make_interleaved_pipeline_loss), reducing to plain GPipe
            # (v = 0, m = k) at V == 1
            k = t - s
            active = jnp.logical_and(k >= 0, k < M * V)
            if V == 1:
                m = jnp.clip(k, 0, M - 1)
                chunk = local_blocks
                inject = s == 0
                finish = s == S - 1
            else:
                v, m, _, _ = _slot_map(k, V, S, M)
                chunk = jax.tree.map(
                    lambda x: lax.dynamic_index_in_dim(
                        x, v, 0, keepdims=False
                    ),
                    local_blocks,
                )
                inject = jnp.logical_and(s == 0, v == 0)
                finish = jnp.logical_and(s == S - 1, v == V - 1)

            # the first (virtual) stage injects microbatch m (embed is a
            # cheap gather; the clamp keeps the index static during drain)
            x_first = llama.embed(head, tokens_mb[m], cfg)
            x_in = jnp.where(inject, x_first, incoming)
            if cfg.n_experts > 0:
                x_out, aux = llama.apply_blocks(
                    chunk, x_in, cfg, with_aux=True, moe_fn=moe_fn,
                    tp_axis=tp_axis, **block_kw
                )
                # aux from drain-tick garbage is masked (the weight also
                # zeroes its cotangent)
                w_f = jnp.where(active, 1.0, 0.0).astype(jnp.float32)
                aux_term = w_f * jnp.float32(cfg.moe_aux_weight) * aux
                if instr:
                    # router load-balance per ACTIVE tick: the E·Σ f_e·P_e
                    # product the aux loss measures (1.0 = perfectly
                    # balanced routing; drain ticks excluded by the mask)
                    obs.counters.emit("pipeline.moe_aux", w_f * aux, force=True)
            else:
                x_out = llama.apply_blocks(
                    chunk, x_in, cfg, tp_axis=tp_axis, **block_kw
                )
                aux_term = jnp.float32(0.0)

            # the last (virtual) stage finishes microbatch m on this tick.
            # lax.cond so non-last stages skip the unembed matmul entirely;
            # the zero branch must carry the same varying-axis type as the
            # loss branch (JAX 0.9 shard_map VMA typing)
            if seq_axis is not None:
                # collective-free local CE SUM over this shard's
                # positions (targets + mask precomputed above); the
                # cross-shard psum and the mean normalization happen
                # once, after the scan
                from ddl25spring_tpu.parallel.sp import sp_local_ce_sum

                def loss_branch(x, y):
                    return sp_local_ce_sum(
                        llama.unembed(head, x, cfg), y, valid_row
                    )
            else:
                def loss_branch(x, y):
                    return causal_lm_loss(llama.unembed(head, x, cfg), y)

            with jax.named_scope("head_loss"):
                loss_mb = lax.cond(
                    jnp.logical_and(finish, active),
                    loss_branch,
                    lambda x, y: pcast(jnp.float32(0.0), axes, to="varying"),
                    x_out,
                    targets_mb[m],
                )

            # hand activation to the next stage: the isend/irecv chain of
            # s01_b1_microbatches.py:87-140 as one collective-permute (at
            # V > 1 the wrap S-1 -> 0 is the chunk v -> v+1 hand-off,
            # arriving exactly one tick before its consumption slot)
            with jax.named_scope("stage_permute"):
                outgoing = lax.ppermute(
                    x_out, stage_axis, [(i, (i + 1) % S) for i in range(S)]
                )
            # the aux loss rides its OWN carry: under seq_axis the CE
            # slot holds token-count-normalized SUMS while aux stays a
            # per-dispatch-group mean — one denominator cannot serve both
            return (outgoing, loss_sum + loss_mb, aux_sum + aux_term), None

        carry0 = (
            pcast(jnp.zeros((mb, L, cfg.dmodel), dtype), axes, to="varying"),
            pcast(jnp.float32(0.0), axes, to="varying"),
            pcast(jnp.float32(0.0), axes, to="varying"),
        )
        tick_fn = jax.checkpoint(tick) if remat else tick
        # "schedule" names what the tick scan itself adds around the parts:
        # injecting and carrying activations and, in its transpose,
        # stacking every tick's residuals and summing weight gradients
        with jax.named_scope("schedule"):
            (_, loss_sum, aux_sum), _ = lax.scan(
                tick_fn, carry0, jnp.arange(M * V + S - 1)
            )

        total = lax.psum(loss_sum, stage_axis)
        aux_total = lax.psum(aux_sum, stage_axis) / M
        if seq_axis is not None:
            # the ticks banked LOCAL CE sums; one psum over seq and the
            # global-token-count mean reproduce the serial causal loss
            # (L here is the local shard length).  The aux term is the
            # mean over seq shards of per-shard dispatch-group losses —
            # the standard sharded-MoE estimator, exactly
            # make_sp_loss's (per microbatch)
            n_seq = lax.psum(1, seq_axis)
            total = lax.psum(total, seq_axis) / (
                M * mb * (L * n_seq - 1)
            )
            aux_total = lax.pmean(aux_total, seq_axis)
        else:
            total = total / M
        total = total + aux_total
        if data_axis is not None:
            total = lax.pmean(total, data_axis)
        if tp_axis is not None:
            # every TP member computed the identical loss (psums complete
            # each matmul); the pmean normalizes the varying type, and its
            # transpose restores each member's full cotangent
            total = lax.pmean(total, tp_axis)
        return total

    def loss(params: Params, tokens: jax.Array) -> jax.Array:
        B, L = tokens.shape
        if B % M:
            raise ValueError(f"batch {B} not divisible by {M} microbatches")
        tokens_mb = tokens.reshape(M, B // M, L)
        return pipelined(params, tokens_mb)

    return loss


def make_interleaved_pipeline_loss(
    cfg: LlamaConfig,
    mesh: Mesh,
    num_microbatches: int,
    num_chunks: int,
    stage_axis: str = "stage",
    data_axis: str | None = None,
    remat: bool = False,
    tp_axis: str | None = None,
    ep_axis: str | None = None,
):
    """Interleaved virtual-stage pipeline (Megatron-LM-style chunking).

    Each device holds ``V = num_chunks`` NON-contiguous layer chunks
    (device ``s`` owns global chunks ``{v·S + s}``, split by
    :func:`~ddl25spring_tpu.models.llama.split_blocks_interleaved`), and
    the schedule streams each microbatch around the device ring ``V``
    times.  Why: the pipeline bubble is per-*chunk*, not per-stage —
    schedule length is ``M·V + S - 1`` chunk-ticks versus the
    non-interleaved ``V·(M + S - 1)`` chunk-times of work+bubble, saving
    ``(V-1)(S-1)`` chunk-times of bubble (the classic interleaved
    schedule; bubble fraction falls ~V×) at the price of ``V×`` the
    boundary traffic — the right trade on TPU, where the hop is one ICI
    collective-permute.

    Tick algebra (the whole schedule is these four lines): at tick ``t``
    device ``s`` runs forward slot ``k = t - s``; slot ``k`` maps to
    ``(chunk v, microbatch m)`` by Megatron's grouping —

    - ``g, j = divmod(k, V·S)`` (group of S microbatches, position in it)
    - ``v, r = divmod(j, S)``; ``m = g·S + r``

    so each device does chunk 0 for S microbatches, then chunk 1 for the
    same S, ..., then the next group.  One ``ppermute`` ring hop per tick
    serves every transfer: producer ``(v, m, s)`` finishes at tick
    ``k + s`` and consumer ``(v, m, s+1)`` reads at ``k + s + 1``; the
    wrap ``S-1 → 0`` lands exactly where device 0 needs the ``v+1``
    input ``S`` slots later (``m`` re-enters chunk ``v+1`` after the
    group's other S-1 microbatches).  Device 0 injects the embed on its
    ``v == 0`` slots; device S-1 takes unembed+loss on its ``v == V-1``
    slots.  Backward is the scan transpose (GPipe-style; ``remat=True``
    checkpoints each tick), which replays the same reduced-bubble
    schedule in reverse.

    Constraints: ``M % S == 0`` (groups of S microbatches — the standard
    interleaved-schedule requirement) and ``n_layers % (S·V) == 0``.
    ``num_chunks=1`` reduces exactly to :func:`make_pipeline_loss`, which
    holds the single implementation of both schedules — this wrapper is
    the named entry point for the interleaved design documented above.
    """
    return make_pipeline_loss(
        cfg, mesh, num_microbatches, stage_axis, data_axis, remat,
        num_chunks=num_chunks, tp_axis=tp_axis, ep_axis=ep_axis,
    )


def make_1f1b_value_and_grad(
    cfg: LlamaConfig,
    mesh: Mesh,
    num_microbatches: int,
    stage_axis: str = "stage",
    data_axis: str | None = None,
    stash: str = "input",
    tp_axis: str | None = None,
    ep_axis: str | None = None,
    num_chunks: int = 1,
    seq_axis: str | None = None,
    sp_mode: str = "ring",
):
    """1F1B: the memory-bounded pipeline schedule, hand-rolled backward.

    The reference names 1F1B explicitly (single-batch forward/backward chain,
    ``lab/tutorial_1b/PP/1F1B/intro_PP_1F1B.py:50-95``); its defining
    production property — which GPipe lacks — is the *bounded activation
    live-range*: a stage starts draining backwards before all M microbatch
    forwards have streamed through, so in-flight activations stay O(S)
    instead of O(M).

    The GPipe path here gets backward from the scan transpose, which saves
    every tick's residuals (attention internals included) across all
    ``M + S - 1`` ticks — memory grows linearly in M.  That cannot express
    1F1B, so this schedule writes the backward by hand:

    - tick ``t``: stage ``s`` runs the forward of microbatch ``t - s``
      (GPipe timing) AND the backward of microbatch ``t - (2(S-1) - s)`` —
      in the steady state every stage does one forward and one backward per
      tick, which is exactly 1F1B;
    - each stage stashes only its *input* activation per in-flight
      microbatch in a ring buffer of ``2S - 1`` slots (+1 scratch) — the
      live-range ``2(S-1-s)`` ticks never exceeds it — and the backward
      tick recomputes its stage forward from the stash under ``jax.vjp``
      (rematerialization: one extra stage-forward per microbatch, the
      standard memory/FLOPs trade, cf. ``jax.checkpoint``);
    - boundary cotangents ride a reverse ``ppermute`` (stage ``s`` ->
      ``s - 1``), the mirror of the forward activation hop;
    - schedule length is ``M + 2(S-1)`` ticks vs GPipe's ``M + S - 1``
      forward ticks + transpose drain.

    Activation stash: ``(2S-1) * mb * L * dmodel`` elements, M-invariant —
    vs GPipe's ``(M+S-1)`` tick carries *plus* per-tick block internals.
    Grad/loss equality with GPipe and the serial model is asserted in
    ``tests/test_pipeline.py``.

    Returns ``f(params, tokens) -> (loss, grads)`` with the same contract as
    ``jax.value_and_grad(make_pipeline_loss(...))``.

    Switch-MoE configs are supported: every stage's local loss carries its
    layers' weighted aux term (see :func:`make_pipeline_loss`), so the
    cotangent seed is 1.0 on EVERY stage's loss output, not just the last —
    for dense configs the non-last loss branch is the constant 0, so the
    uniform seed leaves their gradients untouched.

    ``stash`` selects the memory/FLOPs point of the backward:

    - ``"input"`` (default): ring-stash only the stage INPUT; the backward
      tick recomputes the stage forward under ``jax.vjp`` (remat — one
      extra stage-forward per microbatch);
    - ``"residuals"``: the production-standard non-remat 1F1B.  The
      forward slot runs the stage under ``jax.vjp`` and ring-stashes the
      pullback's RESIDUAL arrays (hoisted out of the closure with
      ``jax.closure_convert``); the backward tick replays the converted
      pullback on the stashed residuals — no recompute, at
      ``(2S-1) x |stage residuals|`` memory.  The ring is initialized from
      a valid example trace (not zeros) so drain-tick replays stay finite
      before the ``w = 0`` mask zeroes them.

    ``ep_axis`` (must be the data axis): EP x DP x PP under 1F1B — the
    expert stacks shard over the data axis, each tick's MoE dispatch
    moving capacity buckets between data rows via ``all_to_all``
    (:func:`~ddl25spring_tpu.parallel.ep.ep_moe_local`, same design as
    the GPipe path).  Collectives must sit in UNIFORM control flow, so
    with ``ep_axis`` the forward slot runs the stage body on every tick
    and masks the output (``jnp.where``) instead of ``lax.cond``-skipping
    it — the standard restructure; drain ticks then pay one dead stage
    forward, the price of composing the a2a with the tick schedule.
    Expert-slice grads are per-shard (each data row owns ``E/n`` experts
    assembled from every row's tokens by the a2a transpose), so they take
    ``1/n`` normalization instead of the data ``pmean``.

    ``num_chunks > 1`` is the INTERLEAVED 1F1B — the production Megatron
    schedule: each device holds ``V`` non-contiguous chunks
    (``split_blocks_interleaved``) and BOTH streams ride the Megatron slot
    grouping.  Forward slot ``k = t - s`` maps to ``(chunk v, microbatch
    m)`` exactly as in :func:`make_interleaved_pipeline_loss`; the
    backward stream is its mirror — slot ``k_b = t - (VS-1) - (S-1-s)``
    maps through the SAME grouping onto REVERSED chunks (``v_b = V-1-v'``)
    so cotangents walk the reversed virtual pipeline one device per tick,
    the wrap ``0 -> S-1`` of the reverse ppermute carrying the
    chunk-``v`` -> ``v-1`` hand-off exactly one tick before use.  The
    delay ``VS - 1`` is the tightest that keeps every backward after its
    forward (equality holds at ``(V-1, S-1)``: same-tick fwd+bwd, as at
    ``V = 1``).  The input ring grows to ``2VS - 1`` slots (max live
    range ``2VS - 2`` ticks at ``(v=0, s=0)``), still M-invariant —
    O(S·V) activations versus the scan-transpose interleaved schedule's
    O(M·V) — and the schedule length is ``MV + VS + S - 2`` chunk-ticks
    versus plain 1F1B's ``V(M + 2S - 2)``: the ``(V-1)(S-2)``-chunk-tick
    bubble win of interleaving composed with the bounded memory of 1F1B.
    ``V = 1`` reduces every formula to the plain schedule above (this is
    the single implementation of both).  ``stash`` must be ``"input"``
    under ``num_chunks > 1``; ``ep_axis`` composes (the EP branch runs
    the chunk unconditionally with a masked output, as at V = 1).
    """
    if stash not in ("input", "residuals"):
        raise ValueError(f"stash must be 'input' or 'residuals', got {stash!r}")
    S = mesh.shape[stage_axis]
    M = num_microbatches
    V = num_chunks
    dtype = jnp.dtype(cfg.dtype)
    K = 2 * V * S - 1  # ring slots; slot K is scratch for inactive ticks
    DELTA = V * S - 1  # backward-stream delay (== S-1 at V == 1)
    if seq_axis is not None:
        # SP under the hand-rolled 1F1B: same design as the GPipe path
        # (pre-scan boundary targets, collective-free per-tick loss sums,
        # unconditional-masked forward slot so the ring/a2a collectives
        # stay uniform), plus psum-over-seq grad assembly at the end
        if cfg.n_experts > 0 or ep_axis is not None:
            raise NotImplementedError(
                "SP under 1F1B ships dense blocks (no MoE/EP composition)"
            )
        if stash != "input":
            raise NotImplementedError(
                "SP under 1F1B rides the remat (stash='input') backward"
            )
        _check_sp(cfg, mesh, seq_axis, sp_mode, tp_axis)
    if V > 1:
        if stash != "input":
            raise NotImplementedError(
                "interleaved 1F1B ships the input-stash (remat) backward; "
                "residual rings are not wired for chunked stacks"
            )
        if M % S:
            raise ValueError(
                f"interleaved schedule needs microbatches ({M}) divisible "
                f"by stages ({S})"
            )
    if tp_axis is not None:
        _check_tp(cfg, mesh, tp_axis)

    tok_spec = P(None, data_axis, seq_axis)
    # one spec tree serves both sides: param grads come back in the same
    # layout the params go in
    param_specs = staged_param_specs(
        stage_axis, ep_axis=ep_axis, tp_axis=tp_axis, chunked=V > 1,
        n_experts=cfg.n_experts,
    )
    moe_fn = (
        _tp_moe_fn(cfg, tp_axis)
        if tp_axis is not None and cfg.n_experts > 0 else None
    )
    if ep_axis is not None:
        # the router is pcast over data with the other invariant block
        # leaves below, so vary_axes is empty here (unlike the GPipe
        # path, which keeps blocks invariant over data)
        moe_fn = _ep_moe_fn(cfg, mesh, ep_axis, data_axis, ())

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(param_specs, tok_spec),
        out_specs=(P(), param_specs),
    )
    def value_and_grad(params: Params, tokens_mb: jax.Array):
        local_blocks = jax.tree.map(lambda x: x[0], params["blocks"])
        s = lax.axis_index(stage_axis)
        mb, L = tokens_mb.shape[1], tokens_mb.shape[2]
        axes = (
            (stage_axis,)
            + ((data_axis,) if data_axis else ())
            + ((tp_axis,) if tp_axis else ())
            + ((seq_axis,) if seq_axis else ())
        )

        head = pcast(
            {k: params[k] for k in ("embed", "ln_f", "unembed")},
            axes,
            to="varying",
        )
        # blocks are varying over stage (and tp, when sharded) already;
        # the data and seq axes need the explicit pcast — per-shard
        # "copies" whose grads the final assembly combines explicitly
        # (an invariant weight would instead get an implicit cotangent
        # psum inside EVERY tick's vjp: one hidden collective per tick,
        # and double-counting under the explicit assembly)
        vary = ((data_axis,) if data_axis else ()) + (
            (seq_axis,) if seq_axis else ()
        )
        if vary and ep_axis:
            # the expert stacks arrive SHARDED (hence varying) over the
            # data axis; pcast only the data-invariant leaves (ep and
            # seq are mutually exclusive, so vary == (data_axis,))
            vblocks = {
                k: pcast(v, vary, to="varying")
                for k, v in local_blocks.items() if k != "moe"
            }
            vblocks["moe"] = dict(
                local_blocks["moe"],
                router=pcast(
                    local_blocks["moe"]["router"], vary, to="varying"
                ),
            )
        elif vary:
            vblocks = pcast(local_blocks, vary, to="varying")
        else:
            vblocks = local_blocks

        is_last = s == S - 1

        # same design as the GPipe seq path (shared _sp_block_kw)
        block_kw, targets_mb, valid_row = _sp_block_kw(
            cfg, seq_axis, sp_mode, L, tokens_mb
        )
        if seq_axis is not None:
            from ddl25spring_tpu.parallel.sp import sp_local_ce_sum

        def local_fwd_loss(
            blocks, hd, x_in, tok, inject=None, finish=None, embed_in=True,
            tgt=None,
        ):
            """This (virtual) stage's slice of the model, as one
            differentiable fn: the injecting slot prepends embed
            (``embed_in=True``), the finishing slot appends unembed+loss;
            MoE stages add their layers' weighted aux loss.  ``inject`` /
            ``finish`` default to the plain-1F1B flags (first / last
            device); the interleaved schedule passes its slot-dependent
            flags.  ``tgt`` (defaults to ``tok``) carries the loss
            targets when they differ from the embed tokens — the SP path,
            whose targets are the pre-shifted boundary-ppermute output.
            The residual-stash path passes ``embed_in=False`` and handles
            the embed outside — see the closure_convert note there."""
            inject = (s == 0) if inject is None else inject
            finish = is_last if finish is None else finish
            tgt = tok if tgt is None else tgt
            if embed_in:
                x_in = lax.cond(
                    inject,
                    lambda x: llama.embed(hd, tok, cfg),
                    lambda x: x,
                    x_in,
                )
            if cfg.n_experts > 0:
                x_out, aux = llama.apply_blocks(
                    blocks, x_in, cfg, with_aux=True, moe_fn=moe_fn,
                    tp_axis=tp_axis,
                )
                aux_term = jnp.float32(cfg.moe_aux_weight) * aux
            else:
                x_out = llama.apply_blocks(
                    blocks, x_in, cfg, tp_axis=tp_axis, **block_kw
                )
                aux_term = jnp.float32(0.0)
            if seq_axis is not None:
                # collective-free local CE SUM (psum + mean after the scan)
                def loss_branch(x):
                    return sp_local_ce_sum(
                        llama.unembed(hd, x, cfg), tgt, valid_row
                    )
            else:
                def loss_branch(x):
                    return causal_lm_loss(llama.unembed(hd, x, cfg), tgt)

            with jax.named_scope("head_loss"):
                loss = lax.cond(
                    finish,
                    loss_branch,
                    lambda x: pcast(jnp.float32(0.0), axes, to="varying"),
                    x_out,
                )
            return x_out, loss + aux_term

        def chunk_slice(tree, v):
            """Chunk ``v``'s blocks from the local ``[V, Lc, ...]`` stacks
            (identity at V == 1, where the stacks are ``[Lc, ...]``)."""
            if V == 1:
                return tree
            return jax.tree.map(
                lambda x: lax.dynamic_index_in_dim(x, v, 0, keepdims=False),
                tree,
            )

        def fwd_slot(k):
            """Megatron slot map (``_slot_map``): forward slot ``k`` ->
            (chunk ``v``, microbatch ``m``, and the inject/finish flags
            for this device)."""
            if V == 1:
                m = jnp.clip(k, 0, M - 1)
                return 0, m, s == 0, is_last
            v, m, _, _ = _slot_map(k, V, S, M)
            return v, m, jnp.logical_and(s == 0, v == 0), jnp.logical_and(
                is_last, v == V - 1
            )

        def bwd_slot(k_b):
            """The mirrored backward stream: slot ``k_b`` maps through
            the SAME ``_slot_map`` grouping onto REVERSED chunks, plus
            the ring index of the matching forward slot (where its input
            was stashed)."""
            if V == 1:
                m = jnp.clip(k_b, 0, M - 1)
                return 0, m, jnp.clip(k_b, 0, M - 1), s == 0, is_last
            v_rev, m, r, g = _slot_map(k_b, V, S, M)
            v = V - 1 - v_rev
            k_fwd = g * V * S + v * S + r  # forward slot of (v, m)
            return v, m, k_fwd, jnp.logical_and(s == 0, v == 0), (
                jnp.logical_and(is_last, v == V - 1)
            )

        def tick(carry, t):
            fwd_in, cot_in, ring, gblocks, ghead, loss_sum = carry

            # ---- forward slot: GPipe timing (slot k = t - s) --------------
            f_idx = t - s
            fwd_active = jnp.logical_and(f_idx >= 0, f_idx < M * V)
            v_f, m_f, inject_f, finish_f = fwd_slot(f_idx)
            tok_f = tokens_mb[m_f]
            x_first = llama.embed(head, tok_f, cfg)
            x_in = jnp.where(inject_f, x_first, fwd_in)
            # stash the stage INPUT (all the backward needs — the stage body
            # is recomputed); inactive ticks write the scratch slot
            ring = lax.dynamic_update_index_in_dim(
                ring, x_in, jnp.where(fwd_active, f_idx % K, K), axis=0
            )
            # a finishing slot's forward is fully redone by its same-tick
            # backward below; skip the dead compute.  Under EP the stage
            # body carries an all_to_all, which must execute in UNIFORM
            # control flow — run it unconditionally and mask the output
            # instead (drain ticks pay one dead stage forward)
            run_fwd = jnp.logical_and(fwd_active, jnp.logical_not(finish_f))
            if ep_axis is not None or seq_axis is not None:
                # EP's a2a / SP's ring collectives must execute in
                # uniform control flow: run unconditionally, mask
                x_body = llama.apply_blocks(
                    chunk_slice(vblocks, v_f), x_in, cfg, tp_axis=tp_axis,
                    moe_fn=moe_fn, **block_kw
                )
                x_out = jnp.where(run_fwd, x_body, x_in)
            else:
                chunk_f = chunk_slice(local_blocks, v_f)
                x_out = lax.cond(
                    run_fwd,
                    lambda x: llama.apply_blocks(
                        chunk_f, x, cfg, tp_axis=tp_axis, moe_fn=moe_fn
                    ),
                    lambda x: x,
                    x_in,
                )

            # ---- backward slot: the reversed stream at delay VS-1 (mb b
            # finishes its last chunk at the last device and walks the
            # reversed virtual pipeline one device per tick) ----------------
            b_idx = t - DELTA - (S - 1 - s)
            bwd_active = jnp.logical_and(b_idx >= 0, b_idx < M * V)
            v_b, m_b, k_fwd_b, inject_b, finish_b = bwd_slot(b_idx)
            x_saved = ring[
                jnp.clip(jnp.where(bwd_active, k_fwd_b % K, K), 0, K)
            ]
            tok_b = tokens_mb[m_b]
            tgt_b = targets_mb[m_b]
            vchunk_b = chunk_slice(vblocks, v_b)

            (x_out_b, loss_b), pull = jax.vjp(
                lambda b, h, x: local_fwd_loss(
                    b, h, x, tok_b, inject_b, finish_b, tgt=tgt_b
                ),
                vchunk_b, head, x_saved,
            )
            # cotangent seed: downstream cotangent for interior slots, the
            # scalar loss for the finishing one (its x_out feeds nothing but
            # the loss).  The loss seed is 1.0 on EVERY slot: non-finishing
            # dense slots output the constant 0 (zero pullback), and MoE
            # chunks need their aux term differentiated
            g_out = jnp.where(finish_b, jnp.zeros_like(cot_in), cot_in)
            g_loss = pcast(jnp.float32(0.0), axes, to="varying") + 1.0
            db, dh, dx = pull((g_out.astype(x_out_b.dtype), g_loss))

            w = jnp.where(bwd_active, jnp.float32(1.0), jnp.float32(0.0))
            if V == 1:
                gblocks = jax.tree.map(lambda a, g: a + w * g, gblocks, db)
            else:
                # scatter-accumulate into chunk v_b's slice of the
                # [V, Lc, ...] grad stacks
                gblocks = jax.tree.map(
                    lambda a, g: a.at[v_b].add(w * g), gblocks, db
                )
            ghead = jax.tree.map(lambda a, g: a + w * g, ghead, dh)
            loss_sum = loss_sum + w * loss_b

            # ---- boundary hops: activations forward, cotangents back ------
            with jax.named_scope("stage_permute"):
                fwd_next = lax.ppermute(
                    x_out, stage_axis, [(i, (i + 1) % S) for i in range(S)]
                )
                cot_next = lax.ppermute(
                    dx, stage_axis, [(i, (i - 1) % S) for i in range(S)]
                )
            return (fwd_next, cot_next, ring, gblocks, ghead, loss_sum), None

        def vzeros(x, dt=None):
            return pcast(
                jnp.zeros(jnp.shape(x), dt or jnp.result_type(x)),
                axes, to="varying",
            )

        gzero = (
            jax.tree.map(lambda x: vzeros(x, jnp.float32), local_blocks),
            jax.tree.map(lambda x: vzeros(x, jnp.float32), head),
        )
        # schedule length: M + 2(S-1) at V == 1; MV + VS + S - 2 interleaved
        T = M * V + V * S + S - 2

        if stash == "residuals":
            # One example trace of the stage vjp: closure_convert hoists
            # the pullback's closed-over residuals into an explicit array
            # list (its design use), giving the ring element shapes.
            #
            # CAVEAT that shapes this path: closure_convert hoists only
            # consts on the PERTURBED (differentiable) path; the integer
            # token batch stays baked in the converted callable's closure,
            # i.e. a replay would read the REPLAYING tick's tokens.  The
            # last stage is immune (its backward is same-tick, f_idx ==
            # b_idx, and it is the only consumer of the CE targets), but
            # stage 0's embed-gather indices would be 2(S-1) ticks stale.
            # So the embed runs OUTSIDE the vjp (embed_in=False), tokens
            # get their own int ring, and the embed gradient is formed
            # explicitly at the backward slot: a scatter-add of the x_in
            # cotangent at the stashed token ids.
            ex_x = vzeros(jnp.empty((mb, L, cfg.dmodel)), dtype)
            ex_tok = tokens_mb[0]
            _, ex_pull = jax.vjp(
                lambda b, h, x: local_fwd_loss(b, h, x, ex_tok, embed_in=False),
                vblocks, head, ex_x,
            )
            ex_cot = (
                vzeros(jnp.empty((mb, L, cfg.dmodel)), dtype),
                pcast(jnp.float32(0.0), axes, to="varying"),
            )
            _, ex_consts = jax.closure_convert(ex_pull, ex_cot)
            # ring slots start from the VALID example residuals, not zeros:
            # drain-tick replays then stay finite before the w=0 mask
            ring0 = [jnp.repeat(c[None], K + 1, axis=0) for c in ex_consts]
            tok_ring0 = vzeros(jnp.empty((K + 1, mb, L)), jnp.int32)

            def tick_res(carry, t):
                fwd_in, cot_in, ring, tok_ring, gblocks, ghead, loss_sum = carry

                # ---- forward slot: run the stage under vjp, stash the
                # pullback residuals (no recompute at backward) ----------
                f_idx = t - s
                fwd_active = jnp.logical_and(f_idx >= 0, f_idx < M)
                tok_f = tokens_mb[jnp.clip(f_idx, 0, M - 1)]
                x_first = llama.embed(head, tok_f, cfg)
                x_in = jnp.where(s == 0, x_first, fwd_in)
                (x_out, loss_f), pull_f = jax.vjp(
                    lambda b, h, x: local_fwd_loss(
                        b, h, x, tok_f, embed_in=False
                    ),
                    vblocks, head, x_in,
                )
                # the converted pullback MUST come from this same trace so
                # the ring's write (consts_f) and read (consts_b) agree on
                # const ordering; the example trace above only sizes the
                # ring (its const VALUES are scratch initialization)
                pull_conv, consts_f = jax.closure_convert(pull_f, ex_cot)
                idx_w = jnp.where(fwd_active, f_idx % K, K)
                ring = [
                    lax.dynamic_update_index_in_dim(r, c, idx_w, 0)
                    for r, c in zip(ring, consts_f)
                ]
                tok_ring = lax.dynamic_update_index_in_dim(
                    tok_ring, tok_f, idx_w, 0
                )
                # loss is banked at the forward slot here (the backward
                # replay no longer recomputes it)
                w_f = jnp.where(fwd_active, jnp.float32(1.0), jnp.float32(0.0))
                loss_sum = loss_sum + w_f * loss_f

                # ---- backward slot: replay the converted pullback on the
                # ring residuals (same-tick write-then-read serves the
                # last stage, where f_idx == b_idx) ----------------------
                b_idx = t - (2 * (S - 1) - s)
                bwd_active = jnp.logical_and(b_idx >= 0, b_idx < M)
                idx_r = jnp.clip(jnp.where(bwd_active, b_idx % K, K), 0, K)
                consts_b = [r[idx_r] for r in ring]
                tok_b = tok_ring[idx_r]
                g_out = jnp.where(is_last, jnp.zeros_like(cot_in), cot_in)
                g_loss = pcast(jnp.float32(0.0), axes, to="varying") + 1.0
                db, dh, dx = pull_conv(
                    (g_out.astype(x_out.dtype), g_loss), *consts_b
                )
                # stage 0's embed grad, by hand: scatter dx at the STASHED
                # token ids (dh["embed"] from the vjp is zero — the fn no
                # longer touches it)
                is0 = jnp.where(s == 0, jnp.float32(1.0), jnp.float32(0.0))
                dE = jnp.zeros_like(ghead["embed"]).at[
                    tok_b.reshape(-1)
                ].add(dx.astype(jnp.float32).reshape(-1, cfg.dmodel))
                dh = dict(dh, embed=dh["embed"] + is0 * dE)
                w = jnp.where(bwd_active, jnp.float32(1.0), jnp.float32(0.0))
                gblocks = jax.tree.map(lambda a, g: a + w * g, gblocks, db)
                ghead = jax.tree.map(lambda a, g: a + w * g, ghead, dh)

                with jax.named_scope("stage_permute"):
                    fwd_next = lax.ppermute(
                        x_out, stage_axis,
                        [(i, (i + 1) % S) for i in range(S)],
                    )
                    cot_next = lax.ppermute(
                        dx, stage_axis,
                        [(i, (i - 1) % S) for i in range(S)],
                    )
                return (
                    fwd_next, cot_next, ring, tok_ring, gblocks, ghead,
                    loss_sum,
                ), None

            carry0 = (
                vzeros(jnp.empty((mb, L, cfg.dmodel)), dtype),
                vzeros(jnp.empty((mb, L, cfg.dmodel)), dtype),
                ring0,
                tok_ring0,
                *gzero,
                pcast(jnp.float32(0.0), axes, to="varying"),
            )
            (_, _, _, _, gblocks, ghead, loss_sum), _ = lax.scan(
                tick_res, carry0, jnp.arange(T)
            )
        else:
            carry0 = (
                vzeros(jnp.empty((mb, L, cfg.dmodel)), dtype),      # fwd act
                vzeros(jnp.empty((mb, L, cfg.dmodel)), dtype),      # cotangent
                vzeros(jnp.empty((K + 1, mb, L, cfg.dmodel)), dtype),  # stash
                *gzero,
                pcast(jnp.float32(0.0), axes, to="varying"),
            )
            (_, _, _, gblocks, ghead, loss_sum), _ = lax.scan(
                tick, carry0, jnp.arange(T)
            )

        # mean over microbatches; DP mean over the data axis (the automatic
        # cotangent psum of the GPipe path, done by hand here)
        if seq_axis is not None:
            # the ticks banked LOCAL CE sums and every seq shard
            # accumulated only its own compute's grad paths: one psum
            # over seq assembles both, then the global-token-count mean
            # replaces the /M (L here is the local shard length)
            n_sq = lax.psum(1, seq_axis)
            norm = M * mb * (L * n_sq - 1)
            loss = lax.psum(
                lax.psum(loss_sum, stage_axis), seq_axis
            ) / norm
            gblocks = jax.tree.map(
                lambda g: lax.psum(g, seq_axis)[None] / norm, gblocks
            )
            ghead = jax.tree.map(
                lambda g: lax.psum(g, seq_axis) / norm, ghead
            )
        else:
            loss = lax.psum(loss_sum, stage_axis) / M
            gblocks = jax.tree.map(lambda g: g[None] / M, gblocks)
            ghead = jax.tree.map(lambda g: g / M, ghead)
        ghead = jax.tree.map(lambda g: lax.psum(g, stage_axis), ghead)
        if tp_axis is not None:
            # the uniform 1.0 seed on every TP member differentiates the
            # SUM of t identical loss copies (each member's loss depends on
            # every member's weight slice through the in-block psums, and
            # the cooperative vjp assembles the full cross-member flow
            # locally), so every hand-accumulated grad is t x the true
            # gradient.  Normalization (what the GPipe TP path gets from
            # its final pmean's transpose automatically, measured leaf by
            # leaf against the serial model): the head grads carry
            # per-member PARTIALS -> pmean (= psum/t); the tp-sharded
            # matmul slices and the block norm scales are already fully
            # assembled on every member by the cooperative vjp (the
            # in-block psum transposes hand each member the complete
            # downstream flow) -> scale by 1/t, with the norm scales
            # additionally pmean-re-typed (identical across members, but
            # their P(stage) out_spec needs the static invariance)
            t = lax.psum(1, tp_axis)
            loss = lax.pmean(loss, tp_axis)

            def _norm_repl(g):
                return lax.pmean(g / t, tp_axis)

            def _norm_shard(g):
                return g / t

            def _norm(k, v):
                if k == "moe":
                    # router is replicated across tp like the norms (its
                    # P(stage) out_spec needs the invariance re-typing);
                    # the expert stacks are tp-sharded slices like the
                    # dense matmuls
                    return {
                        kk: (_norm_repl if kk == "router" else _norm_shard)(vv)
                        for kk, vv in v.items()
                    }
                return (_norm_repl if k in ("ln1", "ln2") else _norm_shard)(v)

            gblocks = {k: _norm(k, v) for k, v in gblocks.items()}
            ghead = jax.tree.map(lambda g: lax.pmean(g, tp_axis), ghead)
        if data_axis is not None:
            loss = lax.pmean(loss, data_axis)
            if ep_axis is not None:
                # expert slices are per-shard (each data row owns E/n
                # experts, their grads already assembled from every row's
                # tokens by the a2a transpose): 1/n normalization, no
                # collective — a pmean would average DIFFERENT experts.
                # The replicated router keeps the invariant treatment.
                n = lax.psum(1, data_axis)
                gmoe = gblocks["moe"]
                gblocks = {
                    k: jax.tree.map(lambda g: lax.pmean(g, data_axis), v)
                    for k, v in gblocks.items() if k != "moe"
                }
                gblocks["moe"] = {
                    kk: (lax.pmean(vv, data_axis) if kk == "router"
                         else vv / n)
                    for kk, vv in gmoe.items()
                }
            else:
                gblocks = jax.tree.map(
                    lambda g: lax.pmean(g, data_axis), gblocks
                )
            ghead = jax.tree.map(lambda g: lax.pmean(g, data_axis), ghead)
        grads = {
            "embed": ghead["embed"],
            "blocks": gblocks,
            "ln_f": ghead["ln_f"],
            "unembed": ghead["unembed"],
        }
        return loss, grads

    def f(params: Params, tokens: jax.Array):
        B, L = tokens.shape
        if B % M:
            raise ValueError(f"batch {B} not divisible by {M} microbatches")
        return value_and_grad(params, tokens.reshape(M, B // M, L))

    return f


def make_pipeline_train_step(
    cfg: LlamaConfig,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    num_microbatches: int,
    stage_axis: str = "stage",
    data_axis: str | None = None,
    schedule: str = "gpipe",
    ep_axis: str | None = None,
    num_chunks: int = 1,
    tp_axis: str | None = None,
    seq_axis: str | None = None,
    sp_mode: str = "ring",
    donate: bool | None = None,
    sentinel: bool | None = None,
):
    """Jitted train step for the (DPx)PP llama workload: the one-program
    replacement for the reference's 3- or 6-process schedule + per-group
    all_reduce + Adam step (``s01_b2_dp_pp.py:93-227``).

    ``schedule``: ``"gpipe"`` (scan-transpose backward, parity with the
    homework B1 microbatch solution), ``"1f1b"`` (memory-bounded
    interleaved schedule with remat backward, parity with
    ``intro_PP_1F1B.py`` generalized to M microbatches),
    ``"1f1b-stash"`` (non-remat 1F1B: pullback residuals ring-stashed,
    no forward recompute — see :func:`make_1f1b_value_and_grad`),
    ``"interleaved"`` (virtual-stage chunking with ``num_chunks`` chunks
    per device, bubble reduced ~V× — see
    :func:`make_interleaved_pipeline_loss`; params split by
    ``split_blocks_interleaved``), or ``"interleaved-1f1b"`` (the
    production Megatron schedule: interleaved virtual stages WITH the
    memory-bounded hand-rolled 1F1B backward — O(S·V) ring stash instead
    of the scan transpose's O(M·V) residuals; params split by
    ``split_blocks_interleaved``).

    ``ep_axis``: shard the MoE expert stacks over the data axis too
    (EP x DP x PP) — on EVERY schedule: gpipe and interleaved (see
    :func:`make_pipeline_loss`), both 1F1B stashes and interleaved-1F1B
    (see :func:`make_1f1b_value_and_grad`).  Pass params through
    ``shard_staged_params(..., ep_axis=...)`` (``chunked=True`` for the
    interleaved 5-d expert stacks).

    ``tp_axis``: Megatron TP inside each stage (DP x PP x TP) on EVERY
    schedule; pass params through ``shard_staged_params(..., tp_axis=...)``
    (adding ``chunked=True`` for the interleaved 5-d stacks).

    ``seq_axis``: sequence parallelism inside each stage (SP x (DP x)
    PP, gpipe schedule only — see :func:`make_pipeline_loss`); tokens
    shard their length dim over the axis, ``sp_mode`` picks
    ring/ulysses attention.

    ``donate`` (default on): params/opt-state buffers alias in place
    (:func:`~ddl25spring_tpu.parallel.dp.donate_argnums`); ``sentinel``
    opts into the in-step numerics sentinels
    (:mod:`ddl25spring_tpu.obs.sentinels`).
    """
    from ddl25spring_tpu.obs import sentinels

    s_on, s_policy = sentinels.resolve(sentinel)
    if seq_axis is not None and schedule not in (
        "gpipe", "1f1b", "interleaved-1f1b"
    ):
        raise NotImplementedError(
            "seq_axis rides gpipe, 1f1b, and interleaved-1f1b (the "
            "residual-stash and scan-transpose-interleaved backwards "
            "are not wired for sequence-sharded stages)"
        )
    if num_chunks > 1 and schedule not in ("interleaved", "interleaved-1f1b"):
        # silently falling back to plain GPipe would train a different
        # schedule than asked for AND fail later at shard_map spec-rank
        # mismatch if the params were split with split_blocks_interleaved
        raise ValueError(
            f"num_chunks={num_chunks} needs schedule='interleaved' or "
            f"'interleaved-1f1b' (got {schedule!r})"
        )
    if schedule == "interleaved":
        loss_fn = make_interleaved_pipeline_loss(
            cfg, mesh, num_microbatches, num_chunks, stage_axis, data_axis,
            tp_axis=tp_axis, ep_axis=ep_axis,
        )
        vag = jax.value_and_grad(loss_fn)
    elif schedule == "interleaved-1f1b":
        if num_chunks < 2:
            raise ValueError("interleaved-1f1b needs num_chunks >= 2")
        vag = make_1f1b_value_and_grad(
            cfg, mesh, num_microbatches, stage_axis, data_axis,
            stash="input", tp_axis=tp_axis, ep_axis=ep_axis,
            num_chunks=num_chunks, seq_axis=seq_axis, sp_mode=sp_mode,
        )
    elif schedule in ("1f1b", "1f1b-stash"):
        vag = make_1f1b_value_and_grad(
            cfg, mesh, num_microbatches, stage_axis, data_axis,
            stash="residuals" if schedule == "1f1b-stash" else "input",
            tp_axis=tp_axis, ep_axis=ep_axis, seq_axis=seq_axis,
            sp_mode=sp_mode,
        )
    elif schedule == "gpipe":
        loss_fn = make_pipeline_loss(
            cfg, mesh, num_microbatches, stage_axis, data_axis,
            ep_axis=ep_axis, tp_axis=tp_axis, seq_axis=seq_axis,
            sp_mode=sp_mode,
        )
        vag = jax.value_and_grad(loss_fn)
    else:
        raise ValueError(f"unknown schedule {schedule!r}")

    @partial(jax.jit, donate_argnums=donate_argnums(donate))
    def step(params, opt_state, tokens):
        loss, grads = vag(params, tokens)
        with jax.named_scope("optimizer"):
            updates, new_state = tx.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
        new_params, new_state = sentinels.guard(
            "pipeline", (new_params, new_state), loss=loss, grads=grads,
            params=params, updates=updates,
            fallback=(params, opt_state), enabled=s_on, policy=s_policy,
        )
        return new_params, new_state, loss

    return step


def fuse_train_steps(step_fn, k: int, donate: bool | None = None):
    """Fuse ``k`` train steps into ONE dispatched program.

    ``step_fn(params, opt_state, tokens) -> (params, opt_state, loss)``
    (any schedule from :func:`make_pipeline_train_step`) becomes
    ``multi(params, opt_state, tokens_k)`` over stacked ``[k, B, L]``
    token batches, scanning the step as the ``lax.scan`` body and
    returning the per-step ``[k]`` loss vector.

    Why: each Python dispatch pays a host round-trip.  At the
    reference-parity config (batch 3, ctx 256 — 768 tokens/step,
    `lab/run-b1.sh`) a step is small enough for dispatch to dominate, so
    the fused scan multiplies throughput; at large batch it amortizes to
    noise (on-chip numbers: not measured).  Same trick as ``benchmarks.build_resnet_scan_step``, input
    semantics preserved exactly: the K batches are REAL distinct batches
    staged to HBM once per dispatch (equality with K sequential steps is
    pinned in ``tests/test_pipeline.py``).  TPU-path oriented: on the
    XLA CPU backend scans over large bodies run slower than dispatched
    steps — CPU callers should keep k=1.
    """

    @partial(jax.jit, donate_argnums=donate_argnums(donate))
    def multi(params, opt_state, tokens_k):
        if tokens_k.shape[0] != k:
            raise ValueError(
                f"fused for {k} steps but got a window of "
                f"{tokens_k.shape[0]} batches — caller accounting would "
                "silently drift"
            )

        def body(carry, toks):
            p, o = carry
            p, o, loss = step_fn(p, o, toks)
            return (p, o), loss

        (params, opt_state), losses = lax.scan(
            body, (params, opt_state), tokens_k
        )
        return params, opt_state, losses

    return multi


def shard_staged_params(
    params: Params,
    mesh: Mesh,
    stage_axis: str = "stage",
    ep_axis: str | None = None,
    tp_axis: str | None = None,
    chunked: bool | None = None,
):
    """Place staged params on the mesh: blocks sharded over the stage axis,
    the rest replicated — each device holds only its stages' layers, like
    each reference rank building only its own ``LLamaStage``.  With
    ``ep_axis``, the expert stacks additionally shard over that axis
    (each device then holds only ``E/n`` experts of its stages); with
    ``tp_axis``, block matmuls additionally column/row-shard over it
    (DP x PP x TP).

    ``chunked`` (params from ``split_blocks_interleaved``: 5-d
    ``[S, V, Lc, d, d]`` stacks, so the EP/TP specs must target the
    matmul/expert dims past the extra chunk dim) is INFERRED from the
    tree by default — a forgotten explicit flag under ``ep_axis`` would
    silently shard the layer dim over the expert axis.  Switch-MoE
    params are detected from the tree too (the ``moe`` subtree) so the
    TP branch emits the expert-sharded schema instead of failing on the
    dense key set."""
    n_experts = (
        params["blocks"]["moe"]["router"].shape[-1]
        if "moe" in params["blocks"] else 0
    )
    if chunked is None:
        # dense-split wq stacks are [S, Lc, d, d]; interleaved add a
        # chunk dim -> 5-d
        wq = params["blocks"]["wq"]
        chunked = getattr(wq, "ndim", len(jnp.shape(wq))) == 5
    specs = staged_param_specs(
        stage_axis, ep_axis, tp_axis, chunked, n_experts=n_experts
    )
    blocks_spec = specs["blocks"]
    if isinstance(blocks_spec, P):
        blocks = jax.tree.map(
            lambda _: NamedSharding(mesh, blocks_spec), params["blocks"]
        )
    else:
        blocks = jax.tree.map(
            lambda sp: NamedSharding(mesh, sp), blocks_spec,
            is_leaf=lambda x: isinstance(x, P),
        )
    shardings = {
        "embed": NamedSharding(mesh, specs["embed"]),
        "blocks": blocks,
        "ln_f": NamedSharding(mesh, specs["ln_f"]),
        "unembed": NamedSharding(mesh, specs["unembed"]),
    }
    return jax.device_put(params, shardings)


def describe(
    mesh: Mesh,
    num_microbatches: int = 4,
    stage_axis: str = "stage",
    data_axis: str | None = None,
):
    """Registry hook for :mod:`ddl25spring_tpu.obs.xla_analytics`: the
    lowerable GPipe program + example inputs + the analytic collective
    signature.

    The GPipe schedule's signature is ONE ``collective-permute`` site
    inside the tick scan, executed ``M + S - 1`` times per forward pass
    (XLA pins the trip count on the optimized while op) — i.e.
    "microbatches + stages - 1 boundary hops per direction".  The hook
    lowers ``value_and_grad``: the scan transpose replays the permutes in
    reverse, doubling the executions, and autodiff sums the grads of the
    leaves every stage holds whole (embedding, final norm, head) over the
    stage axis — the one all-reduce the signature declares.
    """
    if data_axis is None and "data" in mesh.axis_names:
        data_axis = "data"  # --mesh 2x2 style requests: ride DP x PP
    cfg = LlamaConfig(
        vocab_size=64, dmodel=16, num_heads=2, n_layers=4, ctx_size=16,
        dtype="float32",
    )
    S = mesh.shape[stage_axis]
    M = num_microbatches
    dp = mesh.shape[data_axis] if data_axis else 1
    mb = 2
    params = llama.init_llama_params(jax.random.PRNGKey(0), cfg)
    staged = llama.split_blocks_for_stages(params, S)
    loss = make_pipeline_loss(
        cfg, mesh, M, stage_axis, data_axis, instrument=False
    )
    tokens = jnp.zeros((M * mb * dp, cfg.ctx_size), jnp.int32)
    fn = jax.jit(jax.value_and_grad(loss))
    T = M + S - 1
    hops = 2 * T  # transpose replays the ring in reverse
    boundary_bytes = mb * cfg.ctx_size * cfg.dmodel * 4  # f32 activations
    all_bytes = sum(x.nbytes for x in jax.tree.leaves(staged))
    shared_bytes = all_bytes - sum(
        x.nbytes for x in jax.tree.leaves(staged["blocks"])
    )
    return {
        "fn": fn,
        "args": (staged, tokens),
        "lowered": "value_and_grad",
        "meta": {
            "num_stages": S,
            "num_microbatches": M,
            "ticks": T,
            "boundary_bytes": boundary_bytes,
            "bubble_fraction": (S - 1) / T,
        },
        "expected": {
            "scalar_bytes": 64,
            "collective-permute": {
                "min_count": hops,
                # fusion may not merge every hop; a stray EXTRA permute
                # per tick (e.g. an accidentally stage-varying carry)
                # would exceed this
                "max_count": hops + T,
                "axes": [stage_axis],
            },
            # grads of the stage-replicated leaves, summed over stage
            # (under DP x PP every grad leaf is reduced over data too),
            # beside the scalar loss reductions
            "all-reduce": {
                "min_bytes": shared_bytes,
                "max_bytes": shared_bytes + 256
                + (all_bytes if data_axis else 0),
                "axes": [stage_axis] + ([data_axis] if data_axis else []),
            },
            "forbidden": ["all-to-all", "reduce-scatter"],
            # loss/value_and_grad lowers (no train-step outputs to alias),
            # so no donation floor — but the HBM budget still pins
            "memory": {"max_peak_hbm_bytes": 8 * 1024 * 1024},
        },
    }


def make_grad_accum_step(
    loss_fn: Callable, tx: optax.GradientTransformation, num_microbatches: int,
    donate: bool | None = None,
):
    """Single-device microbatch gradient accumulation: chunk the batch, scan
    per-microbatch grads into a summed carry, one optimizer step — the
    capability of ``s01_b1_microbatches.py``'s grad accumulation (homework
    note on unzeroed ``.grad``, ``homework-1.ipynb`` cell 33) as a scan carry.

    ``loss_fn(params, batch, key) -> scalar``; batch leaves are chunked on
    their leading dim.
    """
    M = num_microbatches
    @partial(jax.jit, donate_argnums=donate_argnums(donate))
    def step(params, opt_state, batch, key):
        chunked = jax.tree.map(
            lambda x: x.reshape((M, x.shape[0] // M) + x.shape[1:]), batch
        )

        def micro(acc, mb):
            mb_batch, k = mb
            loss, grads = jax.value_and_grad(loss_fn)(params, mb_batch, k)
            return jax.tree.map(jnp.add, acc, (grads, loss)), None

        zero = (jax.tree.map(jnp.zeros_like, params), jnp.float32(0.0))
        keys = jax.random.split(key, M)
        (gsum, lsum), _ = lax.scan(micro, zero, (chunked, keys))
        grads = jax.tree.map(lambda g: g / M, gsum)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, lsum / M

    return step
