"""The ``train`` runner: the pipeline trainer's step, as the launcher
``lab/s01_b2_dp_pp.py`` builds it, around the model of the configuration's
family file (``ctx["family"]``: configuration object, seeded weights split
by stage, the loss's check, the counts of FLOPs and bytes; ``families/``).

The launcher takes no width (PERF.md, Open questions), so this calls what
it calls, one level down: the family's staged weights ->
``pipeline.shard_staged_params`` ->
``pipeline.make_pipeline_train_step(cfg, tx, mesh, M, data_axis=...)`` with
``optax.adam(8e-4)``, the launcher's optimizer.

One step = dispatch, then ``block_until_ready`` on its loss.  The window
opens after two warm steps and is closed by the END of the step in flight
when ``--seconds`` have passed, so every step that began in the window
also ends in it and the rate is all tokens over all time.
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmark import traffic

TRACE_STEPS = 4


def placement(config: dict, chips: int) -> tuple[int, int, int]:
    """``(data, stage, n_layers)`` of a training configuration on ``chips``
    chips: the mesh of its ``placement`` entry, and that entry's layers a
    stage (else the configuration's) times the stages."""
    place = config["placement"][str(chips)]
    stages = int(place["stage"])
    per_stage = place.get("layers_per_stage", config["run"]["layers_per_stage"])
    return int(place["data"]), stages, stages * int(per_stage)


def init_state(family, cfg, tx, mesh, stages: int, seed: int):
    """The family's seeded weights, split for the stages, placed as the
    launcher places them, and the optimizer's state beside them.
    ``tx.init`` runs eagerly on purpose: its ``zeros_like`` keeps each
    parameter's placement, where a jitted init (and ``device_put`` inside
    one) left the whole state replicated on every chip, which did not fit
    four chips (PR 25)."""
    from ddl25spring_tpu.parallel.pipeline import shard_staged_params

    staged = shard_staged_params(family.init_staged_params(cfg, seed, stages), mesh)
    return staged, tx.init(staged)


def run(ctx: dict) -> dict:
    import jax.numpy as jnp
    import optax

    from ddl25spring_tpu.parallel.pipeline import make_pipeline_train_step
    from ddl25spring_tpu.utils.mesh import make_mesh

    t_run = time.perf_counter()
    cell, config, tracer = ctx["cell"], ctx["config"], ctx["tracer"]
    family, devices = ctx["family"], ctx["devices"]
    on_tpu = devices[0].platform == "tpu"
    dp, stages, n_layers = placement(config, ctx["chips"])
    use_flash = bool(config["run"].get("use_flash")) and on_tpu
    cfg = family.build(config, n_layers=n_layers, use_flash=use_flash)
    seq_len = family.seq_len(cfg)
    step_spec = cell["traffic"]
    batch, M = int(step_spec["sequences_per_step"]), int(step_spec["microbatches"])
    block = batch // (M * dp)  # sequences of one microbatch on one replica
    if block * M * dp != batch:
        raise ValueError(f"{batch} sequences do not split over {M} x {dp}")

    mesh = make_mesh(devices, data=dp, stage=stages)
    tx = optax.adam(float(config["run"]["learning_rate"]))

    staged, opt_state = init_state(family, cfg, tx, mesh, stages, ctx["seed"])
    step = make_pipeline_train_step(
        cfg, tx, mesh, M, data_axis="data" if dp > 1 else None,
        schedule=step_spec.get("schedule", "gpipe"),
    )
    batches = traffic.train_batches(
        step_spec, family.vocab(cfg), batch, seq_len, ctx["seed"]
    )

    # correctness, outside the window, in the two warm-up steps: before
    # each, the reference's loss on n_check sequences at the parameters as
    # they stand; the step then runs a batch that repeats exactly those
    # sequences in every microbatch of every replica, so that the loss it
    # returns (taken before its update) is their mean.  The first call
    # compiles (or reads the cache); the second takes the first's donated
    # outputs, as every later step will.
    n_check = min(block, 2)
    t_state = time.perf_counter()
    first = next(batches)
    checks, call_s = [], []
    for i in range(2):
        seqs = first[i * n_check:(i + 1) * n_check]
        ref_loss = family.reference_loss(cfg, staged, jnp.asarray(seqs))
        t0 = time.perf_counter()
        staged, opt_state, loss = step(
            staged, opt_state, jnp.asarray(np.tile(seqs, (batch // n_check, 1)))
        )
        system_loss = float(loss)
        call_s.append(time.perf_counter() - t0)
        checks.append(family.check_train_loss(system_loss, ref_loss))
    check = {
        "ok": all(c["ok"] for c in checks),
        "sequences": 2 * n_check, "rtol": checks[0]["rtol"], "steps": checks,
    }

    def one_step(tokens):
        nonlocal staged, opt_state
        with tracer.span("train_step_dispatch"):
            staged, opt_state, loss = step(staged, opt_state, jnp.asarray(tokens))
        with tracer.span("harness_client"):
            nxt = next(batches)
        with tracer.span("train_step_wait"):
            loss.block_until_ready()
        return loss, nxt

    tokens = next(batches)
    losses, ends = [], []
    t_open = time.perf_counter()
    while not ends or ends[-1] - t_open < ctx["seconds"]:
        loss, tokens = one_step(tokens)
        ends.append(time.perf_counter())
        losses.append(loss)
    t_close = ends[-1]

    if ctx["trace"]:
        tracer.start()
        for _ in range(TRACE_STEPS):
            _, tokens = one_step(tokens)
        tracer.stop()

    losses = [float(x) for x in losses]
    finite = [math.isfinite(x) for x in losses]
    tail = losses[-10:] if len(losses) > 10 else losses[-1:]
    rise = sum(tail) / len(tail) - losses[0]
    learned = len(losses) < 2 or rise < 0
    starts = [t_open] + ends[:-1]
    flash = family.flash_calls(cfg, block)
    return {
        "setup_s": t_open - ctx["t_process"],
        "t_open_host": t_open, "t_close_host": t_close,
        "window_s": t_close - t_open,
        "step_s": [e - s for s, e in zip(starts, ends)],
        "tokens_per_step": batch * seq_len,
        "tokens": len(ends) * batch * seq_len,
        "compile_s": call_s[0],
        # the family's counts: what the ALGORITHM needs, for the readers
        "flops_per_token": family.train_flops_per_token(cfg),
        "flash": {"used": use_flash, "calls_per_step": M * dp * flash["calls"],
                  "forward": flash["forward"], "backward": flash["backward"]},
        "trace_steps": TRACE_STEPS,
        "correct": check["ok"] and all(finite) and learned,
        "attempted": len(losses),
        "failed": finite.count(False),
        "compared": {
            **{f"loss_rel_step{i + 1}": {"value": c["rel"], "limit": c["rtol"]}
               for i, c in enumerate(checks)},
            "nonfinite_losses": {"value": finite.count(False), "limit": 0},
            "loss_rise": {"value": rise if len(losses) > 1 else None, "limit": "< 0"},
        },
        "notes": {"check": check, "steps": len(losses),
                  # where set-up went: imports and device init, state and
                  # step builder, the two checked warm-up steps
                  "setup_parts_s": [t_run - ctx["t_process"], t_state - t_run,
                                    t_open - t_state],
                  "first_call_s": call_s[0], "second_call_s": call_s[1],
                  "first_loss": losses[0], "last_loss": losses[-1],
                  "learned": learned, "mesh": {"data": dp, "stage": stages},
                  "n_layers": n_layers},
    }
