"""The ``train`` runner: the pipeline trainer's step, as the launcher
``lab/s01_b2_dp_pp.py`` builds it, at the widths of a configuration file.

The launcher takes no width (PERF.md, Open questions), so this calls what
it calls, one level down: ``llama.init_llama_params`` ->
``llama.split_blocks_for_stages`` -> ``pipeline.shard_staged_params`` ->
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

from benchmark import model, reference, traffic

TRACE_STEPS = 4


def init_state(cfg, tx, mesh, stages: int, seed: int):
    """Seeded weights, made on the device in one jitted call and split for
    the stages, then placed as the launcher places them, and the
    optimizer's state beside them.  ``tx.init`` runs eagerly on purpose:
    its ``zeros_like`` keeps each parameter's placement, where a jitted
    init (and ``device_put`` inside one) left the whole state replicated on
    every chip, which did not fit four chips (PR 25)."""
    import jax

    from ddl25spring_tpu.models import llama
    from ddl25spring_tpu.parallel.pipeline import shard_staged_params

    params = jax.jit(lambda key: llama.split_blocks_for_stages(
        llama.init_llama_params(key, cfg), stages
    ))(jax.random.PRNGKey(seed))
    staged = shard_staged_params(params, mesh)
    return staged, tx.init(staged)


def run(ctx: dict) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from ddl25spring_tpu.parallel.pipeline import make_pipeline_train_step
    from ddl25spring_tpu.utils.mesh import make_mesh

    cell, config, tracer = ctx["cell"], ctx["config"], ctx["tracer"]
    devices = ctx["devices"]
    on_tpu = devices[0].platform == "tpu"
    dp, stages, n_layers = model.train_placement(config, ctx["chips"])
    cfg = model.llama_config(
        config, n_layers=n_layers,
        use_flash=bool(config["run"].get("use_flash")) and on_tpu,
    )
    step_spec = cell["traffic"]
    batch, M = int(step_spec["sequences_per_step"]), int(step_spec["microbatches"])
    block = batch // (M * dp)  # sequences of one microbatch on one replica
    if block * M * dp != batch:
        raise ValueError(f"{batch} sequences do not split over {M} x {dp}")

    mesh = make_mesh(devices, data=dp, stage=stages)
    tx = optax.adam(float(config["run"]["learning_rate"]))

    staged, opt_state = init_state(cfg, tx, mesh, stages, ctx["seed"])
    step = make_pipeline_train_step(
        cfg, tx, mesh, M, data_axis="data" if dp > 1 else None,
        schedule=step_spec.get("schedule", "gpipe"),
    )
    batches = traffic.train_batches(
        step_spec, cfg.vocab_size, batch, cfg.ctx_size, ctx["seed"]
    )

    # correctness, outside the window, in the two warm-up steps: before
    # each, the reference's loss on n_check sequences at the parameters as
    # they stand; the step then runs a batch that repeats exactly those
    # sequences in every microbatch of every replica, so that the loss it
    # returns (taken before its update) is their mean.  The first call
    # compiles (or reads the cache); the second takes the first's donated
    # outputs, as every later step will.
    n_check = min(block, 2)
    first = next(batches)
    checks, call_s = [], []
    for i in range(2):
        seqs = first[i * n_check:(i + 1) * n_check]
        ref_loss = float(reference.loss(
            reference.flat_blocks(staged), jnp.asarray(seqs), num_heads=cfg.num_heads
        ))
        t0 = time.perf_counter()
        staged, opt_state, loss = step(
            staged, opt_state, jnp.asarray(np.tile(seqs, (batch // n_check, 1)))
        )
        system_loss = float(loss)
        call_s.append(time.perf_counter() - t0)
        checks.append({
            "system_loss": system_loss, "reference_loss": ref_loss,
            "rel": abs(system_loss - ref_loss) / abs(ref_loss),
        })
    check = {
        "ok": all(c["rel"] <= reference.TRAIN_LOSS_RTOL for c in checks),
        "sequences": 2 * n_check, "rtol": reference.TRAIN_LOSS_RTOL,
        "steps": checks,
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
    learned = len(losses) < 2 or sum(tail) / len(tail) < losses[0]
    starts = [t_open] + ends[:-1]
    return {
        "setup_s": t_open - ctx["t_process"],
        "t_open_host": t_open, "t_close_host": t_close,
        "window_s": t_close - t_open,
        "step_s": [e - s for s, e in zip(starts, ends)],
        "tokens_per_step": batch * cfg.ctx_size,
        "tokens": len(ends) * batch * cfg.ctx_size,
        "compile_s": call_s[0],
        "model": {"dmodel": cfg.dmodel, "ffn_dim": cfg.ffn_dim,
                  "n_layers": cfg.n_layers, "vocab": cfg.vocab_size,
                  "ctx": cfg.ctx_size, "heads": cfg.num_heads,
                  "head_dim": cfg.head_dim},
        "flash": {"batch_per_call": block, "calls_per_step": M * n_layers * dp,
                  "used": bool(cfg.use_flash)},
        "trace_steps": TRACE_STEPS,
        "correct": check["ok"] and all(finite) and learned,
        "attempted": len(losses),
        "failed": finite.count(False),
        "notes": {"check": check, "steps": len(losses),
                  "first_call_s": call_s[0], "second_call_s": call_s[1],
                  "first_loss": losses[0], "last_loss": losses[-1],
                  "learned": learned, "mesh": {"data": dp, "stage": stages},
                  "n_layers": n_layers},
    }
