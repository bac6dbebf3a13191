#!/usr/bin/env python
"""Homework B2 — DP x PP hybrid, TPU-native.

The reference runs SIX processes — two 3-stage pipelines {0,1,2}/{3,4,5} with
per-stage DP groups {0,3},{1,4},{2,5} built via ``dist.new_group``, microbatch
``isend/irecv`` chains, then barrier + flatten + per-group ``all_reduce(SUM)``
+ unflatten/2 + Adam step (``lab/s01_b2_dp_pp.py``).  Here the whole topology
is ONE jitted program over a 2-D mesh ``(data, stage)``: the per-stage DP
groups ARE the ``data`` axis, the pipelines ARE the ``stage`` axis, and the
flatten/all_reduce dance is the automatic cotangent psum.

Two workloads:

- ``--workload llama``  — the reference's capability: the 288-d LLaMA on
  TinyStories, 2 pipelines x 3 stages (collapses gracefully to the devices
  available);
- ``--workload resnet`` (default) — the BASELINE.json benchmark config:
  ResNet-18/CIFAR-10 DP(+PP) with microbatches, printing samples/sec/chip
  against the >= 5k north star.  With ``--pp`` the heterogeneous 2-stage
  pipeline is used; default is pure DP (the fastest layout when the model
  fits on one chip — pipelining a chip-resident ResNet only adds bubble).
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", choices=("resnet", "llama"), default="resnet")
    ap.add_argument("--iters", type=int, default=0,
                    help="0 = workload default (resnet 30, llama 200)")
    ap.add_argument("--batch", type=int, default=0,
                    help="global batch; 0 = workload default "
                         "(resnet 1024/chip, llama 6)")
    ap.add_argument("--microbatches", type=int, default=0,
                    help="0 = workload default (resnet 2 when --pp, llama 3)")
    ap.add_argument("--pp", action="store_true",
                    help="resnet: use the 2-stage heterogeneous pipeline")
    ap.add_argument("--lr", type=float, default=0.0,
                    help="0 = workload default")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--force-cpu-devices", type=int, default=0, metavar="N")
    ap.add_argument("--ckpt-dir", default="",
                    help="llama workload: checkpoint/resume directory; a "
                         "relaunched run continues from the latest step")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--input",
                    choices=("auto", "hbm-scan", "hbm", "stream", "fixed"),
                    default="auto",
                    help="resnet input pipeline: 'hbm' = whole train split "
                         "resident in device memory with on-device epoch "
                         "shuffle (zero steady-state host->device traffic — "
                         "the TPU-native path for datasets that fit HBM); "
                         "'stream' = native C++ prefetching loader pushing a "
                         "fresh uint8 batch over the host link every step; "
                         "'fixed' = one device-resident batch re-fed (pure "
                         "compute).  'auto' = hbm (CIFAR-10 is 147 MiB)")
    ap.add_argument("--stream", dest="input", action="store_const",
                    const="stream", help="alias for --input stream")
    ap.add_argument("--no-stream", dest="input", action="store_const",
                    const="fixed", help="alias for --input fixed")
    ap.add_argument("--schedule",
                    choices=("gpipe", "1f1b", "1f1b-stash", "interleaved",
                             "interleaved-1f1b"),
                    default="gpipe",
                    help="llama: pipeline schedule (1f1b bounds activation "
                         "memory at O(S) instead of O(M); 1f1b-stash is the "
                         "non-remat variant; interleaved chunks each stage "
                         "into --chunks virtual stages, bubble ~/V; "
                         "interleaved-1f1b composes chunking with the "
                         "bounded 1F1B backward — the Megatron production "
                         "schedule)")
    ap.add_argument("--chunks", type=int, default=2, metavar="V",
                    help="llama interleaved schedule: layer chunks per "
                         "device (needs microbatches %% stages == 0 and "
                         "n_layers %% (stages*V) == 0)")
    ap.add_argument("--no-flash", action="store_true",
                    help="llama: dense attention in place of the Pallas "
                         "flash-attention kernel (flash is the default on "
                         "TPU; a CPU run is dense and says so)")
    ap.add_argument("--trace-dir", default="",
                    help="capture a jax.profiler trace of the timed loop")
    return ap.parse_args(argv)


def run_llama(args, jax, jnp):
    import optax

    from ddl25spring_tpu.data.tinystories import TinyStories
    from ddl25spring_tpu.data.tokenizer import get_tokenizer
    from ddl25spring_tpu.models import llama
    from ddl25spring_tpu.parallel.pipeline import (
        make_pipeline_train_step,
        shard_staged_params,
    )
    from ddl25spring_tpu.utils.config import LlamaConfig, replace
    from ddl25spring_tpu.utils.mesh import make_mesh

    devices = jax.devices()
    n = len(devices)
    # reference topology 2x3 when possible, else collapse (SURVEY §3.1)
    if n >= 6:
        dp, S = 2, 3
    elif n >= 4:
        dp, S = 2, 2
    elif n >= 2:
        dp, S = 1, 2
    else:
        dp, S = 1, 1
    mesh = make_mesh(devices[: dp * S], data=dp, stage=S)

    on_tpu = devices[0].platform == "tpu"
    tokenizer = get_tokenizer()
    # the reference constants (LlamaConfig(): 288-d, 6 heads, 6 layers,
    # ctx 256, vocab 4096 — wider only if the tokenizer's is).  The
    # platform picks dtype and attention, and the line below SAYS which:
    # Pallas flash + bf16 on TPU; dense fp32 on CPU, where Pallas would
    # run interpreted
    ref = LlamaConfig()
    cfg = replace(
        ref, vocab_size=max(ref.vocab_size, tokenizer.vocab_size),
        dtype="bfloat16" if on_tpu else "float32",
        use_flash=on_tpu and not args.no_flash,
    )
    M = args.microbatches or 3
    batch = args.batch or 3 * dp  # reference: batch 3 per pipeline
    iters = args.iters or 200
    print(f"llama DPxPP on {devices[0].platform}: mesh(data={dp}, "
          f"stage={S}), batch={batch}, microbatches={M}, "
          f"schedule={args.schedule}, dtype={cfg.dtype}, "
          f"attention={'flash' if cfg.use_flash else 'dense'}")

    params = llama.init_llama_params(jax.random.PRNGKey(0), cfg)
    chunked = args.schedule.startswith("interleaved")
    if chunked:
        split = lambda p: llama.split_blocks_interleaved(p, S, args.chunks)
    else:
        split = lambda p: llama.split_blocks_for_stages(p, S)
    staged = shard_staged_params(split(params), mesh)
    tx = optax.adam(args.lr or 8e-4)
    opt_state = tx.init(staged)

    step = make_pipeline_train_step(
        cfg, tx, mesh, M, data_axis="data" if dp > 1 else None,
        schedule=args.schedule,
        num_chunks=args.chunks if chunked else 1,
    )

    start_it = 0
    ckpt = None
    if args.ckpt_dir:
        from ddl25spring_tpu.utils.checkpoint import (
            Checkpointer, with_mesh_placement,
        )

        ckpt = Checkpointer(args.ckpt_dir)
        state, start_it = ckpt.restore_or_init(
            with_mesh_placement({"params": staged, "opt_state": opt_state}, mesh)
        )
        staged, opt_state = state["params"], state["opt_state"]
        if start_it:
            print(f"resumed from step {start_it - 1} in {args.ckpt_dir}")

    # disjoint per-replica data like the reference's skip=rank*N: one global
    # stream here, sharded over the data axis by the step's in_spec
    ds = iter(TinyStories(
        tokenizer, batch_size=batch, seq_l=cfg.ctx_size,
        skip=start_it * batch,
    ))
    # warmup outside the timer: jit compile dominates the first step.  The
    # outputs are DISCARDED — a warmup that stepped the optimizer would give
    # every resumed run one extra update and break kill-and-resume
    # equivalence with an uninterrupted run.  A kernel that does not
    # lower fails here, loudly: there is no quiet retry with dense.
    # The step donates its params/opt-state, so it warms up on COPIES
    tokens_w = jnp.asarray(next(ds))
    t_c = time.perf_counter()
    float(step(*jax.tree.map(jnp.copy, (staged, opt_state)), tokens_w)[2])
    compile_s = time.perf_counter() - t_c

    import contextlib

    from ddl25spring_tpu.utils.tracing import trace

    ctx = trace(args.trace_dir) if args.trace_dir else contextlib.nullcontext()
    t0 = time.perf_counter()
    last_it = start_it - 1
    logged: list[tuple[int, float]] = []
    with ctx:
        for it in range(start_it, start_it + iters):
            staged, opt_state, loss = step(
                staged, opt_state, jnp.asarray(next(ds))
            )
            if (args.log_every and it % args.log_every == 0) \
                    or it == start_it + iters - 1:
                logged.append((it, float(loss)))
                print(f"iter {it:5d}  loss {logged[-1][1]:.4f}", flush=True)
            if ckpt is not None and args.ckpt_every > 0 \
                    and (it + 1) % args.ckpt_every == 0:
                ckpt.save(it, {"params": staged, "opt_state": opt_state})
            last_it = it
    dt = time.perf_counter() - t0
    if ckpt is not None and last_it >= start_it:
        # persist the tail: without this, up to ckpt_every-1 trailing steps
        # would be redone on relaunch.  Skip if the loop's periodic save
        # already covered last_it (orbax refuses duplicate steps).
        if args.ckpt_every <= 0 or (last_it + 1) % args.ckpt_every != 0:
            ckpt.save(last_it, {"params": staged, "opt_state": opt_state},
                      force=True)
        ckpt.close()
    tok_s = iters * batch * cfg.ctx_size / dt
    print(f"done: {iters} iters in {dt:.1f}s ({tok_s:,.0f} tok/s, "
          f"{tok_s / (dp * S):,.0f} tok/s/chip)")

    from ddl25spring_tpu.utils.flops import compiled_flops, mfu

    fl = compiled_flops(step, staged, opt_state, tokens_w)
    tf, frac = mfu(fl, dt / iters, dp * S, devices[0])
    if tf is not None:
        print(f"achieved {tf:.2f} TFLOP/s/chip"
              + (f" (MFU {frac:.2%})" if frac is not None else ""))
    if args.trace_dir:
        print(f"profiler trace written to {args.trace_dir}")
    # what a caller (chip_smoke.py) checks: the logged losses, the times,
    # and the jitted step with arguments it can be lowered against
    return {
        "cfg": cfg, "mesh": mesh, "losses": logged, "compile_s": compile_s,
        "run_s": dt, "tokens_per_s": tok_s, "step": step,
        "step_args": (staged, opt_state, tokens_w),
    }


def run_resnet(args, jax, jnp):
    from ddl25spring_tpu.benchmarks import (
        DeviceDataset, InputFeed, build_resnet_scan_step, build_resnet_step,
        report_line,
    )

    devices = jax.devices()
    n = len(devices)
    on_tpu = devices[0].platform == "tpu"
    iters = args.iters or 30

    if args.pp and n >= 2:
        dp, S = n // 2, 2
    else:
        dp, S = n, 1
    n_used = dp * S  # odd counts strand a device in the --pp layout
    M = (args.microbatches or 2) if S == 2 else 1
    # CPU simulation can't sustain the TPU-sized default batch: a --pp tick
    # slower than XLA's ~40s collective-rendezvous deadline aborts the
    # process, and full-width conv ticks on fake CPU devices hit that at
    # microbatches of ~16; default to microbatches of ~4
    batch = args.batch or (1024 if on_tpu else 4) * n_used
    batch = batch // (dp * M) * (dp * M)

    if args.input == "auto":
        # hbm needs batch <= dataset size (50k CIFAR rows); on a slice big
        # enough to exceed that, auto degrades to the streaming loader.
        # The scan-fused hbm mode is the bench primary (amortized dispatch)
        # but TPU-only: lax.scan over a conv body is ~55x slower on the
        # XLA CPU backend (see build_resnet_scan_step)
        if batch > 50_000:
            mode = "stream"
        else:
            mode = "hbm-scan" if on_tpu else "hbm"
    else:
        mode = args.input

    # the SAME builders + input pipelines bench.py uses (benchmarks.py):
    # raw uint8 batches in, normalization fused into the jitted step
    if mode == "hbm-scan":
        feed = DeviceDataset(batch)
        K = max(k for k in range(1, 17) if feed.batches_per_epoch % k == 0)
        multi, step, params, opt_state, meta = build_resnet_scan_step(
            devices, dp, S, M, batch, K, feed.n, lr=args.lr or 0.1
        )
    else:
        K = 1
        step, params, opt_state, meta = build_resnet_step(
            devices, dp, S, M, batch, lr=args.lr or 0.1
        )
        feed = (
            DeviceDataset(batch) if mode == "hbm"
            else InputFeed(batch, stream=(mode == "stream"))
        )

    input_mode = (
        f"{feed.input_mode}-scan{K}" if mode == "hbm-scan" else feed.input_mode
    )
    print(f"resnet18/cifar10: {meta['topology']}, global batch={batch}, "
          f"{n_used}/{n} device(s) in mesh, input={input_mode}")

    import contextlib

    from ddl25spring_tpu.utils.tracing import trace

    def one_iter(params, opt_state):
        if mode == "hbm-scan":
            return multi(params, opt_state, feed.x, feed.y,
                         *feed.scan_window(K))
        return step(params, opt_state, feed.feed())

    n_disp = max(2, iters // K)
    # warmup (compile) happens before the timer; wrap the timed loop only
    ctx = trace(args.trace_dir) if args.trace_dir else contextlib.nullcontext()
    with ctx:
        for _ in range(3):  # warmup / compile
            params, opt_state, loss = one_iter(params, opt_state)
        float(loss)
        t0 = time.perf_counter()
        for it in range(n_disp):
            params, opt_state, loss = one_iter(params, opt_state)
            if args.log_every and (it % args.log_every == 0):
                # the dispatch returns the loss of its LAST fused step
                print(f"iter {(it + 1) * K - 1:4d}  loss {float(loss):.4f}",
                      flush=True)
        float(loss)
        dt = time.perf_counter() - t0
    sps_chip = n_disp * K * batch / dt / n_used

    from ddl25spring_tpu.utils.flops import compiled_flops, mfu

    fixed = getattr(feed, "fixed", None)
    fl = compiled_flops(step, params, opt_state, fixed)
    tf, frac = mfu(fl, dt / (n_disp * K), n_used, devices[0])
    if tf is not None:
        print(f"achieved {tf:.2f} TFLOP/s/chip"
              + (f" (MFU {frac:.2%})" if frac is not None else ""))
    if args.trace_dir:
        print(f"profiler trace written to {args.trace_dir}")
    print(report_line(meta["layout"], sps_chip, input_mode, frac, tf))
    feed.close()


def main(argv=None) -> None:
    args = parse_args(argv)
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

    from ddl25spring_tpu.utils.platform import force_cpu_devices

    force_cpu_devices(args.force_cpu_devices)
    if not args.force_cpu_devices:
        from ddl25spring_tpu.utils.platform import enable_compilation_cache

        enable_compilation_cache()

    import jax
    import jax.numpy as jnp
    if args.workload == "llama":
        run_llama(args, jax, jnp)
    else:
        run_resnet(args, jax, jnp)


if __name__ == "__main__":
    main()
