#!/usr/bin/env python
"""Homework B1 — GPipe microbatch pipeline, TPU-native.

The reference runs this as THREE OS processes (``python s01_b1_microbatches.py
<rank>``, ``lab/run-b1.sh:8-15``), each holding one LLaMA stage and chaining
``isend/irecv`` with per-microbatch tags (``lab/s01_b1_microbatches.py:66-178``).
Here the same workload — the reference constants dmodel=288, 6 heads, 6 layers,
ctx 256, batch 3 split into 3 microbatches, Adam — is ONE jitted SPMD program:
stages live on a mesh ``stage`` axis, the microbatch schedule is a ``lax.scan``
of ``ppermute`` hops, and backward/grad-accumulation fall out of ``jax.grad``.

Single-controller launch: no rank argv, no MASTER_ADDR/PORT rendezvous.  On a
host without 3 accelerator devices, ``--force-cpu-devices N`` simulates the
mesh on CPU (the TPU-world analogue of the reference's gloo-on-localhost runs).
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--iters", type=int, default=200,
                    help="outer iterations (reference: 5000)")
    ap.add_argument("--batch", type=int, default=3,
                    help="global batch size (reference: 3)")
    ap.add_argument("--microbatches", type=int, default=3,
                    help="microbatches per batch (reference: 3)")
    ap.add_argument("--stages", type=int, default=0,
                    help="pipeline stages; 0 = largest divisor of n_layers "
                         "that fits the device count (reference: 3)")
    ap.add_argument("--lr", type=float, default=8e-4)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--force-cpu-devices", type=int, default=0, metavar="N",
                    help="simulate an N-device mesh on CPU")
    ap.add_argument("--schedule",
                    choices=("gpipe", "1f1b", "1f1b-stash", "interleaved",
                             "interleaved-1f1b"),
                    default="gpipe",
                    help="pipeline schedule: gpipe (homework B1 parity), "
                         "1f1b (memory-bounded, remat backward; activation "
                         "stash O(S) not O(M)), 1f1b-stash (non-remat "
                         "1F1B: pullback residuals stashed, no forward "
                         "recompute), interleaved (virtual-stage "
                         "chunking, --chunks per device; bubble ~/V), or "
                         "interleaved-1f1b (Megatron production schedule: "
                         "chunked AND memory-bounded)")
    ap.add_argument("--chunks", type=int, default=2, metavar="V",
                    help="interleaved schedule: layer chunks per device "
                         "(needs microbatches %% stages == 0 and "
                         "n_layers %% (stages*V) == 0)")
    ap.add_argument("--scan-steps", type=int, default=0,
                    help="fuse K train steps per dispatched program "
                         "(lax.scan over K stacked batches); 0 = auto "
                         "(16 on TPU, 1 on CPU).  Amortizes the "
                         "per-dispatch host cost that dominates at the "
                         "reference-parity batch size")
    ap.add_argument("--no-flash", action="store_true",
                    help="dense attention in place of the Pallas flash "
                         "kernel (flash is the default on TPU; a CPU run "
                         "is dense and says so)")
    ap.add_argument("--trace-dir", default="",
                    help="capture a jax.profiler trace of the timed loop "
                         "(Perfetto/TensorBoard-loadable)")
    return ap.parse_args(argv)


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
    import optax
    from ddl25spring_tpu.data.tinystories import TinyStories
    from ddl25spring_tpu.data.tokenizer import get_tokenizer
    from ddl25spring_tpu.models import llama
    from ddl25spring_tpu.parallel.pipeline import (
        make_pipeline_train_step,
        shard_staged_params,
    )
    from ddl25spring_tpu.utils.config import LlamaConfig
    from ddl25spring_tpu.utils.mesh import make_mesh

    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    tokenizer = get_tokenizer()
    # fastest correct path by default: the Pallas flash kernel on TPU
    # (measured 1.8x at ctx 4096), dense attention on CPU where Pallas
    # would run interpreted
    cfg = LlamaConfig(
        vocab_size=tokenizer.vocab_size, dmodel=288, num_heads=6,
        n_layers=6, ctx_size=args.seq_len,
        dtype="bfloat16" if on_tpu else "float32",
        use_flash=on_tpu and not args.no_flash,
    )
    S = args.stages or max(
        s for s in (6, 3, 2, 1) if s <= len(devices) and cfg.n_layers % s == 0
    )
    mesh = make_mesh(devices[:S], stage=S)
    print(f"devices={len(devices)} ({devices[0].platform}) -> "
          f"pipeline stages={S}, microbatches={args.microbatches}, "
          f"batch={args.batch}, schedule={args.schedule}, "
          f"attention={'flash' if cfg.use_flash else 'dense'}")

    params = llama.init_llama_params(jax.random.PRNGKey(0), cfg)
    chunked = args.schedule.startswith("interleaved")
    if chunked:
        split = lambda p: llama.split_blocks_interleaved(p, S, args.chunks)
    else:
        split = lambda p: llama.split_blocks_for_stages(p, S)
    staged = shard_staged_params(split(params), mesh)
    tx = optax.adam(args.lr)
    opt_state = tx.init(staged)

    step = make_pipeline_train_step(
        cfg, tx, mesh, args.microbatches, schedule=args.schedule,
        num_chunks=args.chunks if chunked else 1,
    )

    ds = iter(TinyStories(tokenizer, batch_size=args.batch, seq_l=args.seq_len))
    # warmup outside the timer: jit compile dominates the first step (a
    # kernel that does not lower fails here: no quiet retry with dense)
    tokens = jnp.asarray(next(ds))
    staged, opt_state, loss = step(staged, opt_state, tokens)
    float(loss)

    import contextlib

    from ddl25spring_tpu.utils.flops import compiled_flops, mfu
    from ddl25spring_tpu.utils.tracing import trace

    K = args.scan_steps or (16 if on_tpu else 1)
    if K > 1:
        from ddl25spring_tpu.parallel.pipeline import fuse_train_steps

        import numpy as np

        multi = fuse_train_steps(step, K)
        iters = max(1, args.iters // K)
        if iters * K != args.iters:
            print(f"note: --iters {args.iters} adjusted to {iters * K} "
                  f"(a dispatch runs {K} fused steps; use --scan-steps to "
                  "change the granularity)")
        print(f"fusing {K} steps per dispatch ({iters} dispatches)")
        # warmup compile of the fused program outside the timer
        window = jnp.asarray(np.stack([next(ds) for _ in range(K)]))
        staged, opt_state, losses = multi(staged, opt_state, window)
        float(losses[-1])
    else:
        multi, iters = None, args.iters

    ctx = trace(args.trace_dir) if args.trace_dir else contextlib.nullcontext()
    t0 = time.perf_counter()
    with ctx:
        for it in range(iters):
            if multi is None:
                tokens = jnp.asarray(next(ds))
                staged, opt_state, loss = step(staged, opt_state, tokens)
            else:
                window = jnp.asarray(np.stack([next(ds) for _ in range(K)]))
                staged, opt_state, losses = multi(staged, opt_state, window)
                loss = losses[-1]
            if it % args.log_every == 0 or it == iters - 1:
                # host transfer forces completion of the async dispatch
                # chain; fused windows label the loss with the step it
                # belongs to (the window's LAST step)
                step_no = it if multi is None else it * K + K - 1
                print(f"iter {step_no:5d}  loss {float(loss):.4f}",
                      flush=True)
    dt = time.perf_counter() - t0
    n_chips = len(mesh.devices.flat)
    n_steps = iters * K if multi is not None else args.iters
    tok_s = n_steps * args.batch * args.seq_len / dt
    print(f"done: {n_steps} steps in {dt:.1f}s "
          f"({tok_s:,.0f} tok/s, {tok_s / n_chips:,.0f} tok/s/chip)")
    fl = compiled_flops(step, staged, opt_state, tokens)
    tf, frac = mfu(fl, dt / n_steps, n_chips, devices[0])
    if tf is not None:
        print(f"achieved {tf:.2f} TFLOP/s/chip"
              + (f" (MFU {frac:.2%})" if frac is not None else ""))
    if args.trace_dir:
        print(f"profiler trace written to {args.trace_dir}")


if __name__ == "__main__":
    main()
