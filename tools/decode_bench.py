#!/usr/bin/env python
"""KV-cache generation throughput on the chip.

The reference has no generation path at all (its LLaMA only trains —
``lab/s01_b1_microbatches.py``); this framework adds autoregressive
KV-cache decoding (``models/decode.py``), and this tool measures it: the
full jitted prefill+decode program at the reference workload constants
(dmodel 288, 6 heads, 6 layers), greedy decoding, across batch sizes.

Run: ``python tools/decode_bench.py [--ctx 256] [--new 224]``
"""

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--prompt", type=int, default=32)
    ap.add_argument("--new", type=int, default=224)
    ap.add_argument("--batches", type=int, nargs="+", default=[1, 8, 64])
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--tp", type=int, default=0, metavar="T",
                    help="TP-sharded decode over a (model=T) mesh "
                         "(head-sharded KV cache, vocab-sharded "
                         "embed/unembed; needs T devices — use "
                         "--force-cpu-devices via --cpu + "
                         "XLA_FLAGS for local smoke)")
    args = ap.parse_args(argv)

    import os

    if args.tp and args.cpu:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={args.tp}"
            ).strip()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp

    from ddl25spring_tpu.models import llama
    from ddl25spring_tpu.models.decode import generate, make_tp_generate
    from ddl25spring_tpu.utils.config import LlamaConfig

    on_tpu = jax.devices()[0].platform == "tpu"
    cfg = LlamaConfig(
        vocab_size=4096, dmodel=288, num_heads=6, n_layers=6,
        ctx_size=args.prompt + args.new,
        dtype="bfloat16" if on_tpu else "float32",
    )
    params = llama.init_llama_params(jax.random.PRNGKey(0), cfg)
    print(f"device={jax.devices()[0].device_kind}  dtype={cfg.dtype}  "
          f"dmodel={cfg.dmodel} "
          f"L{cfg.n_layers}  prompt={args.prompt}  new={args.new}"
          + (f"  tp={args.tp}" if args.tp else ""))

    if args.tp:
        from ddl25spring_tpu.parallel.tp import shard_tp_params
        from ddl25spring_tpu.utils.mesh import make_mesh

        mesh = make_mesh(jax.devices()[: args.tp], model=args.tp)
        params = shard_tp_params(params, mesh)
        tp_gen = make_tp_generate(cfg, mesh, args.new)
        key0 = jax.random.PRNGKey(0)
        gen = lambda p, prompt: tp_gen(p, prompt, key0)
    else:
        gen = jax.jit(
            lambda p, prompt: generate(p, prompt, cfg, args.new),
        )
    for B in args.batches:
        prompt = jax.random.randint(
            jax.random.PRNGKey(1), (B, args.prompt), 0, cfg.vocab_size
        )
        toks = gen(params, prompt)  # compile
        jax.block_until_ready(toks)
        best = float("inf")
        for _ in range(args.reps):
            t0 = time.perf_counter()
            toks = gen(params, prompt)
            # the clock stops on a scalar fetch, which waits for the result
            _ = int(toks[0, -1])
            best = min(best, time.perf_counter() - t0)
        total = B * args.new
        print(f"B={B:>3}: {total / best:,.0f} tok/s "
              f"({best * 1e3 / args.new:.2f} ms/token-step at batch {B})")


if __name__ == "__main__":
    main()
