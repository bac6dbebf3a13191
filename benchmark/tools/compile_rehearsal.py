#!/usr/bin/env python3
"""Compile a cell's programs at their real size for a DESCRIBED v5e, here
on the CPU, and print what the chip's compiler says they need.

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_rehearsal.py <cell>

Nothing runs and no number printed here is a measurement: this only finds,
at no chip time, what the compiler refuses and whether a step fits a
chip's 16 GB (``on-chip-measurement`` guide, section 2, third rehearsal).
It counts one program at a time, not what else the process keeps on the
device.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding  # noqa: E402

from benchmark.run import BENCH_DIR, load_cell, load_family, load_module  # noqa: E402

GB = 1e9


def report(name: str, compiled) -> None:
    """The compiler's own bill.  Whether the parts add up to a chip is the
    compiler's call, not this sum's: ``compile()`` raises RESOURCE_EXHAUSTED,
    with the largest allocations, for a program that does not fit."""
    m = compiled.memory_analysis()
    print(f"{name} fits: arguments {m.argument_size_in_bytes / GB:.2f} GB "
          f"(of them donated {m.alias_size_in_bytes / GB:.2f} GB), "
          f"outputs {m.output_size_in_bytes / GB:.2f} GB, "
          f"temporaries {m.temp_size_in_bytes / GB:.2f} GB", flush=True)


def with_sharding(tree, shardings):
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, shardings,
    )


def train(cell, config, family, topo) -> None:
    import optax

    from ddl25spring_tpu.parallel.pipeline import (
        make_pipeline_train_step, staged_param_specs,
    )

    chips = int(cell["chips"])
    runner = load_module(BENCH_DIR, "runners", "train")
    dp, stages, n_layers = runner.placement(config, chips)
    cfg = family.build(config, n_layers=n_layers, use_flash=True)
    mesh = Mesh(np.array(topo.devices[:chips]).reshape(dp, stages), ("data", "stage"))
    tx = optax.adam(config["run"]["learning_rate"])
    staged = jax.eval_shape(lambda: family.init_staged_params(cfg, 0, stages))
    specs = staged_param_specs("stage", None, None, False, n_experts=0)
    shard = {
        k: (jax.tree.map(lambda _: NamedSharding(mesh, specs["blocks"]), staged["blocks"])
            if k == "blocks" else NamedSharding(mesh, specs[k]))
        for k in staged
    }
    staged = with_sharding(staged, shard)
    opt_state = jax.eval_shape(tx.init, staged)
    rep = NamedSharding(mesh, P())

    # Adam's moments have their parameters' shapes and follow their
    # placement; the step count is replicated
    by_shape = {
        leaf.shape: s
        for leaf, s in zip(jax.tree.leaves(staged), jax.tree.leaves(shard))
    }
    opt_state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=by_shape.get(x.shape, rep)
        ),
        opt_state,
    )
    t = cell["traffic"]
    tokens = jax.ShapeDtypeStruct(
        (t["sequences_per_step"], family.seq_len(cfg)), jnp.int32, sharding=rep
    )
    step = make_pipeline_train_step(
        cfg, tx, mesh, t["microbatches"], data_axis="data" if dp > 1 else None,
        schedule=t.get("schedule", "gpipe"),
    )
    lowered = step.lower(staged, opt_state, tokens)
    text = lowered.as_text()
    print("flash kernel in the lowered step:", "tpu_custom_call" in text)
    compiled = lowered.compile()
    report(f"{cell['name']} train step", compiled)
    hlo = compiled.as_text()
    for op in ("all-reduce", "collective-permute", "all-gather", "reduce-scatter"):
        print(f"  {op}: {hlo.count(' ' + op + '(') + hlo.count(' ' + op + '-start(')}")


def serve(cell, config, family, topo) -> None:
    from ddl25spring_tpu.serve import driver, kv_pages
    from ddl25spring_tpu.serve.engine import (
        make_decode_tick, make_prefill, prefill_widths,
    )

    one = SingleDeviceSharding(topo.devices[0])
    cfg = family.build(config, use_flash=False)
    k = {**driver.engine_knobs(), **cell["engine"]}

    def abstract(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree
        )

    params = abstract(jax.eval_shape(lambda: family.init_params(cfg, 0)))
    pool = abstract(jax.eval_shape(lambda: kv_pages.init_page_pool(
        cfg, n_pages=k["n_pages"], page_len=k["page_len"],
        max_slots=k["max_slots"], pages_per_seq=k["pages_per_seq"],
    )))
    key = abstract(jax.eval_shape(lambda: jax.random.PRNGKey(0)))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    tick = jax.jit(make_decode_tick(cfg, temperature=0.0, sentinel=False),
                   donate_argnums=(1,))
    report(f"{cell['name']} decode tick",
           tick.lower(params, pool, i32(k["max_slots"]), key).compile())
    B, Lp = k["prefill_batch"], k["max_prompt_len"]
    prefill = jax.jit(
        make_prefill(cfg, max_prompt_len=Lp, temperature=0.0, sentinel=False),
        donate_argnums=(1,),
    )
    for W in prefill_widths(Lp):  # one program a width, as the engine warms them
        report(f"{cell['name']} prefill at width {W}",
               prefill.lower(params, pool, i32(B, W), i32(B), i32(B), i32(B), key).compile())


def main() -> int:
    cell, config = load_cell(BENCH_DIR, sys.argv[1])
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    # the program asks jax.default_backend() whether to use the kernel; a
    # described chip is not attached, so say "tpu" for the lowering only
    jax.default_backend = lambda: "tpu"
    {"train": train, "serve": serve}[cell["runner"]](
        cell, config, load_family(BENCH_DIR, config), topo
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
