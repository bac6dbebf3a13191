#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

``python chip_smoke.py`` drives the main path once on ONE TPU chip, in this
one process, through the entry points a user would call, at the full width
of the models the repo has:

- **resnet** — the ``python bench.py`` / ``lab/s01_b2_dp_pp.py --workload
  resnet`` trainer: ResNet-18 on CIFAR-10 shapes, ``DeviceDataset`` +
  ``benchmarks.build_resnet_scan_step``, per-chip batch 1024;
- **llama** — the ``lab/s01_b2_dp_pp.py --workload llama`` trainer at the
  reference constants (``LlamaConfig()``: 288-d, 6 heads, 6 layers, ctx
  256, vocab 4096, bf16), Pallas flash attention ON, against the dense
  step on the same batch;
- **serve** — ``bench.py --serve --serve-model ref`` as
  ``serve.driver.run_serve_bench`` runs it on the wall clock, then the
  same engine's greedy tokens against ``models/decode.generate``.

Before them it answers two questions the code's comments used to guess at
(does ``block_until_ready`` block; does a ``jax.profiler`` trace complete
with a device plane).  Every line of stdout is one JSON object; the LAST is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``
and is printed only after every phase passed on ``platform == "tpu"``.
Any failure: traceback on stderr, non-zero exit, no ``ok`` line.

``--chips 4`` runs ONLY the paths that exist across chips, each with the
one-device result it is compared with: the LLaMA DP x PP train step on
``mesh(data=2, stage=2)`` and TP-sharded serving on ``mesh(model=2)`` (6
heads: 2 divides them, 4 does not).  Its last line reports ``"count": 4``.

``--rehearse`` is the CPU rehearsal (tiny sizes, dense attention,
``JAX_PLATFORMS=cpu``): it walks the same control flow and exits non-zero
without an ``ok: true`` line — nothing a CPU prints is a chip result.

The compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else at
``<checkout>/.jax_cache`` (``utils/platform.enable_compilation_cache``).
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import sys
import threading
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out")


def emit(**record) -> None:
    print(json.dumps(record), flush=True)


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def load_launcher():
    """``lab/s01_b2_dp_pp.py`` as a module (``lab/`` is a directory of
    scripts, not a package)."""
    spec = importlib.util.spec_from_file_location(
        "s01_b2_dp_pp", os.path.join(ROOT, "lab", "s01_b2_dp_pp.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------------ probes


def probe_runtime(jax, jnp, rehearse: bool) -> dict:
    """Two facts about this runtime, measured: whether
    ``block_until_ready`` waits for the device, and whether a
    ``jax.profiler`` trace completes and holds a device plane."""
    import numpy as np

    n, reps = (512, 4) if rehearse else (8192, 48)

    @jax.jit
    def chain(a):
        x = jax.lax.fori_loop(0, reps, lambda _, x: (x @ a) * 0.01, a)
        return x, x[0, 0].astype(jnp.float32)

    a = jnp.full((n, n), 0.01, jnp.bfloat16)
    jax.block_until_ready(chain(a))  # compile
    t0 = time.perf_counter()
    y, y00 = chain(a)
    dispatch_s = time.perf_counter() - t0
    y.block_until_ready()
    block_s = time.perf_counter() - t0
    float(np.asarray(y00))
    fetch_s = time.perf_counter() - t0 - block_s
    flops = 2.0 * n**3 * reps
    out = {
        "matmul_n": n, "matmul_reps": reps,
        "dispatch_returned_after_s": round(dispatch_s, 6),
        "block_until_ready_returned_after_s": round(block_s, 6),
        "fetch_after_block_s": round(fetch_s, 6),
        "tflops_if_block_waited": round(flops / block_s / 1e12, 2),
        # it blocks if the wait, not the later fetch, paid for the work
        "block_until_ready_blocks": bool(
            block_s > 4 * dispatch_s and fetch_s < 0.5 * block_s
        ),
    }

    trace_dir = os.path.join(OUT_DIR, "smoke_trace")
    done: dict = {}

    def _trace():
        try:
            with jax.profiler.trace(trace_dir):
                for _ in range(3):
                    jax.block_until_ready(chain(a))
            done["ok"] = True
        except Exception as e:  # noqa: BLE001 — the answer is the finding
            done["error"] = f"{type(e).__name__}: {e}"

    t = threading.Thread(target=_trace, daemon=True, name="profiler-probe")
    t0 = time.perf_counter()
    t.start()
    t.join(180.0)
    out["profiler_trace_s"] = round(time.perf_counter() - t0, 3)
    out["profiler_completes"] = bool(done.get("ok"))
    if "error" in done:
        out["profiler_error"] = done["error"]
    if t.is_alive():
        out["profiler_error"] = "trace did not return within 180 s"
    planes: list[str] = []
    if done.get("ok"):
        pbs = [
            os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
            for f in fs if f.endswith(".xplane.pb")
        ]
        if pbs:
            pd = jax.profiler.ProfileData.from_file(
                max(pbs, key=os.path.getmtime)
            )
            planes = [p.name for p in pd.planes]
    out["profiler_planes"] = planes
    out["profiler_has_device_plane"] = any(
        "/device:" in p and "CPU" not in p.upper().split("/device:")[-1]
        for p in planes
    )
    return out


# ------------------------------------------------------------------ resnet


def phase_resnet(jax, jnp, rehearse: bool) -> dict:
    from ddl25spring_tpu.benchmarks import (
        DeviceDataset, build_resnet_scan_step, build_resnet_step,
    )

    devices = jax.devices()[:1]
    per_chip = 8 if rehearse else 1024  # lab/s01_b2_dp_pp.py's TPU default
    t0 = time.perf_counter()
    ds = DeviceDataset(per_chip, n_train=64 if rehearse else None)
    jax.block_until_ready(ds.x)
    data_s = time.perf_counter() - t0
    # bench.py's choice of K: the scan is the TPU input path; a CPU
    # rehearsal takes the single-step builder, as bench.py does there
    K = 1 if rehearse else max(
        k for k in range(1, 17) if ds.batches_per_epoch % k == 0
    )
    if K > 1:
        multi, _, params, opt_state, meta = build_resnet_scan_step(
            devices, 1, 1, 1, per_chip, K, ds.n
        )

        def dispatch(p, o):
            return multi(p, o, ds.x, ds.y, *ds.scan_window(K))
    else:
        # batch 8 cannot carry bench.py's lr of 0.1; the chip run keeps it
        step, params, opt_state, meta = build_resnet_step(
            devices, 1, 1, 1, per_chip, lr=0.01
        )

        def dispatch(p, o):
            return step(p, o, ds.feed())

    t0 = time.perf_counter()
    params, opt_state, loss = dispatch(params, opt_state)
    losses = [float(loss)]
    compile_s = time.perf_counter() - t0
    warm_s = []
    for _ in range(2):  # warm: layouts settle after a round trip
        t0 = time.perf_counter()
        params, opt_state, loss = dispatch(params, opt_state)
        losses.append(float(loss))
        warm_s.append(round(time.perf_counter() - t0, 3))
    n_timed = 2 if rehearse else 4
    t0 = time.perf_counter()
    for _ in range(n_timed):
        params, opt_state, loss = dispatch(params, opt_state)
        losses.append(float(loss))
    run_s = time.perf_counter() - t0
    assert all(l == l and abs(l) < 1e4 for l in losses), losses
    # (batch 8 on fresh batches is noise: the rehearsal checks control
    # flow, the chip run checks learning)
    assert rehearse or losses[-1] < losses[0], f"loss did not fall: {losses}"
    return {
        "layout": meta["layout"], "topology": meta["topology"],
        "dtype": meta["dtype"],
        "per_chip_batch": per_chip, "scan_steps": K,
        "data": ds.provenance, "data_setup_s": round(data_s, 3),
        "compile_s": round(compile_s, 3), "warm_dispatch_s": warm_s,
        "run_s": round(run_s, 3), "timed_steps": n_timed * K,
        "samples_per_s_per_chip": round(n_timed * K * per_chip / run_s, 1),
        "first_loss": losses[0], "last_loss": losses[-1],
        "losses": [round(l, 4) for l in losses],
    }


# ------------------------------------------------------------------- llama


def phase_llama(jax, jnp, rehearse: bool) -> dict:
    lab = load_launcher()
    common = ["--workload", "llama", "--log-every", "1",
              "--batch", "6" if rehearse else "24"]
    iters = "3" if rehearse else "8"
    flash = lab.run_llama(
        lab.parse_args(common + ["--iters", iters]), jax, jnp
    )
    dense = lab.run_llama(
        lab.parse_args(common + ["--iters", "1", "--no-flash"]), jax, jnp
    )
    cfg = flash["cfg"]
    losses = [l for _, l in flash["losses"]]
    assert all(l == l and l < 1e4 for l in losses), losses
    assert losses[-1] < losses[0], f"llama loss did not fall: {losses}"
    # same init (PRNGKey(0)), same batch: iter 0 of both runs
    l_flash, l_dense = losses[0], dense["losses"][0][1]
    assert abs(l_flash - l_dense) <= 2e-2 * abs(l_dense), (l_flash, l_dense)
    text = flash["step"].lower(*flash["step_args"]).as_text()
    dense_text = dense["step"].lower(*dense["step_args"]).as_text()

    def step_seconds(run, n=3):
        """Median wall of ``n`` more steps (the launcher's own loop also
        tokenizes, uploads and logs)."""
        p, o, toks = run["step_args"]
        walls = []
        for _ in range(n):
            t0 = time.perf_counter()
            p, o, loss = run["step"](p, o, toks)
            loss.block_until_ready()
            walls.append(time.perf_counter() - t0)
        return sorted(walls)[n // 2]

    flash_step_s, dense_step_s = step_seconds(flash), step_seconds(dense)
    kernel = "tpu_custom_call" in text
    if not rehearse:
        assert cfg.use_flash and cfg.dtype == "bfloat16", cfg
        assert kernel, "the LLaMA step lowered without the Pallas kernel"
        assert "tpu_custom_call" not in dense_text
    return {
        "config": {
            "dmodel": cfg.dmodel, "num_heads": cfg.num_heads,
            "n_layers": cfg.n_layers, "ctx_size": cfg.ctx_size,
            "vocab_size": cfg.vocab_size, "dtype": cfg.dtype,
            "use_flash": cfg.use_flash,
        },
        "mesh": dict(flash["mesh"].shape),
        "compile_s": round(flash["compile_s"], 3),
        "run_s": round(flash["run_s"], 3),
        "tokens_per_s": round(flash["tokens_per_s"], 1),
        "first_loss": losses[0], "last_loss": losses[-1],
        "losses": [round(l, 4) for l in losses],
        "step_s_flash": flash_step_s, "step_s_dense": dense_step_s,
        "dense_first_loss": l_dense,
        "flash_vs_dense_rel": abs(l_flash - l_dense) / abs(l_dense),
        "tpu_custom_call_in_step": kernel,
    }


# ------------------------------------------------------------------- serve


def dense_greedy(jax, jnp, params, cfg, reqs) -> list[list[int]]:
    """``models/decode.generate`` (dense KV cache, greedy) per request,
    one compile per distinct (prompt length, new tokens) shape."""
    from functools import partial

    from ddl25spring_tpu.models import decode

    fns: dict = {}
    out = []
    for r in reqs:
        shape = (len(r.prompt), r.max_new_tokens)
        if shape not in fns:
            fns[shape] = jax.jit(partial(
                decode.generate, cfg=cfg, max_new_tokens=shape[1]
            ))
        toks = fns[shape](params, jnp.asarray([list(r.prompt)], jnp.int32))
        out.append([int(t) for t in toks[0]])
    return out


def served_vs_dense(jax, jnp, params, cfg, trace, knobs, what: str,
                    **engine_kw) -> dict:
    """Serve ``trace`` to drain and hold every completed request's greedy
    tokens to ``models/decode.generate``.

    Exactly, where exact is defined: the same weights in float32 with
    ``highest`` matmul precision, where the paged and the dense reduction
    orders differ in the last bits only.  In bf16 — the dtype the server
    runs — two random-weight logits within rounding of each other flip
    an argmax and the continuations part for good (seen on the first chip
    run), so there the agreement is REPORTED, not asserted."""
    from ddl25spring_tpu.serve import driver
    from ddl25spring_tpu.utils.config import replace

    def run(c):
        eng = driver._build_engine(
            params, c, knobs, clock="wall", temperature=0.0,
            trace_label=None, **engine_kw,
        )
        eng.warmup()
        m = eng.run(trace, max_steps=50_000)
        assert eng.drained and m["completed"] == len(trace), m
        leak = eng.mem_leak_check()
        assert leak["ok"], leak
        reqs = sorted(eng.done, key=lambda r: r.rid)
        got = [[int(t) for t in r.tokens] for r in reqs]
        return eng, got, dense_greedy(jax, jnp, params, c, reqs)

    with jax.default_matmul_precision("highest"):
        eng32, got32, want32 = run(replace(cfg, dtype="float32"))
    bad = [(g, w) for g, w in zip(got32, want32) if g != w]
    assert not bad, f"{what}: served tokens differ from dense: {bad[:2]}"
    out = {
        "requests": len(got32),
        "tokens_checked_fp32": sum(len(w) for w in want32),
        "tokens_equal_dense_fp32": True,
        "leaked_pages": 0,
    }
    if cfg.dtype != "float32":
        _, got, want = run(cfg)
        same = sum(
            a == b for g, w in zip(got, want) for a, b in zip(g, w)
        )
        out[f"{cfg.dtype}_tokens_equal_dense"] = (
            f"{same}/{sum(len(w) for w in want)}"
        )
        out[f"{cfg.dtype}_requests_equal_dense"] = (
            f"{sum(g == w for g, w in zip(got, want))}/{len(want)}"
        )
    return out, eng32


def phase_serve(jax, jnp, rehearse: bool) -> dict:
    from ddl25spring_tpu.models import llama
    from ddl25spring_tpu.serve import driver
    from ddl25spring_tpu.serve.traffic import TrafficSpec, synth_trace

    model = "tiny" if rehearse else "ref"
    t0 = time.perf_counter()
    rec = driver.run_serve_bench(
        smoke=rehearse, model=model, duration_s=2.0, rate_rps=6.0,
        profile="flat", seed=0,
        ledger_path=os.path.join(OUT_DIR, "smoke_ledger.jsonl"),
        # the virtual-clock A/B arms are CPU-era judges, not the path
        skip_ab=True, skip_prefix_ab=True, skip_spec_ab=True,
        skip_tp_ab=True,
    )
    bench_s = time.perf_counter() - t0
    ramp = rec["ramp"]
    assert ramp["completed"] > 0 and ramp["generated_tokens"] > 0, ramp
    assert ramp["completed"] == ramp["admitted"], ramp

    # the same engine build, the same traffic generator: tokens this time
    cfg = driver.serve_model(model)
    params = llama.init_llama_params(jax.random.PRNGKey(0), cfg)
    trace = synth_trace(TrafficSpec(
        seed=1, duration_s=1.0, rate_rps=6.0, profile="flat",
        vocab_size=cfg.vocab_size,
    ))
    check, _ = served_vs_dense(
        jax, jnp, params, cfg, trace, driver.engine_knobs(smoke=rehearse),
        "serve",
    )
    return {
        "model": model, "bench_s": round(bench_s, 3),
        "requests_served": ramp["completed"],
        "tokens_served": ramp["generated_tokens"],
        "tokens_per_sec_per_chip": ramp.get("tokens_per_sec_per_chip"),
        "ttft_p50_s": ramp.get("ttft_s_p50"),
        **check,
    }


# ---------------------------------------------------------------- 4 chips


def per_device_bytes(jax) -> list[int | None]:
    return [
        (d.memory_stats() or {}).get("bytes_in_use") for d in jax.devices()
    ]


def shard_spread(x) -> dict:
    """How one array sits on its devices: distinct shard shapes and the
    number of devices holding a DIFFERENT slice of it."""
    shards = x.addressable_shards
    return {
        "global": list(x.shape),
        "shard": list(shards[0].data.shape),
        "devices": len(shards),
        "distinct_slices": len({str(s.index) for s in shards}),
    }


def phase_dp_pp(jax, jnp, rehearse: bool) -> dict:
    """The LLaMA ``ref`` DP x PP train step on ``mesh(data=2, stage=2)``
    against the one-device step on the same batch.  SGD at lr 1 makes the
    parameter delta the gradient itself, so "updated params agree" is a
    statement about every gradient leaf, not about Adam's sign."""
    import numpy as np
    import optax

    from ddl25spring_tpu.models import llama
    from ddl25spring_tpu.parallel.pipeline import (
        make_pipeline_train_step, shard_staged_params,
    )
    from ddl25spring_tpu.utils.config import LlamaConfig, replace
    from ddl25spring_tpu.utils.mesh import make_mesh

    devs = jax.devices()
    cfg = LlamaConfig() if not rehearse else replace(
        LlamaConfig(), vocab_size=64, dmodel=32, num_heads=2, n_layers=2,
        ctx_size=16, dtype="float32",
    )
    cfg = replace(cfg, use_flash=not rehearse)
    M, batch = 2, 8
    params = llama.init_llama_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, cfg.ctx_size), 0, cfg.vocab_size
    )
    tx = optax.sgd(1.0)

    def one_step(mesh, S, data_axis):
        staged = shard_staged_params(
            llama.split_blocks_for_stages(params, S), mesh
        )
        step = make_pipeline_train_step(
            cfg, tx, mesh, M, data_axis=data_axis, donate=False
        )
        t0 = time.perf_counter()
        new, _, loss = step(staged, tx.init(staged), tokens)
        loss = float(loss)
        return staged, new, loss, time.perf_counter() - t0, step

    mesh4 = make_mesh(devs[:4], data=2, stage=2)
    staged4, new4, loss4, s4, step4 = one_step(mesh4, 2, "data")
    placed = per_device_bytes(jax)
    spread = shard_spread(staged4["blocks"]["wq"])
    assert spread["devices"] == 4 and spread["distinct_slices"] == 2, spread
    assert spread["shard"][0] == 1, spread  # one stage's layers per device
    if not rehearse:
        assert "tpu_custom_call" in step4.lower(
            staged4, tx.init(staged4), tokens
        ).as_text()
    mesh1 = make_mesh(devs[:1], data=1, stage=1)
    staged1, new1, loss1, s1, _ = one_step(mesh1, 1, None)

    def grads(old, new):  # [S, L/S, ...] -> [L, ...] so both layouts align
        g = jax.tree.map(lambda a, b: np.asarray(a - b, np.float32), old, new)
        g["blocks"] = jax.tree.map(
            lambda x: x.reshape((-1,) + x.shape[2:]), g["blocks"]
        )
        return g

    g4, g1 = grads(staged4, new4), grads(staged1, new1)
    worst = 0.0
    for a, b in zip(jax.tree.leaves(g4), jax.tree.leaves(g1)):
        assert a.shape == b.shape, (a.shape, b.shape)
        cos = float((a * b).sum() / (
            np.linalg.norm(a) * np.linalg.norm(b) + 1e-30
        ))
        worst = max(worst, 1.0 - cos)
    tol = 1e-5 if rehearse else 2e-2
    assert abs(loss4 - loss1) <= tol * abs(loss1), (loss4, loss1)
    assert worst <= tol, f"a gradient leaf disagrees: 1-cos = {worst}"
    return {
        "mesh": {"data": 2, "stage": 2}, "microbatches": M, "batch": batch,
        "use_flash": cfg.use_flash, "loss_4chip": loss4,
        "loss_1chip": loss1, "loss_rel": abs(loss4 - loss1) / abs(loss1),
        "worst_grad_leaf_1_minus_cos": worst,
        "first_step_s_4chip": round(s4, 3),
        "first_step_s_1chip": round(s1, 3),
        "bytes_in_use_per_device_after_placement": placed,
        "blocks_wq_spread": spread,
    }


def phase_tp_serve(jax, jnp, rehearse: bool) -> dict:
    """TP-sharded serving on ``mesh(model=2)`` — the reference LLaMA has
    6 heads, which 2 divides and 4 does not — against one-device greedy
    generation."""
    from ddl25spring_tpu.models import llama
    from ddl25spring_tpu.serve import driver
    from ddl25spring_tpu.serve.traffic import TrafficSpec, synth_trace

    model = "tiny" if rehearse else "ref"
    cfg = driver.serve_model(model)
    params = llama.init_llama_params(jax.random.PRNGKey(0), cfg)
    trace = synth_trace(TrafficSpec(
        seed=2, duration_s=1.0, rate_rps=6.0, profile="flat",
        vocab_size=cfg.vocab_size,
    ))
    check, eng = served_vs_dense(
        jax, jnp, params, cfg, trace, driver.engine_knobs(smoke=rehearse),
        "tp-serve", tp=2,
    )
    placed = per_device_bytes(jax)
    kv = shard_spread(eng.pool["k"])
    wq = shard_spread(eng.params["blocks"]["wq"])
    for s in (kv, wq):
        assert s["devices"] == 2 and s["distinct_slices"] == 2, s
    return {
        "model": model, "mesh": {"model": 2},
        "bytes_in_use_per_device_after_placement": placed,
        "kv_pool_spread": kv, "wq_spread": wq, **check,
    }


# -------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the DP x PP and TP-serving comparisons")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny sizes; never prints ok:true")
    args = ap.parse_args(argv)

    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if args.chips == 4:
            from ddl25spring_tpu.utils.platform import force_cpu_devices

            force_cpu_devices(4)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax

    dev = jax.devices()[0]  # no accelerator and no --rehearse: raises
    device = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    if not args.rehearse and dev.platform != "tpu":
        print(f"chip_smoke: no TPU here (found {device}); this script "
              "proves the chip path and does not fall back",
              file=sys.stderr)
        return 2
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {device}",
              file=sys.stderr)
        return 2

    import jax.numpy as jnp
    import jaxlib

    from ddl25spring_tpu.data.native_loader import rebuild_native_libs
    from ddl25spring_tpu.utils.platform import enable_compilation_cache

    os.makedirs(OUT_DIR, exist_ok=True)
    cache_dir = enable_compilation_cache()
    cache = {"hits": 0, "misses": 0}

    def _count(event, **_):
        if event.endswith("/cache_hits"):
            cache["hits"] += 1
        elif event.endswith("/cache_misses"):
            cache["misses"] += 1

    jax.monitoring.register_event_listener(_count)
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — a CPU-only install has none
        libtpu = None
    emit(
        phase="environment", device=device, rehearsal=args.rehearse,
        jax=jax.__version__, jaxlib=jaxlib.__version__, libtpu=libtpu,
        compile_cache_dir=cache_dir,
        compile_cache_entries_at_start=(
            len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
        ),
        native_libs_built=rebuild_native_libs(),
    )

    phases = (
        [("dp_pp", phase_dp_pp), ("tp_serve", phase_tp_serve)]
        if args.chips == 4 else
        [("runtime_probe", probe_runtime), ("resnet", phase_resnet),
         ("llama", phase_llama), ("serve", phase_serve)]
    )
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            # what the launchers print for their users goes to stderr:
            # stdout carries one JSON object per line and nothing else
            with contextlib.redirect_stdout(sys.stderr):
                out = fn(jax, jnp, args.rehearse)
        except Exception:  # noqa: BLE001 — any phase failing fails the run
            traceback.print_exc()
            emit(phase=name, ok=False,
                 seconds=round(time.perf_counter() - t0, 3))
            return 1
        emit(phase=name, ok=True,
             seconds=round(time.perf_counter() - t0, 3),
             peak_bytes_in_use=peak_bytes(dev), **out)
    emit(phase="compile_cache", dir=cache_dir, **cache,
         entries_at_end=(
             len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
         ))
    if args.rehearse:
        emit(ok=False, rehearsal=True, device=device)
        return 3
    # exactly the contract's keys, and nothing more, on the last line
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
