"""Shared builder for the BASELINE.json north-star benchmark workload.

Both the headline ``bench.py`` and the ``lab/s01_b2_dp_pp.py`` driver
(`run-b2.sh`) construct the ResNet-18/CIFAR-10 DP(+PP) train step from
here, so the bench can never drift from what the launcher actually runs.

The returned step takes a RAW uint8 batch ``(x_u8 [B,32,32,3], y [B])`` and
normalizes on device *inside* the jit boundary — 4x less host->device
traffic than fp32, and XLA fuses the normalize into the first conv's input
pipeline.  Parity anchor: the benchmark config of ``lab/run-b2.sh``
(reference: ``lab/s01_b2_dp_pp.py:93-227``, retargeted per BASELINE.json).
"""

from __future__ import annotations

import time
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ddl25spring_tpu.data.native_loader import normalize_on_device
from ddl25spring_tpu.models.resnet import ResNet18, make_resnet_stages
from ddl25spring_tpu.ops.losses import cross_entropy_logits
from ddl25spring_tpu.parallel import bucketing
from ddl25spring_tpu.parallel.bucketing import donate_argnums
from ddl25spring_tpu.parallel.dp import make_dp_train_step
from ddl25spring_tpu.parallel.het_pipeline import make_het_pipeline_train_step
from ddl25spring_tpu.utils.mesh import make_mesh

BASELINE_SAMPLES_PER_SEC_PER_CHIP = 5_000.0


def build_resnet_step(
    devices: list,
    dp: int,
    S: int,
    num_microbatches: int,
    batch: int,
    lr: float = 0.1,
    dtype: Any = None,
    instrument: bool | None = None,
    donate: bool | None = None,
    sentinel: bool | None = None,
    overlap: bool = False,
):
    """Build the north-star train step on ``devices[: dp * S]``.

    ``S >= 2`` -> the S-stage heterogeneous pipeline x DP (``layout
    "dppp"``; S up to 4, covering the reference's 2-pipeline x 3-stage
    flagship topology, ``lab/s01_b2_dp_pp.py:22-29``); ``S == 1`` -> pure
    DP.  Returns ``(step, params, opt_state, meta)`` where
    ``step(params, opt_state, (x_u8, y))`` is jitted and ``meta`` carries
    layout/topology strings and chip count for reporting.

    ``instrument`` threads through to the DP / pipeline builders
    (:mod:`ddl25spring_tpu.obs` counters; None = follow the global flag,
    True/False hard-enable/-disable,
    zero-cost and HLO-identical when disabled).

    ``donate`` (default on): the returned step aliases its params/
    opt-state inputs to the outputs (``donate_argnums=(0, 1)``), so the
    ResNet replica + momentum buffers live once in HBM instead of twice
    across the update — callers must rebind ``params, opt_state`` from
    the step's outputs every call (``timed_run`` and both drivers do).

    ``sentinel`` threads through to the inner DP / pipeline builder:
    in-step numerics sentinels (loss, grad global-norm, non-finite leaf
    flags, update ratio) with policy log/halt/skip on violation
    (:mod:`ddl25spring_tpu.obs.sentinels`; None = follow
    ``DDL25_SENTINELS`` at build time; HLO-identical when disabled).

    ``overlap`` (pure-DP layouts only, ``S == 1``): the grad-bucket
    all-reduces are emitted inside the backward in backward-readiness
    bucket order instead of after the full grad tree
    (:func:`ddl25spring_tpu.parallel.dp.make_dp_train_step`'s overlap
    mode — the graft-lint H001 restructure).  The layout string becomes
    ``"dp-overlap"`` so BENCH lines and perf-ledger records name the
    variant they measured.  Bitwise-equal to sync DP (pinned).
    """
    if S not in (1, 2, 3, 4):
        raise ValueError(f"resnet pipeline supports S in (1, 2, 3, 4), got {S}")
    if overlap and S != 1:
        raise ValueError(
            "overlap applies to the pure-DP layout (S == 1); the DPxPP "
            "het pipeline owns its own gradient reduction"
        )
    n_used = dp * S
    M = num_microbatches if S >= 2 else 1
    if batch % (dp * M):
        raise ValueError(f"batch {batch} not divisible by dp*M = {dp * M}")
    if dtype is None:
        dtype = jnp.bfloat16 if devices[0].platform == "tpu" else jnp.float32
    tx = optax.sgd(lr, momentum=0.9)
    x8 = jnp.zeros((8, 32, 32, 3), jnp.float32)

    if S >= 2:
        mesh = (
            make_mesh(devices[:n_used], data=dp, stage=S)
            if dp > 1
            else make_mesh(devices[:S], stage=S)
        )
        stages = make_resnet_stages(S, dtype=dtype)
        params, shapes, h = [], [], x8
        for i, sm in enumerate(stages):
            p = sm.init(jax.random.PRNGKey(i), h)["params"]
            h = sm.apply({"params": p}, h)
            params.append(p)
            shapes.append(h.shape)
        params = tuple(params)
        mb = batch // M // dp
        inner = make_het_pipeline_train_step(
            [
                (lambda sm: lambda p, h: sm.apply({"params": p}, h))(sm)
                for sm in stages
            ],
            lambda logits, b: cross_entropy_logits(logits, b["y"]),
            (mb, 32, 32, 3), [(mb,) + s[1:] for s in shapes],
            tx, mesh, M, data_axis="data" if dp > 1 else None,
            compute_dtype=dtype, instrument=instrument, sentinel=sentinel,
        )

        @partial(jax.jit, donate_argnums=donate_argnums(donate))
        def step(params, opt_state, raw):
            x = normalize_on_device(raw[0], dtype)
            return inner(params, opt_state, {"x": x, "y": raw[1]})

        layout = "dppp"
        topo = f"mesh(data={dp}, stage={S}), microbatches={M}"
    else:
        mesh = make_mesh(devices[:n_used], data=dp)
        model = ResNet18(norm="group", dtype=dtype)
        params = model.init(jax.random.PRNGKey(0), x8)["params"]

        def loss_fn(p, bat, key):
            xb, yb = bat
            logits = model.apply({"params": p}, xb.astype(dtype), train=True)
            return cross_entropy_logits(logits, yb)

        inner = make_dp_train_step(
            loss_fn, tx, mesh, per_shard_rng=False, instrument=instrument,
            sentinel=sentinel, overlap=overlap,
        )
        key = jax.random.PRNGKey(1)

        @partial(jax.jit, donate_argnums=donate_argnums(donate))
        def step(params, opt_state, raw):
            x = normalize_on_device(raw[0], dtype)
            return inner(params, opt_state, (x, raw[1]), key)

        layout = "dp-overlap" if overlap else "dp"
        topo = f"mesh(data={dp})"

    opt_state = tx.init(params)
    meta = {
        "n_chips": n_used,
        "batch": batch,
        "layout": layout,
        "topology": topo,
        "device": devices[0],
        # chosen from the device's platform when not given (bf16 on TPU,
        # fp32 elsewhere): carried so that every line SAYS which it ran
        "dtype": jnp.dtype(dtype).name,
        "mesh": mesh,
        "num_stages": S,
        "num_microbatches": M,
        # the effective grad-bucket threshold (DDL25_BUCKET_BYTES-aware)
        # rides every BENCH line / perf-ledger record so sweep results
        # stay comparable across runs; the DPxPP pipeline owns its own
        # reduction and carries None
        "bucket_bytes": (
            bucketing.resolve_bucket_bytes(bucketing.AUTO)
            if S == 1 else None
        ),
        "overlap": overlap,
    }
    return step, params, opt_state, meta


def build_resnet_scan_step(
    devices: list,
    dp: int,
    S: int,
    num_microbatches: int,
    batch: int,
    scan_steps: int,
    n_data: int,
    lr: float = 0.1,
    dtype: Any = None,
    instrument: bool | None = None,
    donate: bool | None = None,
    sentinel: bool | None = None,
    overlap: bool = False,
):
    """K train steps per dispatch: the on-device input+train loop.

    Each Python dispatch costs a host round-trip.  Fusing ``scan_steps``
    iterations into one ``lax.scan`` amortizes it while keeping REAL input
    semantics: the scan
    body draws the next disjoint batch of the epoch's on-device
    permutation, exactly like :meth:`DeviceDataset.feed`, then runs the
    same jitted train step ``build_resnet_step`` returns (traced inline).
    This is the idiomatic TPU input design: data lives in HBM, the input
    pipeline is part of the compiled program, the host only ticks epochs.

    Returns ``(multi, step1, params, opt_state, meta)`` with
    ``multi(params, opt_state, xs_u8, ys, key, epoch, off0)`` jitted and
    ``step1`` the inner per-batch step (for FLOPs accounting — XLA's cost
    analysis counts a scan body once, so per-step FLOPs come from the
    inner program); pair with :meth:`DeviceDataset.scan_window`.

    TPU-only in practice: on the XLA CPU backend a ``lax.scan`` whose body
    carries convolutions executes ~55x slower than the same steps
    dispatched sequentially (measured: 2 jitted ResNet steps 3.0 s vs the
    same two steps scanned 164 s; conv custom-calls appear not to survive
    inside control flow there).  CPU callers — tests, `--force-cpu-devices` smokes —
    should use K=1 / `build_resnet_step`, as `bench.py` and the b2 driver
    do automatically.
    """

    step1, params, opt_state, meta = build_resnet_step(
        devices, dp, S, num_microbatches, batch, lr, dtype,
        instrument=instrument, donate=donate, sentinel=sentinel,
        overlap=overlap,
    )
    K = scan_steps

    @partial(jax.jit, donate_argnums=donate_argnums(donate))
    def multi(params, opt_state, xs, ys, key, epoch, off0):
        perm = jax.random.permutation(jax.random.fold_in(key, epoch), n_data)

        def body(carry, i):
            p, o = carry
            idx = jax.lax.dynamic_slice(perm, (off0 + i * batch,), (batch,))
            p, o, loss = step1(p, o, (xs[idx], ys[idx]))
            return (p, o), loss

        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), jnp.arange(K)
        )
        return params, opt_state, losses[-1]

    meta = dict(meta, scan_steps=K)
    return multi, step1, params, opt_state, meta


class DeviceDataset:
    """TPU-native input pipeline for datasets that fit in HBM.

    The whole train split lives on device as raw uint8 (CIFAR-10's 50k x
    32x32x3 = 147 MiB vs >= 16 GiB HBM/chip); every step draws the next
    batch of an epoch-wise on-device shuffle — a `jax.random.permutation`
    keyed per epoch, sliced per step, gathered on device.  Real input
    semantics (each step a fresh disjoint batch, every sample visited once
    per epoch) with **zero steady-state host->device traffic**: the
    idiomatic JAX input path for small datasets, and the design that maps
    to TPU hardware, where HBM bandwidth (~800 GB/s) dwarfs the host link.

    Contrast with the reference, which re-reads mini-batches through a
    host-side ``DataLoader`` every step (`lab/tutorial_1a/hfl_complete.py`
    loaders; `lab/s01_b1_microbatches.py` TinyStories iterator) because
    torch/gloo keeps tensors host-resident between ranks.
    """

    input_mode = "hbm-resident-shuffle"

    def __init__(self, batch: int, n_train: int | None = None):
        from ddl25spring_tpu.data.cifar10 import load_cifar10_u8

        d = load_cifar10_u8(n_train=n_train or 50_000)
        self.provenance = d["provenance"]
        self.x = jnp.asarray(d["x"])  # [N,32,32,3] uint8, one-time upload
        self.y = jnp.asarray(d["y"])
        self.n = int(self.x.shape[0])
        if batch > self.n:
            raise ValueError(f"batch {batch} exceeds dataset size {self.n}")
        self.batch = batch
        # drop-last epochs: nb disjoint batches per epoch, every sample at
        # most once per epoch (the tail n % B is dropped, torch drop_last)
        self.batches_per_epoch = self.n // batch
        self._i = 0
        n, B = self.n, batch

        @jax.jit
        def select(xs, ys, key, epoch, off):
            perm = jax.random.permutation(jax.random.fold_in(key, epoch), n)
            idx = jax.lax.dynamic_slice(perm, (off,), (B,))
            return xs[idx], ys[idx]

        self._select = select
        self.seed = 20  # epoch-shuffle key; surfaced in run metadata
        self._key = jax.random.PRNGKey(self.seed)
        # block on the one-time upload so it's not billed to the timed loop
        self.x.block_until_ready()
        self.y.block_until_ready()
        self.fixed = self.feed()  # also the template for compiled_flops

    def feed(self):
        # epoch/offset math on HOST Python ints: immune to the int32
        # overflow a traced i*B product would hit at i ~ 2^31/B
        epoch, b = divmod(self._i, self.batches_per_epoch)
        self._i += 1
        out = self._select(
            self.x, self.y, self._key,
            np.int32(epoch % (2**31 - 1)), np.int32(b * self.batch),
        )
        return out

    @property
    def cursor(self) -> int:
        """The input-pipeline position (which batch/window of the epoch
        permutation comes next).  Part of the FULL resume state the
        fault-tolerance layer checkpoints (:mod:`ddl25spring_tpu.ft.
        autosave`): together with :attr:`seed` it pins the exact batch
        sequence, so a resumed run consumes the batches the dead run
        never got to, not a replay of its epoch from zero."""
        return self._i

    @cursor.setter
    def cursor(self, value: int) -> None:
        self._i = int(value)

    def scan_window(self, K: int):
        """Host-side scalars for one ``build_resnet_scan_step`` dispatch:
        ``(key, epoch, off0)`` covering K consecutive disjoint batches of
        the epoch permutation.  K must divide batches_per_epoch so a
        window never crosses an epoch boundary (the scan body shares one
        perm).  Uses the same step counter as :meth:`feed` — don't
        interleave the two modes within a run."""
        if self.batches_per_epoch % K:
            raise ValueError(
                f"scan_steps={K} must divide batches_per_epoch="
                f"{self.batches_per_epoch}"
            )
        epoch, w = divmod(self._i, self.batches_per_epoch // K)
        self._i += 1
        return (
            self._key,
            np.int32(epoch % (2**31 - 1)),
            np.int32(w * K * self.batch),
        )

    def close(self):
        pass


class InputFeed:
    """The benchmark input pipeline, shared by ``bench.py`` and the lab
    driver: native C++ streaming of raw uint8 batches when enabled, with a
    fixed device-resident batch as the fallback/secondary mode.

    ``stream``: ``True`` forces streaming (synthesizing CIFAR-format
    binaries when none exist), ``False`` disables, ``None`` auto-enables
    when binaries are present.  ``feed()`` yields the primary mode's batch;
    ``feed_fixed()`` always yields the fixed batch.
    """

    def __init__(
        self,
        batch: int,
        stream: bool | None = None,
        workers: int = 2,
        prefetch_depth: int = 4,
    ):
        from ddl25spring_tpu.data.cifar10 import (
            _find_loader_dir,
            ensure_bin_dir,
            load_cifar10_u8,
        )
        from ddl25spring_tpu.data.native_loader import (
            NativeCifar10Loader,
            NativeLoaderUnavailable,
        )

        self.loader = self._stream = None
        self.input_mode, self.provenance = "fixed-device-batch", "synthetic"
        want = stream if stream is not None else (_find_loader_dir() is not None)
        if want:
            try:
                bin_dir, self.provenance = ensure_bin_dir()
                self.loader = NativeCifar10Loader(
                    bin_dir, batch_size=batch, normalize=False,
                    workers=workers, prefetch_depth=prefetch_depth,
                )
                self._stream = iter(self.loader)
                self.input_mode = "native-stream-uint8"
                print(f"native streaming input: {bin_dir} "
                      f"({self.provenance} data)")
            except NativeLoaderUnavailable as e:
                print(f"native loader unavailable ({e}); using fixed batch")

        if self._stream is not None:
            xs, ys = next(self._stream)  # doubles as the fixed batch
        else:
            d = load_cifar10_u8(n_train=batch)
            self.provenance = d["provenance"]
            xs, ys = d["x"], d["y"]
        self.fixed = (jnp.asarray(xs), jnp.asarray(ys))

    @property
    def streaming(self) -> bool:
        return self._stream is not None

    def feed(self):
        if self._stream is None:
            return self.fixed
        xs, ys = next(self._stream)
        return jnp.asarray(xs), jnp.asarray(ys)

    def feed_fixed(self):
        return self.fixed

    def close(self):
        if self.loader is not None:
            self.loader.close()
            self.loader = None


def report_line(layout, sps_chip, input_mode, frac, tf, **extra):
    """The one-line JSON record both drivers print (driver contract:
    metric/value/unit/vs_baseline, plus self-describing fields)."""
    import json

    return json.dumps({
        "metric": f"cifar10_resnet18_{layout}_samples_per_sec_per_chip",
        "value": round(sps_chip, 1),
        "unit": "samples/sec/chip",
        "vs_baseline": round(sps_chip / BASELINE_SAMPLES_PER_SEC_PER_CHIP, 3),
        "input": input_mode,
        "mfu": round(frac, 4) if frac else None,
        "achieved_tflops_per_chip": round(tf, 1) if tf else None,
        **extra,
    })


def timed_run(
    step,
    params,
    opt_state,
    feed,
    steps: int,
    warmup: int,
    logger=None,
    label: str = "run",
    samples_per_step: int | None = None,
    steps_per_call: int = 1,
    on_step=None,
    step_offset: int = 0,
    goodput=None,
):
    """Warmup (compile) then time ``steps`` calls; returns ``(dt, params,
    opt_state)``.  The clock stops on a fetch of the last loss: a scalar
    host transfer that waits for the step that produced it
    (``block_until_ready`` waits as well — ``chip_smoke.py``'s
    ``runtime_probe`` measures that on every chip run).

    ``logger`` (an :class:`~ddl25spring_tpu.obs.MetricsLogger`): log one
    ``step`` record per call — ``{step, wall_s, samples, loss, label}`` —
    with host spans around warmup and the timed window.  Per-record wall
    times require blocking on each call's loss (one scalar transfer), so
    the telemetry path pays one extra host round-trip per dispatch — that
    sync is inherent to per-step timing and stays in the measurement, but
    the JSONL write+flush does NOT: the clock is re-armed after each
    ``logger.log`` and the returned bulk ``dt`` is the sum of the
    per-record walls, so logging I/O never inflates the headline.
    ``steps_per_call`` scales the per-record sample count for scan-fused
    dispatches (K train steps per call).

    Every dispatch also feeds the flight recorder
    (:data:`ddl25spring_tpu.obs.flight` — a host-side ring-buffer append,
    never part of the compiled program): the logger path records one
    step entry per call (the crash-surviving post-mortem trail), the
    bare path beats liveness so a stall watchdog watching the run sees
    progress either way.

    ``on_step(global_i, params, opt_state, loss)`` is the
    fault-tolerance hook (:mod:`ddl25spring_tpu.ft`): called after each
    timed dispatch completes, OUTSIDE the timed window (the clock
    re-arms after it, like the logging I/O), with ``global_i =
    step_offset + i`` so chaos faults and checkpoint cadence count
    absolute train-step indices across resumes.  Supplying it forces
    one loss sync per dispatch (the per-step completion the checkpoint
    gate needs) — the same cost the logger path already pays.
    ``step_offset`` also shifts the flight/logger step indices so a
    resumed run's records continue where the dead run's stopped.

    ``goodput`` (an :class:`~ddl25spring_tpu.obs.goodput.GoodputMeter`)
    bills the warmup/compile bracket and each timed dispatch into the
    run's badput decomposition — the same perf-counter reads the
    timing already takes, re-expressed on the meter's axis, so the
    measurement itself is unchanged.
    """
    from ddl25spring_tpu import obs

    loss = None
    w0 = goodput.now() if goodput is not None else 0.0
    with obs.span("warmup", label=label, n=warmup):
        for _ in range(warmup):
            params, opt_state, loss = step(params, opt_state, feed())
            obs.flight.beat()
        if loss is not None:
            float(loss)
    if goodput is not None and warmup > 0:
        goodput.add("warmup_compile", w0, goodput.now(), label=label)
    if logger is None and on_step is None:
        t0 = time.perf_counter()
        for _ in range(steps):
            params, opt_state, loss = step(params, opt_state, feed())
            obs.flight.beat()
        float(loss)  # the step chain is data-dependent through params
        dt = time.perf_counter() - t0
        if goodput is not None and steps > 0:
            # one bulk window: the fast path has no per-step walls
            g1 = goodput.now()
            goodput.add("useful_step", g1 - dt, g1, label=label,
                        steps=steps)
        return dt, params, opt_state

    total = 0.0
    with obs.span("timed_run", label=label, steps=steps):
        prev = time.perf_counter()
        for i in range(steps):
            gi = step_offset + i
            with obs.span("step", label=label, i=gi):
                params, opt_state, loss = step(params, opt_state, feed())
                lval = float(loss)  # force completion per call
            wall = time.perf_counter() - prev
            total += wall
            if goodput is not None:
                g1 = goodput.now()
                goodput.note_step(gi, g1 - wall, g1,
                                  resumable=on_step is not None)
            obs.flight.record(
                kind="step", strategy=label, step=gi,
                wall_s=round(wall, 6), loss=lval,
                # only the checkpoint-hooked phase's indices share units
                # with the durable steps — the steps-lost accounting in
                # bench.py keys on this marker so a secondary phase's
                # single-step indices never mix with K-fused dispatch
                # indices
                **({"resumable": True} if on_step is not None else {}),
            )
            if logger is not None:
                logger.log(
                    step=gi,
                    label=label,
                    wall_s=wall,
                    loss=lval,
                    **(
                        {"samples": samples_per_step * steps_per_call}
                        if samples_per_step
                        else {}
                    ),
                    **(
                        {"fused_steps": steps_per_call}
                        if steps_per_call > 1 else {}
                    ),
                )
            if on_step is not None:
                # may save a checkpoint, arm a chaos fault, or raise a
                # simulated device loss — never inside the timed window
                on_step(gi, params, opt_state, lval)
            prev = time.perf_counter()  # I/O stays outside the window
    return total, params, opt_state
