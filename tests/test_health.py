"""The runtime health layer: in-step numerics sentinels, the
crash-surviving flight recorder, and the stall watchdog.

The contract pins, in order:

1. **HLO identity** — with sentinels disabled, every instrumented
   train-step builder lowers to HLO byte-identical to a build with the
   guard explicitly off (the PR-1 zero-cost pattern, per strategy); with
   sentinels enabled the guard actually lands in the program.
   Lowerings are cached per (builder, mode) — the
   ``tests/test_xla_analytics.py`` compile-once pattern.
2. **Detection** — a NaN injected into a DP and a ZeRO-3 step is caught
   within that step, recorded in the flight ring, and identified down to
   the violating gradient leaf; ``flight.json`` dump contents pinned.
3. **Policies** — ``skip`` suppresses the poisoned update on device,
   ``halt`` raises with flight-record context (strategy, step, leaf),
   not a bare FloatingPointError.
4. **Watchdog** — an artificial stall produces a dump carrying every
   host thread's stack, including the wedged thread's blocking frame.
"""

import contextlib
import json
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ddl25spring_tpu.obs import flight, sentinels
from ddl25spring_tpu.obs.watchdog import StallWatchdog, thread_stacks
from ddl25spring_tpu.utils.mesh import make_mesh


@pytest.fixture(autouse=True)
def _health_clean():
    """Sentinels off, flight ring empty, before and after every test —
    the module flags must never leak (same discipline as test_obs)."""
    sentinels.enable(False)
    sentinels.set_policy("log")
    sentinels.reset()
    flight.reset()
    flight.configure(run_dir=None)
    yield
    sentinels.enable(False)
    sentinels.set_policy("log")
    sentinels.reset()
    flight.reset()
    flight.configure(run_dir=None)


# --------------------------------------------------- tiny builder setups


def _mlp_loss(p, batch, key):
    del key
    x, y = batch
    return jnp.mean((jnp.tanh(x @ p["w1"]) @ p["w2"] - y) ** 2)


def _mlp_params():
    return {
        "w1": jnp.full((8, 16), 0.5, jnp.float32),
        "w2": jnp.full((16, 4), 0.5, jnp.float32),
    }


def _mlp_batch(bad: bool = False, n: int = 8):
    x = jnp.ones((n, 8), jnp.float32)
    if bad:
        x = x.at[0, 0].set(jnp.nan)
    return x, jnp.ones((n, 4), jnp.float32)


def _builder_setups(devices8):
    """name -> (build() -> (lowerable, args)) for every sentinel-wired
    train-step builder.  build() is called under the desired sentinel
    scope; tiny workloads keep ~20 lowerings cheap."""
    from ddl25spring_tpu.parallel import dp, ep, het_pipeline, sp, tp, zero
    from ddl25spring_tpu.utils.config import LlamaConfig

    tx = optax.sgd(0.1)
    p = _mlp_params()
    batch = _mlp_batch()
    key = jax.random.PRNGKey(0)
    mesh2 = make_mesh(devices8[:2], data=2)
    cfg = LlamaConfig(
        vocab_size=32, dmodel=8, num_heads=2, n_layers=2, ctx_size=8,
        dtype="float32",
    )
    toks = jnp.zeros((4, cfg.ctx_size), jnp.int32)

    def serial():
        step = dp.make_train_step(_mlp_loss, tx)
        return step, (p, tx.init(p), batch, key)

    def dp_grad():
        step = dp.make_dp_train_step(
            _mlp_loss, tx, mesh2, per_shard_rng=False
        )
        return step, (p, tx.init(p), batch, key)

    def dp_overlap():
        step = dp.make_dp_train_step(
            _mlp_loss, tx, mesh2, per_shard_rng=False, overlap=True
        )
        return step, (p, tx.init(p), batch, key)

    def dp_wavg():
        step = dp.make_dp_weight_avg_step(
            _mlp_loss, tx, mesh2, per_shard_rng=False
        )
        return step, (p, dp.stack_opt_state(tx.init(p), 2), batch, key)

    def zero3_overlap():
        step = zero.make_zero_dp_train_step(
            _mlp_loss, tx, mesh2, p, per_shard_rng=False, overlap=True
        )
        shards = zero.zero_shard_params(p, mesh2)
        return step, (shards, tx.init(shards), batch, key)

    def zero_stage(stage):
        def build():
            if stage == 3:
                step = zero.make_zero_dp_train_step(
                    _mlp_loss, tx, mesh2, p, per_shard_rng=False
                )
            else:
                step = zero.make_zero_partitioned_train_step(
                    _mlp_loss, tx, mesh2, p, stage=stage,
                    per_shard_rng=False,
                )
            shards = zero.zero_shard_params(p, mesh2)
            args = (
                (shards if stage == 3 else p),
                tx.init(shards), batch, key,
            )
            return step, args
        return build

    def zero3_llama():
        step = zero.make_zero3_llama_train_step(
            cfg, tx, mesh2, per_shard_rng=False
        )
        shards = zero_shard_llama(cfg, mesh2)
        return step, (shards, tx.init(shards), toks, key)

    def zero_shard_llama(cfg, mesh):
        from ddl25spring_tpu.models import llama

        return zero.zero_shard_llama_params(
            llama.init_llama_params(jax.random.PRNGKey(0), cfg), mesh
        )

    def tp_step():
        from ddl25spring_tpu.models import llama

        mesh = make_mesh(devices8[:2], model=2)
        params = tp.shard_tp_params(
            llama.init_llama_params(jax.random.PRNGKey(0), cfg), mesh,
            "model",
        )
        step = tp.make_tp_train_step(cfg, tx, mesh)
        return step, (params, tx.init(params), toks)

    def sp_step():
        from ddl25spring_tpu.models import llama

        mesh = make_mesh(devices8[:2], seq=2)
        params = llama.init_llama_params(jax.random.PRNGKey(0), cfg)
        step = sp.make_sp_train_step(cfg, tx, mesh)
        return step, (params, tx.init(params), toks)

    def ep_step():
        mesh = make_mesh(devices8[:2], expert=2)
        params = ep.shard_moe_params(
            ep.init_moe_params(jax.random.PRNGKey(0), 8, 16, 2), mesh
        )
        step = ep.make_ep_train_step(tx, mesh)
        x = jnp.ones((8, 8), jnp.float32)
        return step, (params, tx.init(params), (x, jnp.zeros_like(x)))

    def pipeline_step():
        from ddl25spring_tpu.models import llama
        from ddl25spring_tpu.parallel.pipeline import (
            make_pipeline_train_step,
            shard_staged_params,
        )

        mesh = make_mesh(devices8[:2], stage=2)
        step = make_pipeline_train_step(cfg, tx, mesh, num_microbatches=2)
        params = shard_staged_params(
            llama.split_blocks_for_stages(
                llama.init_llama_params(jax.random.PRNGKey(0), cfg), 2
            ),
            mesh,
        )
        return step, (params, tx.init(params), toks)

    def het_step():
        mesh = make_mesh(devices8[:2], stage=2)
        params = (
            {"w": jnp.full((8, 16), 0.5)},
            {"w": jnp.full((16, 4), 0.5)},
        )
        step = het_pipeline.make_het_pipeline_train_step(
            [lambda p, x: jnp.tanh(x @ p["w"]),
             lambda p, x: x @ p["w"]],
            lambda out, b: jnp.mean((out - b["y"]) ** 2),
            (2, 8), [(2, 16), (2, 4)], tx, mesh, 2,
        )
        batch = {
            "x": jnp.ones((4, 8), jnp.float32),
            "y": jnp.ones((4, 4), jnp.float32),
        }
        return step, (params, tx.init(params), batch)

    setups = {
        "serial": serial,
        "dp": dp_grad,
        "dp-overlap": dp_overlap,
        "dp-weight-avg": dp_wavg,
        "zero1": zero_stage(1),
        "zero2": zero_stage(2),
        "zero3": zero_stage(3),
        "zero3-prefetch": zero3_llama,
        "zero3-overlap": zero3_overlap,
        "tp": tp_step,
        "sp": sp_step,
        "ep": ep_step,
        "pipeline": pipeline_step,
        "het_pipeline": het_step,
    }
    return setups


def _lowered(devices8, name: str, mode: str) -> str:
    """Lower-once cache over (builder, sentinel-mode) — the shared
    tests/conftest.py memo (one cache for the whole session), applied
    to lowerings."""
    from conftest import cached_lowering

    def build_text():
        build = _builder_setups(devices8)[name]
        ctx = {
            "off": sentinels.scoped(False),
            "default": contextlib.nullcontext(),
            "on": sentinels.scoped(True),
        }[mode]
        with ctx:
            fn, args = build()
        return fn.lower(*args).as_text()

    return cached_lowering(("health-lowered", name, mode), build_text)


def test_every_builder_hlo_identical_when_disabled(devices8):
    """The acceptance pin: sentinels disabled -> byte-identical HLO to a
    sentinel-free build, for EVERY wired builder; enabled -> the guard
    demonstrably lands (catches a builder that forgot to call it)."""
    assert sentinels.enabled() is False
    for name in _builder_setups(devices8):
        off = _lowered(devices8, name, "off")
        on = _lowered(devices8, name, "on")
        assert on != off, f"{name}: enabling sentinels changed nothing"


@pytest.mark.parametrize("name", ["dp", "zero3"])
def test_default_follows_global_flag(devices8, name):
    assert _lowered(devices8, name, "default") == _lowered(
        devices8, name, "off"
    )


def test_sentinels_do_not_serialize_overlapped_collectives(devices8):
    """The PR-8 interaction pin: enabling sentinels on the overlapped
    DP step must not add (or force) any non-scalar collective — the
    guard's facts ride scalar reductions + one host callback, so the
    backward-issued bucket all-reduces keep their overlap structure.
    Compares the OPTIMIZED HLO collective inventories of the on/off
    builds: identical non-scalar sites, and everything the guard added
    is scalar-sized."""
    from ddl25spring_tpu.obs.xla_analytics import parse_hlo_collectives
    from ddl25spring_tpu.parallel import dp

    tx = optax.sgd(0.1)
    p = _mlp_params()
    batch = _mlp_batch()
    key = jax.random.PRNGKey(0)
    mesh2 = make_mesh(devices8[:2], data=2)

    def compiled_ops(on: bool):
        with sentinels.scoped(on):
            step = dp.make_dp_train_step(
                _mlp_loss, tx, mesh2, per_shard_rng=False, overlap=True
            )
        hlo = step.lower(p, tx.init(p), batch, key).compile().as_text()
        return parse_hlo_collectives(hlo)

    def big(ops):
        return sorted(
            (o["kind"], o["result_bytes"], o["count"])
            for o in ops if o["result_bytes"] > 64
        )

    off_ops, on_ops = compiled_ops(False), compiled_ops(True)
    assert big(on_ops) == big(off_ops), (
        "sentinels changed the overlapped step's non-scalar collective "
        "structure — the guard is serializing the bucket all-reduces"
    )


def test_guard_disabled_returns_results_unchanged():
    """Zero-cost by construction: the disabled guard is Python identity
    — the exact object, no tracing, nothing inserted."""
    results = ({"w": jnp.ones(2)}, None)
    out = sentinels.guard("x", results, loss=jnp.float32(1.0),
                          enabled=False)
    assert out is results


# ------------------------------------------------------------- detection


def _run(step, *args):
    out = step(*args)
    jax.block_until_ready(out)
    jax.effects_barrier()
    return out


def test_dp_nan_detected_within_one_step_and_dumped(devices8, tmp_path):
    from ddl25spring_tpu.parallel.dp import make_dp_train_step

    flight.configure(run_dir=str(tmp_path))
    mesh = make_mesh(devices8[:2], data=2)
    tx = optax.sgd(0.1)
    p = _mlp_params()
    with sentinels.scoped(True, policy="log"):
        step = make_dp_train_step(_mlp_loss, tx, mesh, per_shard_rng=False)

    # healthy step: a step record, no violation
    _run(step, p, tx.init(p), _mlp_batch(), jax.random.PRNGKey(0))
    recs = flight.last()
    assert recs and recs[-1]["kind"] == "step"
    assert recs[-1]["strategy"] == "dp"
    assert np.isfinite(recs[-1]["loss"]) and recs[-1]["grad_norm"] > 0
    assert 0 < recs[-1]["update_ratio"] < 1

    # poisoned step: detected in THAT step, leaf named
    _run(step, p, tx.init(p), _mlp_batch(bad=True), jax.random.PRNGKey(0))
    v = [r for r in flight.last() if r["kind"] == "violation"]
    assert len(v) == 1
    v = v[0]
    assert v["strategy"] == "dp" and v["step"] == 1
    assert v["violating_metric"].startswith("grads")
    assert any("w1" in leaf for leaf in v["nonfinite_leaves"])
    assert sentinels.last_violation()["step"] == 1

    # the dump identifies strategy, step index, violating metric
    path = flight.dump(reason="test")
    doc = json.load(open(path))
    assert doc["violations"] == 1
    last = doc["last_violation"]
    assert last["strategy"] == "dp"
    assert last["step"] == 1
    assert last["violating_metric"] == v["violating_metric"]
    assert last["loss"] == "nan"  # JSON-safe encoding of the NaN loss
    assert json.dumps(doc)  # strict JSON round-trips


def test_zero3_nan_detected_once_across_shards(devices8, tmp_path):
    """ZeRO-3's guard sits INSIDE shard_map: facts must arrive globally
    reduced and be recorded once (shard 0), not once per device."""
    from ddl25spring_tpu.parallel import zero

    flight.configure(run_dir=str(tmp_path))
    mesh = make_mesh(devices8[:4], data=4)
    tx = optax.adam(1e-3)
    p = _mlp_params()
    shards = zero.zero_shard_params(p, mesh)
    with sentinels.scoped(True, policy="log"):
        step = zero.make_zero_dp_train_step(
            _mlp_loss, tx, mesh, p, per_shard_rng=False
        )
    _run(step, shards, tx.init(shards), _mlp_batch(bad=True),
         jax.random.PRNGKey(0))
    recs = [r for r in flight.last() if r.get("strategy") == "zero3"]
    assert len(recs) == 1, "per-shard callbacks must collapse to one record"
    assert recs[0]["kind"] == "violation"
    assert recs[0]["nonfinite_leaves"]
    doc = json.load(open(flight.dump()))
    assert doc["last_violation"]["strategy"] == "zero3"


def test_optimizer_nan_detected_in_same_step(devices8):
    """A NaN born in the OPTIMIZER (poisoned Adam moment, finite grads)
    must trip the sentinel in the step that applies it — checking grads
    alone would see it one step late, after skip's fallback is already
    poisoned."""
    from ddl25spring_tpu.parallel.dp import make_dp_train_step

    mesh = make_mesh(devices8[:2], data=2)
    tx = optax.adam(1e-3)
    p = _mlp_params()
    with sentinels.scoped(True, policy="skip"):
        step = make_dp_train_step(_mlp_loss, tx, mesh, per_shard_rng=False)
    o = tx.init(p)
    adam = o[0]
    o = (
        adam._replace(
            mu=dict(adam.mu, w1=adam.mu["w1"].at[0, 0].set(jnp.nan))
        ),
    ) + tuple(o[1:])
    new_p, _, _ = _run(step, p, o, _mlp_batch(), jax.random.PRNGKey(0))
    v = [r for r in flight.last() if r["kind"] == "violation"]
    assert v, "optimizer-made NaN escaped the sentinel"
    assert v[-1]["violating_metric"].startswith("updates")
    assert any("w1" in leaf for leaf in v[-1]["nonfinite_leaves"])
    # skip still protected the params in the SAME step
    assert all(
        np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(new_p)
    )


# -------------------------------------------------------------- policies


def test_skip_policy_suppresses_update_on_device(devices8):
    from ddl25spring_tpu.parallel.dp import make_dp_train_step

    mesh = make_mesh(devices8[:2], data=2)
    tx = optax.sgd(0.1)
    p = _mlp_params()
    with sentinels.scoped(True, policy="skip"):
        step = make_dp_train_step(_mlp_loss, tx, mesh, per_shard_rng=False)
    bad_p, _, _ = _run(
        step, p, tx.init(p), _mlp_batch(bad=True), jax.random.PRNGKey(0)
    )
    np.testing.assert_array_equal(np.asarray(bad_p["w1"]),
                                  np.asarray(p["w1"]))
    good_p, _, _ = _run(
        step, p, tx.init(p), _mlp_batch(), jax.random.PRNGKey(0)
    )
    assert not np.array_equal(np.asarray(good_p["w1"]),
                              np.asarray(p["w1"]))


_HALT_SCRIPT = r"""
import os, sys
os.environ["DDL25_DONATE"] = "0"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp, optax
from ddl25spring_tpu.obs import flight, sentinels
from ddl25spring_tpu.parallel.dp import make_dp_train_step
from ddl25spring_tpu.utils.mesh import make_mesh

flight.configure(run_dir=sys.argv[1])
mesh = make_mesh(jax.devices()[:2], data=2)
tx = optax.sgd(0.1)
p = {"w1": jnp.full((8, 16), 0.5), "w2": jnp.full((16, 4), 0.5)}
def loss_fn(pp, batch, key):
    x, y = batch
    return jnp.mean((jnp.tanh(x @ pp["w1"]) @ pp["w2"] - y) ** 2)
with sentinels.scoped(True, policy="halt"):
    step = make_dp_train_step(loss_fn, tx, mesh, per_shard_rng=False)
x = jnp.ones((8, 8)).at[0, 0].set(jnp.nan)
try:
    out = step(p, tx.init(p), (x, jnp.ones((8, 4))), jax.random.PRNGKey(0))
    jax.block_until_ready(out)
    jax.effects_barrier()
    print("MARKER:no-raise")
except Exception as e:
    print("MARKER:raised", type(e).__name__)
    print("MARKER:msg", str(e).replace("\n", " "))
ctx = sentinels.last_violation()
print("MARKER:ctx", ctx["strategy"], ctx["step"], ctx["violating_metric"])
os._exit(0)  # the poisoned dispatch stream would trip atexit otherwise
"""


def test_halt_policy_raises_with_flight_context(tmp_path):
    """Halt must surface the flight-record context — strategy, step,
    offending leaf, dump path — not a bare FloatingPointError.  The
    runtime may wrap the raise in its own error type (async dispatch:
    the exception surfaces at the next blocking point, see the
    sentinels module docstring).  Run in a SUBPROCESS: halt is a
    terminal policy — the raise leaves the backend's dispatch stream
    errored (observed on the CPU runtime: every later multi-device
    dispatch in the process inherits the failure), which is fine for a
    run that is dying on purpose but must not poison this suite."""
    import subprocess

    r = subprocess.run(
        [sys.executable, "-c", _HALT_SCRIPT, str(tmp_path)],
        capture_output=True, text=True, timeout=300,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    out = r.stdout
    assert "MARKER:raised" in out, (out, r.stderr[-2000:])
    assert "MARKER:no-raise" not in out
    assert "sentinel violation" in out
    assert "MARKER:ctx dp 0 grads" in out
    # the dump happened BEFORE the raise
    doc = json.load(open(os.path.join(str(tmp_path), "flight.json")))
    assert doc["reason"] == "sentinel_halt"
    assert doc["last_violation"]["strategy"] == "dp"
    assert doc["last_violation"]["violating_metric"].startswith("grads")


def test_policy_resolution_and_env_choice():
    with sentinels.scoped(True, policy="skip"):
        assert sentinels.resolve(None) == (True, "skip")
        assert sentinels.resolve(False) == (False, "skip")
        assert sentinels.resolve(None, "halt") == (True, "halt")
    assert sentinels.resolve(None) == (False, "log")
    with pytest.raises(ValueError, match="not one of"):
        sentinels.set_policy("explode")
    from ddl25spring_tpu.utils.config import env_choice

    os.environ["DDL25_TEST_CHOICE"] = "bogus"
    try:
        with pytest.raises(ValueError, match="bogus"):
            env_choice("DDL25_TEST_CHOICE", ("a", "b"), "a")
        os.environ["DDL25_TEST_CHOICE"] = "b"
        assert env_choice("DDL25_TEST_CHOICE", ("a", "b"), "a") == "b"
    finally:
        del os.environ["DDL25_TEST_CHOICE"]


# ------------------------------------------------------- flight recorder


def test_flight_ring_truncates_and_snapshot_counts(tmp_path):
    flight.configure(capacity=8)
    try:
        # one violation FIRST, then enough steps to evict it: the
        # cumulative count (and the --check-health gate riding on it)
        # must survive ring eviction
        flight.record(kind="violation", strategy="dp", step=0,
                      violating_metric="loss", violation=True)
        for i in range(20):
            flight.record(kind="step", step=i)
        snap = flight.snapshot()
        assert snap["recorded"] == 21
        assert len(snap["records"]) == 8
        assert [r["step"] for r in snap["records"]] == list(range(12, 20))
        assert all(r["kind"] == "step" for r in snap["records"])
        assert snap["violations"] == 1
        doc = json.load(open(flight.dump(path=str(tmp_path / "f.json"))))
        assert doc["violations"] == 1
        assert doc["last_violation"]["violating_metric"] == "loss"
    finally:
        flight.configure(capacity=256)


def test_flight_dump_is_atomic_and_json_safe(tmp_path):
    # foreign scalar types land in records/meta in practice (numpy
    # losses, jax ints) — a CRASH dump must encode them, never raise
    flight.annotate(layout="dp", rng_seed=20,
                    h2d=np.float32(3.5), weird=object())
    flight.record(kind="step", loss=float("nan"),
                  grad_norm=float("inf"), npnan=np.float32("nan"), step=0)
    path = flight.dump(path=str(tmp_path / "flight.json"), reason="manual")
    raw = open(path).read()
    doc = json.loads(raw)  # strict: would reject bare NaN tokens
    assert doc["meta"]["layout"] == "dp" and doc["meta"]["rng_seed"] == 20
    assert doc["meta"]["h2d"] == 3.5
    assert isinstance(doc["meta"]["weird"], str)
    assert doc["records"][0]["loss"] == "nan"
    assert doc["records"][0]["grad_norm"] == "inf"
    assert doc["records"][0]["npnan"] == "nan"
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]


def test_configure_none_clears_run_dir(tmp_path, monkeypatch):
    """run_dir=None must CLEAR a previously-set dir (back to the env
    default) — or a stale test/run dir leaks into every later dump."""
    flight.configure(run_dir=str(tmp_path / "a"))
    flight.record(kind="step", step=0)
    monkeypatch.setenv("DDL25_FLIGHT_DIR", str(tmp_path / "dflt"))
    flight.configure(run_dir=None)
    p = flight.dump(reason="manual")
    assert p == os.path.join(str(tmp_path / "dflt"), "flight.json")
    flight.configure()  # no args: leaves the (cleared) dir untouched
    assert flight.dump(reason="manual") == p


def test_sigterm_handler_preserves_sig_ign(tmp_path, monkeypatch):
    """A process that chose to IGNORE SIGTERM must keep ignoring it
    after install(): the handler dumps and returns, never exits."""
    import signal

    exits = []
    monkeypatch.setattr(os, "_exit", lambda code: exits.append(code))
    prev = signal.signal(signal.SIGTERM, signal.SIG_IGN)
    try:
        flight.configure(run_dir=str(tmp_path))
        flight.install()
        flight.record(kind="step", step=0)
        handler = signal.getsignal(signal.SIGTERM)
        assert callable(handler)
        handler(signal.SIGTERM, None)  # simulated delivery
        assert exits == [], "SIG_IGN process must not be killed"
        doc = json.load(open(tmp_path / "flight.json"))
        assert doc["reason"] == "sigterm"
    finally:
        flight.uninstall()
        signal.signal(signal.SIGTERM, prev)


def test_flight_excepthook_dumps_and_chains(tmp_path):
    seen = []
    prev_hook = sys.excepthook
    sys.excepthook = lambda *a: seen.append(a)
    try:
        flight.configure(run_dir=str(tmp_path))
        flight.install()
        assert sys.excepthook is not prev_hook
        flight.record(kind="step", step=0)
        try:
            raise RuntimeError("boom")
        except RuntimeError:
            sys.excepthook(*sys.exc_info())
        doc = json.load(open(tmp_path / "flight.json"))
        assert doc["reason"] == "unhandled_exception"
        assert "boom" in doc["exception"]
        assert seen, "previous excepthook must still run"
    finally:
        flight.uninstall()
        sys.excepthook = prev_hook


# --------------------------------------------------------------- watchdog


def test_watchdog_dump_carries_thread_stacks(tmp_path):
    """The r01–r05 acceptance pin: a stalled step fires the watchdog,
    whose dump names every host thread's blocking frame — including the
    artificially wedged worker's sleep."""
    release = threading.Event()

    def wedged_worker():
        release.wait(10.0)

    t = threading.Thread(
        target=wedged_worker, name="wedged-worker", daemon=True
    )
    t.start()
    wd = StallWatchdog(
        deadline_s=0.25, run_dir=str(tmp_path), name="unit", source="self"
    )
    with wd:
        deadline = time.monotonic() + 5.0
        while not wd.fired and time.monotonic() < deadline:
            time.sleep(0.05)
    release.set()
    assert wd.fired and wd.dump_path
    doc = json.load(open(wd.dump_path))
    assert doc["reason"] == "stall"
    assert doc["stall"]["watchdog"] == "unit"
    assert doc["stall"]["deadline_s"] == 0.25
    stacks = doc["thread_stacks"]
    wedged = [v for k, v in stacks.items() if "wedged-worker" in k]
    assert wedged, f"wedged thread missing from {sorted(stacks)}"
    assert any("wedged_worker" in frame for frame in wedged[0])
    # a LATER dump (end_of_run / atexit) must not erase the stall fact:
    # the ring-derived summary keeps the --check-health gate honest
    doc2 = json.load(open(flight.dump(reason="end_of_run")))
    assert doc2["reason"] == "end_of_run"
    assert doc2["stalls"] == 1
    assert doc2["stall"]["watchdog"] == "unit"


def test_watchdog_beat_rearms_and_flight_source():
    wd = StallWatchdog(deadline_s=0.2, name="beaten", poll_s=0.05)
    with wd:
        for _ in range(8):  # flight activity counts as progress
            flight.beat()
            time.sleep(0.05)
        assert not wd.fired
        time.sleep(0.6)
        assert wd.fired
        flight.beat()
        wd.beat()
        assert not wd.fired  # re-armed


def test_watchdog_restartable_after_stop(tmp_path):
    """stop() then start() must yield a LIVE monitor — a silently dead
    watchdog is the one failure mode this class may never have."""
    wd = StallWatchdog(deadline_s=0.2, run_dir=str(tmp_path),
                       name="restart", source="self", poll_s=0.05)
    with wd:
        time.sleep(0.05)
    assert not wd.fired
    with wd:  # second use of the same instance
        deadline = time.monotonic() + 5.0
        while not wd.fired and time.monotonic() < deadline:
            time.sleep(0.05)
    assert wd.fired, "restarted watchdog never fired"


def test_thread_stacks_sees_this_thread():
    stacks = thread_stacks()
    mine = [v for k, v in stacks.items() if "MainThread" in k]
    assert mine and any("test_thread_stacks" in f for f in mine[0])


# --------------------------------- bench driver + report integration


def test_bench_classify_failure_reason_codes():
    import bench

    assert bench.classify_failure(
        "accelerator unreachable: device init timed out after 240s"
    ) == "device_unreachable"
    assert bench.classify_failure(
        "RuntimeError: UNAVAILABLE: connection closed"
    ) == "device_unreachable"
    assert bench.classify_failure(
        "attempt 2: bench subprocess exceeded 2400s and was killed"
    ) == "stalled"
    assert bench.classify_failure(
        "XlaRuntimeError: INTERNAL: Mosaic compilation failed"
    ) == "compile_error"
    assert bench.classify_failure("ValueError: batch 7 not divisible") \
        == "runtime_error"
    assert bench.classify_failure(None) == "runtime_error"


def test_bench_health_rides_the_dead_line():
    import bench

    rec = {"metric": "m", "value": 0.0,
           "error": "accelerator unreachable: device init timed out",
           "flight_dump": "runs/x/flight.json"}
    failures = [{"record": "bench_retry_failure", "attempt": 1,
                 "error": "device init timed out",
                 "reason": "device_unreachable",
                 "flight_dump": "runs/x/flight.json",
                 "backoff_s": 0.0, "wall_s": 1.0, "rc": None}]
    out = bench.attach_parent_telemetry(rec, failures, None)
    h = out["telemetry"]["health"]
    assert h["flight_dump"] == "runs/x/flight.json"
    assert h["reason"] == "device_unreachable"
    assert out["telemetry"]["retry_failures"][0]["reason"] == (
        "device_unreachable"
    )


def _mini_run_dir(tmp_path, with_violation: bool):
    run = tmp_path / "run"
    run.mkdir(parents=True)
    with open(run / "metrics.jsonl", "w") as f:
        f.write(json.dumps({"record": "header", "n_chips": 1}) + "\n")
        f.write(json.dumps(
            {"record": "step", "step": 0, "wall_s": 0.1, "label": "x"}
        ) + "\n")
    flight.reset()
    flight.record(kind="step", strategy="dp", step=0, loss=1.0)
    if with_violation:
        flight.record(kind="violation", strategy="dp", step=1,
                      violating_metric="loss", violation=True)
    flight.dump(path=str(run / "flight.json"), reason="test")
    return str(run)


def test_report_health_section_and_check_health(tmp_path):
    from ddl25spring_tpu.obs.report import format_report, summarize_run
    from tools.obs_report import main as report_main

    run = _mini_run_dir(tmp_path, with_violation=True)
    s = summarize_run(run)
    assert s["health"]["violations"] == 1
    assert s["health"]["last_violation"]["strategy"] == "dp"
    text = format_report(s)
    assert "health (flight.json" in text
    assert "sentinel violations: 1" in text
    assert "last violation: strategy=dp" in text

    # --check-health: violations -> rc 3; clean run -> rc 0
    assert report_main([run, "--check-health"]) == 3
    clean = _mini_run_dir(tmp_path / "c", with_violation=False)
    assert report_main([clean, "--check-health"]) == 0
    assert report_main([clean]) == 0  # no flag: report only


def test_tools_import_path_for_obs_report(tmp_path):
    """tools/obs_report.py is also runnable as a script; its module
    import above must not have shadowed the package."""
    import tools.obs_report as m

    assert callable(m.main)
