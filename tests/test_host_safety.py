"""graft-race (``analysis/host_safety.py`` + ``analysis/host_sanitizer.py``
+ ``tools/graft_lint.py --host-safety``): the host-side concurrency &
signal-safety verifier.

The load-bearing pins:

- **one positive + one near-miss per rule S201–S205** — each synthetic
  source distills the real hazard the rule was built from (PR-5's
  signal-path self-deadlock, PR-6's shutdown wedge, PR-10/17's mirror
  drift) and its minimally-fixed twin stays quiet.
- **the static finding fires live** — a seeded S204 drift (device
  refcount bumped with no host billing) trips the runtime sanitizer's
  mirror assertion through the engine's own ``step()`` hook, and the
  lock-order proxy raises on a would-be self-deadlock / inversion
  *before* blocking.
- **zero cost when off** — with ``DDL25_SANITIZE=0`` token streams are
  bitwise identical and the decode tick lowers to byte-identical HLO;
  the sanitizer is host-side observation only.
- **the repo's own host surface is clean** — ``lint_repo`` over
  obs/ft/serve/bench/tools returns no findings (the PR-19 dogfood
  fixes hold), and the inventory sees the declared locks and entries.
"""

from __future__ import annotations

import textwrap
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from ddl25spring_tpu.analysis import host_safety, host_sanitizer
from ddl25spring_tpu.analysis.host_sanitizer import (
    OrderCheckedLock,
    SanitizerError,
    wrap_lock,
)
from ddl25spring_tpu.models import llama
from ddl25spring_tpu.serve import kv_pages
from ddl25spring_tpu.serve.engine import ServeEngine
from ddl25spring_tpu.utils.config import LlamaConfig

CFG = LlamaConfig(
    vocab_size=64, dmodel=16, num_heads=2, n_layers=2, ctx_size=32,
    dtype="float32",
)


@pytest.fixture(scope="module")
def params():
    return llama.init_llama_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(autouse=True)
def _clean_sanitizer():
    host_sanitizer.reset()
    yield
    host_sanitizer.reset()


def make_engine(params, **kw):
    # the test_serve smoke geometry — every compiled program rides the
    # session-wide program cache shared with tests/test_serve.py
    kw.setdefault("page_len", 4)
    kw.setdefault("n_pages", 16)
    kw.setdefault("max_slots", 2)
    kw.setdefault("pages_per_seq", 4)
    kw.setdefault("prefill_batch", 1)
    kw.setdefault("max_prompt_len", 8)
    kw.setdefault("clock", "virtual")
    return ServeEngine(params, CFG, **kw)


def drain(eng, max_steps: int = 500):
    steps = 0
    while eng.queue or any(s is not None for s in eng.slots):
        eng.step()
        steps += 1
        assert steps < max_steps, "engine failed to drain"


def lint(src: str, relpath: str = "ddl25spring_tpu/obs/fake.py",
         mirrors=host_safety.MIRRORS):
    return host_safety.lint_source(
        textwrap.dedent(src), relpath, mirrors=mirrors
    )


# --------------------------------------------- S201: cross-context write


S201_BAD = """
    import threading

    class Watch:
        def __init__(self):
            self.fired = False
            self._t = threading.Thread(target=self._monitor, daemon=True)

        def beat(self):
            self.fired = False

        def _monitor(self):
            self.fired = True
"""


def test_s201_unlocked_cross_context_write_fires():
    findings = lint(S201_BAD)
    assert [f.rule for f in findings] == ["S201"]
    (f,) = findings
    assert f.op == "Watch.fired"
    assert "thread:Watch._monitor" in f.message and "main" in f.message


def test_s201_near_miss_shared_lock_stays_quiet():
    src = """
        import threading

        class Watch:
            def __init__(self):
                self.fired = False
                self._lock = threading.Lock()
                self._t = threading.Thread(
                    target=self._monitor, daemon=True)

            def beat(self):
                with self._lock:
                    self.fired = False

            def _monitor(self):
                with self._lock:
                    self.fired = True
    """
    assert lint(src) == []


def test_s201_init_writes_are_exempt():
    # __init__ publishes before the thread starts — construction
    # happens-before; only the thread writes after that
    src = """
        import threading

        class Watch:
            def __init__(self):
                self.fired = False
                self._t = threading.Thread(
                    target=self._monitor, daemon=True)

            def _monitor(self):
                self.fired = True
    """
    assert lint(src) == []


# ------------------------------------------- S202: lock-order inversion


S202_BAD = """
    import threading

    class Pair:
        def __init__(self):
            self._lock_a = threading.Lock()
            self._lock_b = threading.Lock()

        def forward(self):
            with self._lock_a:
                with self._lock_b:
                    self.n = 1

        def backward(self):
            with self._lock_b:
                with self._lock_a:
                    self.n = 2
"""


def test_s202_opposite_nesting_orders_fire():
    findings = lint(S202_BAD)
    assert [f.rule for f in findings] == ["S202"]
    (f,) = findings
    assert "Pair._lock_a" in f.op and "Pair._lock_b" in f.op


def test_s202_near_miss_consistent_order_stays_quiet():
    src = """
        import threading

        class Pair:
            def __init__(self):
                self._lock_a = threading.Lock()
                self._lock_b = threading.Lock()

            def forward(self):
                with self._lock_a:
                    with self._lock_b:
                        self.n = 1

            def backward(self):
                with self._lock_a:
                    with self._lock_b:
                        self.n = 2
    """
    assert lint(src) == []


# ----------------------------------- S203: signal-path non-reentrancy


S203_BAD = """
    import signal
    import threading

    class Reporter:
        def __init__(self):
            self._lock = threading.Lock()

        def install(self):
            signal.signal(signal.SIGTERM, self._on_term)

        def _on_term(self, signum, frame):
            self.dump()

        def dump(self):
            with self._lock:
                self.count = 1
"""


def test_s203_nonreentrant_lock_on_signal_path_fires():
    findings = lint(S203_BAD)
    assert [f.rule for f in findings] == ["S203"]
    (f,) = findings
    assert f.op == "Reporter.dump"
    assert "signal:Reporter._on_term" in f.message


def test_s203_near_miss_rlock_stays_quiet():
    # the PR-5 fix verbatim: the lock the handler path re-enters is
    # declared reentrant
    assert lint(S203_BAD.replace("threading.Lock()",
                                 "threading.RLock()")) == []


# --------------------------------------- S204: host<->device mirror drift


_S204_MIRRORS = (
    {
        "path": "ddl25spring_tpu/serve/fake_engine.py",
        "cls": "FakeEngine",
        "device_state": ("pool",),
        "device_ops": ("_ref",),
        "host_mirrors": ("_reserved",),
    },
)

S204_BAD = """
    class FakeEngine:
        def adopt(self, pages):
            self.pool = _ref(self.pool, pages)
"""


def test_s204_unmirrored_device_mutation_fires():
    findings = lint(S204_BAD, "ddl25spring_tpu/serve/fake_engine.py",
                    mirrors=_S204_MIRRORS)
    assert [f.rule for f in findings] == ["S204"]
    (f,) = findings
    assert f.op == "FakeEngine.adopt"
    assert "self.pool" in f.message and "_ref" in f.message


def test_s204_near_miss_same_method_mirror_write_stays_quiet():
    src = """
        class FakeEngine:
            def adopt(self, pages):
                self.pool = _ref(self.pool, pages)
                self._reserved += len(pages)
    """
    assert lint(src, "ddl25spring_tpu/serve/fake_engine.py",
                mirrors=_S204_MIRRORS) == []


# ------------------------------- S205: unbounded blocking on shutdown


S205_BAD = """
    import atexit

    class Saver:
        def install(self):
            atexit.register(self.close)

        def close(self):
            self.worker.join()
"""


def test_s205_unbounded_join_on_shutdown_path_fires():
    findings = lint(S205_BAD)
    assert [f.rule for f in findings] == ["S205"]
    (f,) = findings
    assert f.severity == "warn" and f.op == "Saver.close"
    assert "atexit:Saver.close" in f.message


def test_s205_near_miss_bounded_join_stays_quiet():
    assert lint(S205_BAD.replace(".join()", ".join(timeout=2.0)")) == []


# ----------------------------------- the repo's own host surface (gate)


def test_repo_host_surface_lints_clean():
    """The PR-19 dogfood state, pinned: after the watchdog/autosave/
    engine fixes the whole host scope passes with zero findings and
    zero waivers, and the inventory sees the machinery we know exists."""
    root = Path(__file__).resolve().parents[1]
    inv, findings = host_safety.lint_repo(str(root))
    assert findings == [], [
        f"{f.rule} {f.source} {f.op}" for f in findings
    ]
    s = inv.summary()
    assert s["files"] >= 30 and s["functions"] >= 300
    locks = s["locks"]
    assert locks[
        "ddl25spring_tpu/obs/recorder.py::FlightRecorder._lock"
    ] == "RLock"  # the PR-5 signal-path fix, still reentrant
    assert locks[
        "ddl25spring_tpu/obs/watchdog.py::StallWatchdog._state_lock"
    ] == "Lock"  # this PR's S201 fix: never held across dump
    assert locks[
        "ddl25spring_tpu/ft/autosave.py::AutoSaver._state_lock"
    ] == "RLock"  # this PR's S201 fix, reentrant because signal-reachable
    entries = s["entry_points"]
    assert entries.get("thread", 0) >= 1
    assert entries.get("signal", 0) >= 1
    assert entries.get("atexit", 0) >= 1
    assert s["mirror_contracts"] == 1


# ------------------------------------------ runtime: lock-order proxy


def test_sanitizer_self_deadlock_raises_before_blocking():
    lk = OrderCheckedLock("t.lock", threading.Lock())
    with lk:
        with pytest.raises(SanitizerError, match="self-deadlock"):
            lk.acquire()  # a plain Lock would hang here forever
    assert [v["kind"] for v in host_sanitizer.violations()] == [
        "self_deadlock"
    ]
    with lk:  # released cleanly; usable after the report
        pass


def test_sanitizer_rlock_reentry_is_fine():
    rl = OrderCheckedLock("t.rlock", threading.RLock())
    with rl:
        with rl:
            pass
    assert host_sanitizer.violations() == []


def test_sanitizer_lock_order_inversion_raises():
    a = OrderCheckedLock("t.a", threading.Lock())
    b = OrderCheckedLock("t.b", threading.Lock())
    with a:
        with b:  # records the edge a -> b
            pass
    with b:
        with pytest.raises(SanitizerError, match="inversion"):
            a.acquire()  # b -> a inverts the recorded order
    v = host_sanitizer.violations()
    assert [x["kind"] for x in v] == ["lock_order_inversion"]
    assert v[0]["held"] == "t.b" and v[0]["acquiring"] == "t.a"


def test_wrap_lock_resolves_flag_at_construction(monkeypatch):
    raw = threading.Lock()
    monkeypatch.setenv("DDL25_SANITIZE", "0")
    assert wrap_lock("t.x", raw) is raw
    monkeypatch.setenv("DDL25_SANITIZE", "1")
    wrapped = wrap_lock("t.x", raw)
    assert isinstance(wrapped, OrderCheckedLock)
    assert wrapped._inner is raw


# ----------------------------- runtime: the S204 mirror assertion, live


def test_sanitized_engine_drains_clean_then_catches_seeded_drift(
    params, monkeypatch
):
    """The dynamic half of S204: a real serve drain passes the mirror
    check at every step boundary, then a seeded drift — one device
    refcount bumped with no host billing, exactly the class the static
    rule flags — trips ``step()``'s own assertion."""
    import numpy as np

    monkeypatch.setenv("DDL25_SANITIZE", "1")
    eng = make_engine(params)
    assert eng._sanitize is True
    assert eng.submit(eng.make_request([5, 9, 11, 3], 4)) is None
    drain(eng)
    assert host_sanitizer.violations() == []

    free = np.asarray(jax.device_get(eng.pool["free"])).astype(bool)
    pid = int(np.argmax(free))
    assert free[pid], "no free page to corrupt"
    eng.pool = kv_pages.ref_pages(
        eng.pool, jnp.asarray([pid], jnp.int32)
    )
    with pytest.raises(SanitizerError, match="mirror drift"):
        eng.step()
    assert host_sanitizer.violations()[-1]["kind"] == "mirror_drift"


# --------------------------------------------------- zero cost when off


def test_tokens_bitwise_identical_with_sanitizer_toggled(
    params, monkeypatch
):
    """DDL25_SANITIZE on/off leaves token streams and the virtual clock
    bitwise unchanged — the mirror check observes, never steers."""

    def run(flag: str):
        monkeypatch.setenv("DDL25_SANITIZE", flag)
        host_sanitizer.reset()
        eng = make_engine(params, prefill_batch=2)
        reqs = [
            eng.make_request([5 + i, 9, 11, 3], 6) for i in range(3)
        ]
        for r in reqs:
            assert eng.submit(r) is None
        drain(eng)
        return [r.tokens for r in reqs], eng.now(), eng._vtime

    off_tokens, off_now, off_vt = run("0")
    on_tokens, on_now, on_vt = run("1")
    assert on_tokens == off_tokens
    assert on_now == off_now and on_vt == off_vt


def test_decode_tick_hlo_identical_with_sanitizer_toggled(
    params, monkeypatch
):
    """The sanitizer never enters a compiled program: the decode tick
    lowers to byte-identical HLO with the flag on or off."""
    from ddl25spring_tpu.serve.engine import make_decode_tick

    pool = kv_pages.init_page_pool(
        CFG, n_pages=16, page_len=4, max_slots=2, pages_per_seq=4,
    )
    args = (params, pool, jax.random.PRNGKey(0))

    def lower(flag: str):
        monkeypatch.setenv("DDL25_SANITIZE", flag)
        tick = make_decode_tick(CFG, temperature=0.0, sentinel=False)
        return jax.jit(tick).lower(*args).as_text()

    assert lower("1") == lower("0")
