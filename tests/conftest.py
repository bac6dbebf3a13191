"""Test harness: simulate an 8-device TPU mesh on CPU.

The TPU-world analogue of the reference's gloo-on-localhost fake cluster
(SURVEY §4): ``--xla_force_host_platform_device_count=8`` gives every test a
multi-device mesh without hardware.  XLA_FLAGS must be set before the CPU
backend initializes.  The driver's command selects the backend from the
environment (``JAX_PLATFORMS=cpu``); the ``jax.config`` line below makes a
bare ``pytest`` on a machine with a chip a CPU run too.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Buffer donation is ON by default in every train-step builder
# (parallel/dp.donate_argnums), which (correctly) invalidates the input
# trees after a call.  The equivalence-oracle tests feed one params tree
# through several independent steps, so the suite opts out here; the
# donation contract itself is pinned explicitly (donate=True) in
# tests/test_bucketing.py and through every describe() hook in
# tests/test_xla_analytics.py.
os.environ.setdefault("DDL25_DONATE", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, "conftest failed to fake 8 CPU devices"
    return devs[:8]


# ----------------------------------------------- lower-once compile caches
#
# Compiles are the suite's wall-clock budget.  Every test that needs a
# registered strategy's compile-time report MUST ride this session cache
# — one compile per strategy per test session, shared across
# test_xla_analytics (signature pins), test_hlo_lint (clean baselines),
# and test_sched (overlap-bound pins).  The generic `lower_once` memo is
# the same pattern for ad-hoc lowerings (test_health's sentinel-mode
# HLO texts).

_strategy_reports: dict = {}
_lowered_once: dict = {}


def cached_strategy_report(name: str) -> dict:
    """Compile + analyze one registered strategy, once per session.
    ``keep_hlo=True``: the report carries the optimized-HLO text, so the
    bitwise rule-table pins and the sharding-flow walks
    (test_shard_flow.py) reuse this one compile instead of paying their
    own."""
    from ddl25spring_tpu.obs import xla_analytics as xa

    if name not in _strategy_reports:
        _strategy_reports[name] = xa.compile_strategy(name, keep_hlo=True)
    r = _strategy_reports[name]
    assert "error" not in r, f"{name} failed to compile: {r.get('error')}"
    return r


@pytest.fixture(scope="session")
def strategy_report():
    """The shared compile-once cache, as a fixture: tests call
    ``strategy_report(name)`` and share one ``compile_strategy`` result
    per strategy across every test module in the session."""
    return cached_strategy_report


def cached_lowering(key, build):
    """Generic memoized-lowering cache: runs ``build()`` on first use of
    ``key`` and replays the result after — for expensive lowerings that
    aren't registry strategies (e.g. the sentinel-mode HLO texts in
    test_health)."""
    if key not in _lowered_once:
        _lowered_once[key] = build()
    return _lowered_once[key]


@pytest.fixture(scope="session")
def lower_once():
    """:func:`cached_lowering`, as a fixture."""
    return cached_lowering
