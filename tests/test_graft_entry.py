"""Driver-contract tests for __graft_entry__.py."""

import os
import subprocess
import sys

import jax
import pytest

import __graft_entry__

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_entry_compiles_and_runs():
    fn, args = __graft_entry__.entry()
    loss = float(jax.jit(fn)(*args))
    assert loss == loss and loss > 0  # finite, positive


@pytest.fixture(scope="module")
def dryrun():
    """Simulate the driver: run dryrun_multichip ONCE in a fresh interpreter
    WITHOUT conftest's platform forcing — dryrun_multichip itself must
    select the CPU platform.  A strict superset of an in-process dryrun
    call, which it replaces to keep the suite from paying it twice."""
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "XLA_FLAGS")
    }
    # 32 workloads, 7-8 minutes of cold compiles beside five busy workers
    # (less when the persistent cache dryrun_multichip enables is warm);
    # the limit is the test's own
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "__graft_entry__.py")],
        capture_output=True, text=True, timeout=1200, env=env, cwd=REPO,
    )


def test_dryrun_multichip_fresh_subprocess(dryrun):
    assert dryrun.returncode == 0, (
        f"dryrun_multichip subprocess failed:\n"
        f"stdout:\n{dryrun.stdout}\nstderr:\n{dryrun.stderr}"
    )


# every leg of the dryrun, by the line it prints when its loss is finite
# and its equality held.  One case each: a leg that silently stopped
# running is a failure here, not a shorter log.  (It also makes this the
# file with many tests that ``--dist loadfile`` starts early — xdist orders
# files by test count — so the one long subprocess overlaps the rest.)
DRYRUN_LEGS = [
    "DPxPP", "1F1B DPxPP", "interleaved DPxPP", "interleaved-1F1B DPxPP",
    "DPxPPxTP", "1F1B DPxPPxTP", "PPxSPxTP", "TPxDP", "TP decode",
    "ZeRO-DP", "SPxDP", "SPxDP ring-flash", "SPxDP ulysses", "SPxPP",
    "SPxPP [1f1b]", "EP MoE", "EPxDP", "EPxDPxPP", "EPxDPxPP [1f1b]",
    "EPxDPxPP [interleaved]", "EPxDPxPP [interleaved-1f1b]", "MoE TPxDP",
    "MoE DPxPPxTP", "MoE LLaMA DP", "MoE LLaMA DPxPP [gpipe]",
    "MoE LLaMA DPxPP [1f1b]", "ResNet het-PP DPxPP", "ResNet het-PP 2x3",
    "hybrid DCN mesh", "b1 microbatch grad-accum", "FedAvg", "vertical FL",
]


@pytest.mark.parametrize("leg", DRYRUN_LEGS)
def test_dryrun_leg_reports_ok(dryrun, leg):
    assert f"dryrun_multichip {leg} OK:" in dryrun.stdout
