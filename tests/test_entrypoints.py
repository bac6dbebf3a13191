"""The entry points' contracts toward a chip (CPU, tiny, first rehearsal of
the ``on-chip-measurement`` guide §2): what must FAIL fails with a non-zero
exit, the relaunch parent leaves the chip to its children, the compile
cache can be placed from outside, and the strict native build does not
fall back.  Every subprocess carries its own time limit."""

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**over):
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "DDL25_CHAOS", "DDL25_BENCH_CHILD",
                     "JAX_COMPILATION_CACHE_DIR")
    }
    env.update(over)
    return env


def _run(argv, env, timeout=240):
    return subprocess.run(
        [sys.executable, *argv], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=timeout,
    )


def test_chip_smoke_refuses_to_pass_off_tpu():
    """No accelerator: non-zero exit and no result line — the script proves
    the chip path and has no CPU fallback."""
    r = _run(["chip_smoke.py"], _env(JAX_PLATFORMS="cpu"))
    assert r.returncode != 0, r.stdout[-500:]
    assert '"ok": true' not in r.stdout
    assert "no TPU here" in r.stderr


# a backend that cannot come up, found in a second: the cheapest failing
# run there is (no compile, no data)
_NO_BACKEND = {"JAX_PLATFORMS": "no_such_platform"}
_QUICK = ["bench.py", "--no-compile-report", "--no-fedavg"]


@pytest.mark.parametrize("argv,children", [
    # a plain run is ONE process: it fails itself
    ([], 0),
    # a resilient run's parent relaunches, and after its last child
    # failed it fails too — never exit 0 with a value of 0.0
    (["--save-every", "2", "--attempts", "2"], 2),
], ids=["plain", "resilient"])
def test_bench_run_that_fails_exits_nonzero(argv, children, tmp_path):
    r = _run(
        _QUICK + argv + ["--ckpt-dir", str(tmp_path / "ckpt")],
        _env(**_NO_BACKEND),
    )
    assert r.returncode != 0, r.stdout[-500:]
    line = json.loads([ln for ln in r.stdout.splitlines() if ln.strip()][-1])
    assert "unreachable" in line["error"] and line["value"] == 0.0
    assert "dppp" not in line["metric"]  # nothing ran in any layout
    retries = [
        json.loads(ln) for ln in r.stderr.splitlines()
        if ln.startswith('{"record": "bench_retry_failure"')
    ]
    assert len(retries) == children
    assert all(f["backoff_s"] == 0.0 for f in retries)


_HOOK = '''
import os
import jax
from jax._src import xla_bridge

def _forbidden(*a, **k):
    who = "child" if os.environ.get("DDL25_BENCH_CHILD") == "1" else "PARENT"
    raise RuntimeError("backend init in the " + who)

xla_bridge.backends = _forbidden
'''


def test_bench_parent_initialises_no_backend(tmp_path):
    """A chip belongs to one process: the relaunch parent must reach its
    ``Popen`` without having touched a backend.  Every interpreter here
    starts with a hook that makes backend init raise and say who asked —
    the children may (and do, and die of it); the parent must not."""
    (tmp_path / "sitecustomize.py").write_text(_HOOK)
    r = _run(
        _QUICK + ["--cpu", "--save-every", "2", "--attempts", "1",
                  "--ckpt-dir", str(tmp_path / "ckpt")],
        _env(PYTHONPATH=f"{tmp_path}{os.pathsep}{REPO}", JAX_PLATFORMS="cpu"),
    )
    out = r.stdout + r.stderr
    assert "backend init in the child" in out, out[-2000:]
    assert "backend init in the PARENT" not in out
    assert r.returncode == 1  # ...and the child's failure is the parent's


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    from ddl25spring_tpu.utils.platform import enable_compilation_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compilation_cache() == str(tmp_path)
        # ...and no other directory was set in code
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(REPO, ".jax_cache")
        assert enable_compilation_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_strict_native_build_raises_when_make_fails(monkeypatch):
    from ddl25spring_tpu.data import native_loader

    def no_make(cmd, **kw):
        assert cmd[:2] == ["make", "-B"], cmd  # a REbuild, whatever exists
        raise subprocess.CalledProcessError(2, cmd, stderr="g++: not found")

    monkeypatch.setattr(native_loader.subprocess, "run", no_make)
    with pytest.raises(RuntimeError, match="g\\+\\+: not found"):
        native_loader.rebuild_native_libs()
