"""CPU tests of the benchmark at tiny sizes.

Run from the root of the repo (they are not part of ``tests/``, the repo's
tier-1 suite):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import os
import shutil

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4"
    ).strip()
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


@pytest.fixture
def bench_dir(tmp_path):
    """A copy of the benchmark's data directories with the tiny test
    configuration and cells ADDED as files: what a later PR does."""
    root = tmp_path / "benchmark"
    for sub in ("metrics", "readers", "runners", "families", "configs", "workloads"):
        shutil.copytree(os.path.join(BENCH, sub), root / sub)
    shutil.copy(os.path.join(BENCH, "peaks.json"), root / "peaks.json")
    for sub in ("configs", "workloads"):
        for name in os.listdir(os.path.join(HERE, "data", sub)):
            shutil.copy(os.path.join(HERE, "data", sub, name), root / sub / name)
    return str(root)
