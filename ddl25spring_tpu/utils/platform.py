"""Platform and compile-cache selection for launchers and examples.

Tests and CPU tools pick the CPU backend from the environment
(``JAX_PLATFORMS=cpu``) and get a multi-device mesh from
``--xla_force_host_platform_device_count``; every runnable script also
exposes ``--force-cpu-devices N`` and calls :func:`force_cpu_devices`: the
SPMD analogue of the reference's gloo-on-localhost fake cluster (SURVEY
§4).  On a machine with a chip JAX uses the TPU by default.

:func:`enable_compilation_cache` is the one place a persistent compile
cache is configured; every entry point calls it before its first compile.
"""

from __future__ import annotations

import os
import re
import warnings

_FLAG = "--xla_force_host_platform_device_count"


def force_cpu_devices(n: int) -> None:
    """Simulate an ``n``-device CPU mesh (no-op when ``n`` is 0/None).

    Must run before the first JAX backend init: XLA reads
    ``xla_force_host_platform_device_count`` when the CPU client starts.
    An existing count in ``XLA_FLAGS`` that disagrees with ``n`` is
    overridden with a warning (the explicit argument wins).
    """
    if not n:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(rf"{_FLAG}=(\d+)", flags)
    if m and int(m.group(1)) != n:
        warnings.warn(
            f"XLA_FLAGS already sets {_FLAG}={m.group(1)}; overriding with "
            f"the requested {n}",
            stacklevel=2,
        )
        flags = re.sub(rf"{_FLAG}=\d+", f"{_FLAG}={n}", flags)
        os.environ["XLA_FLAGS"] = flags
    elif not m:
        os.environ["XLA_FLAGS"] = (flags + f" {_FLAG}={n}").strip()

    import jax

    jax.config.update("jax_platforms", "cpu")


def ensure_cpu_tools_env(n: int = 8) -> None:
    """Module preamble shared by the CPU-only analysis tools
    (``tools/comms_report.py``, ``tools/graft_lint.py``,
    ``obs/compile_report.py``): default to a CPU backend with an
    ``n``-device fake host, RESPECTING any count already configured
    (unlike :func:`force_cpu_devices`, which overrides — tools defer to
    the caller's environment)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if _FLAG.lstrip("-") not in flags:
        os.environ["XLA_FLAGS"] = (flags + f" {_FLAG}={n}").strip()


def enable_compilation_cache() -> str:
    """Turn JAX's persistent compilation cache on and return its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set jax reads it
    itself and no other directory is set in code; where it is not, the
    cache lives at ``<checkout>/.jax_cache`` (git-ignored) — a fixed
    path, because the path is part of the cache's key and a directory
    that moves never hits."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if d:
        return d
    import jax

    d = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)
        ))),
        ".jax_cache",
    )
    jax.config.update("jax_compilation_cache_dir", d)
    return d
