"""Unit tests: FLOPs/MFU accounting and the notebook scrubber."""

import json

import jax
import numpy as np

from ddl25spring_tpu.utils.flops import chip_peak_flops, compiled_flops, mfu


def test_compiled_flops_counts_matmul():
    import jax.numpy as jnp

    @jax.jit
    def f(a, b):
        return (a @ b).sum()

    a = jnp.ones((128, 128))
    fl = compiled_flops(f, a, a)
    # 2*n^3 MACs-as-flops, plus the reduction; cost model may round
    assert fl is not None and fl >= 2 * 128**3


def test_chip_peak_prefix_match_prefers_longest():
    # device_kind "TPU v5 lite" must hit the v5e entry (197e12), not the
    # "TPU v5" (v5p) prefix
    class FakeDev:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    assert chip_peak_flops(FakeDev()) == 197e12

    class FakeV5p:
        platform = "tpu"
        device_kind = "TPU v5"

    assert chip_peak_flops(FakeV5p()) == 459e12

    # a chip that is not in the table is an error, never a default
    class FakeUnknown:
        platform = "tpu"
        device_kind = "TPU v99"

    import pytest

    with pytest.raises(KeyError, match="TPU v99"):
        chip_peak_flops(FakeUnknown())


def test_chip_peak_on_cpu_calibrates_host_fallback():
    # datasheet-only callers still get None off-TPU ...
    assert chip_peak_flops(jax.devices("cpu")[0], allow_host=False) is None
    # ... but the default contract is now DEFINED on the CPU CI image:
    # the calibrated cpu-host pseudo-peak (perfscope's measured-MFU
    # denominator), so obs_report's MFU column stops reading n/a here
    peak = chip_peak_flops(jax.devices("cpu")[0])
    assert peak is not None and peak > 0


def test_host_peak_spec_cpu_host():
    from ddl25spring_tpu.utils.flops import (
        CHIP_SPECS,
        CPU_HOST_KIND,
        host_peak_spec,
    )

    kind, spec = host_peak_spec(jax.devices("cpu")[0])
    assert kind == CPU_HOST_KIND
    assert spec["peak_bf16_flops"] > 0
    # the calibrated peak replaces the placeholder; bandwidth terms
    # come from the static pseudo-spec
    assert spec["hbm_bytes_per_s"] == (
        CHIP_SPECS[CPU_HOST_KIND]["hbm_bytes_per_s"]
    )

    class FakeV4:
        platform = "tpu"
        device_kind = "TPU v4"

    kind, spec = host_peak_spec(FakeV4())
    assert kind == "TPU v4"
    assert spec == CHIP_SPECS["TPU v4"]


def test_roofline_projects_with_peak_only_spec():
    """A chip known only by its bf16 peak (TPU v2/v3/7x — in
    PEAK_BF16_FLOPS but without a full CHIP_SPECS entry, the shape
    host_peak_spec returns there) must still project: an unknown
    bandwidth just doesn't bound the step."""
    from ddl25spring_tpu.obs.xla_analytics import roofline_projection

    p = roofline_projection(
        1e12, 1e9, 1e6, chips=["TPU v2"],
        specs={"TPU v2": {"peak_bf16_flops": 45e12}},
    )["TPU v2"]
    assert p["bound"] == "compute"
    assert p["projected_mfu"] == 1.0


def test_calibration_failure_is_cached(monkeypatch):
    import jax as _jax

    from ddl25spring_tpu.utils import flops as fl

    monkeypatch.setattr(fl, "_HOST_PEAK", None)
    monkeypatch.setattr(fl, "_HOST_PEAK_TRIED", False)
    calls = []

    def broken_jit(*a, **k):
        calls.append(1)
        raise RuntimeError("broken backend")

    monkeypatch.setattr(_jax, "jit", broken_jit)
    assert fl.calibrated_host_peak_flops() is None
    assert fl.calibrated_host_peak_flops() is None
    # the failed attempt is cached: one timed-matmul attempt per
    # process, not one per peak lookup
    assert len(calls) == 1
    # and the placeholder peak never masquerades as a calibration:
    # spec is None, so perfscope nulls measured_mfu instead of faking
    # one against the 5e10 constant
    kind, spec = fl.host_peak_spec(jax.devices("cpu")[0])
    assert kind == fl.CPU_HOST_KIND and spec is None


def test_resnet_roofline_rides_shared_projection():
    """Drift pin for the PR-7 fold: tools/resnet_roofline.py must source
    its chip numbers from the one CHIP_SPECS table and compute each
    layer through xla_analytics.roofline_projection — re-deriving a
    layer independently must reproduce the tool's row exactly."""
    import pytest

    from ddl25spring_tpu.obs.xla_analytics import roofline_projection
    from ddl25spring_tpu.utils.flops import CHIP_SPECS
    from tools.resnet_roofline import CHIP, HBM_BW, PEAK_BF16, layer_rooflines

    assert PEAK_BF16 == CHIP_SPECS[CHIP]["peak_bf16_flops"]
    assert HBM_BW == CHIP_SPECS[CHIP]["hbm_bytes_per_s"]
    rows = layer_rooflines(256)
    assert len(rows) == 11
    for r in rows:
        # per-layer time = max(compute, bandwidth) * count — the
        # roofline contract, now via the shared helper
        assert r["t_s"] == pytest.approx(
            max(r["t_comp_s"], r["t_bw_s"]) * r["count"]
        )
    stem = rows[0]
    spec = CHIP_SPECS[CHIP]
    p = roofline_projection(
        3 * stem["flops_fwd"], 3 * stem["bytes_fwd"], 0.0, chips=[CHIP],
        specs={CHIP: {**spec, "peak_bf16_flops":
                      spec["peak_bf16_flops"] * stem["mxu_eff"]}},
    )[CHIP]
    assert stem["t_s"] == pytest.approx(
        p["projected_step_s"] * stem["count"]
    )
    # the stem's 3->64 conv cannot fill the 128-lane MXU
    assert stem["mxu_eff"] < 0.25


def test_mfu_math():
    class FakeDev:
        platform = "tpu"
        device_kind = "TPU v4"

    tf, frac = mfu(275e12, 1.0, n_chips=1, device=FakeDev())
    assert tf == 275.0
    np.testing.assert_allclose(frac, 1.0)
    assert mfu(None, 1.0) == (None, None)


def test_notebook_scrubber(tmp_path):
    import subprocess
    import sys

    nb = {
        "metadata": {"kernelspec": {"name": "python3"}, "widgets": {"x": 1}},
        "nbformat": 4, "nbformat_minor": 5,
        "cells": [{
            "cell_type": "code", "source": ["1+1"],
            "execution_count": 3, "metadata": {"scrolled": True},
            "outputs": [{"output_type": "execute_result", "data": {}}],
        }],
    }
    from pathlib import Path

    tool = Path(__file__).resolve().parent.parent / "tools/clear_notebook_metadata.py"
    p = tmp_path / "x.ipynb"
    p.write_text(json.dumps(nb))
    r = subprocess.run(
        [sys.executable, str(tool), str(tmp_path)],
        capture_output=True, text=True, check=True,
    )
    assert "1 notebook(s) changed" in r.stdout
    out = json.loads(p.read_text())
    cell = out["cells"][0]
    assert cell["outputs"] == [] and cell["execution_count"] is None
    assert cell["metadata"] == {}
    assert "widgets" not in out["metadata"]
    assert "kernelspec" in out["metadata"]
