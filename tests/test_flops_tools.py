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


def test_no_peak_off_tpu():
    # a host never stands in for a chip: no peak, so no MFU fraction
    cpu = jax.devices("cpu")[0]
    assert chip_peak_flops(cpu) is None
    achieved_tf, frac = mfu(1e9, 1.0, n_chips=1, device=cpu)
    assert achieved_tf == 1e-3 and frac is None


def test_roofline_projects_with_peak_only_spec():
    """A chip known only by its bf16 peak (TPU v2/v3/7x — in
    PEAK_BF16_FLOPS but without a full CHIP_SPECS entry) must still
    project: an unknown bandwidth just doesn't bound the step."""
    from ddl25spring_tpu.obs.xla_analytics import roofline_projection

    p = roofline_projection(
        1e12, 1e9, 1e6, chips=["TPU v2"],
        specs={"TPU v2": {"peak_bf16_flops": 45e12}},
    )["TPU v2"]
    assert p["bound"] == "compute"
    assert p["projected_mfu"] == 1.0


def test_resnet_roofline_rides_shared_projection():
    """Drift pin: tools/resnet_roofline.py must source
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
