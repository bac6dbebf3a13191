#!/usr/bin/env python
"""Analytic per-layer roofline of the bench ResNet-18/CIFAR step on TPU v5e.

Why this exists: before any op-level `jax.profiler` trace of the step had
been read, the "where does the other half of the MXU go" question was
answered with a model: for every conv in
the ResNet-18 CIFAR variant, compute

- FLOPs (fwd; bwd counted as 2x fwd: dgrad + wgrad);
- an MXU efficiency bound from systolic-array tiling: the contraction dim
  (Cin*kh*kw) pads up to a multiple of 128 lanes and the output-channel
  dim to the 128-wide MXU tile, so layers with Cin*9 or Cout below/not a
  multiple of 128 cannot use the full array (e.g. the 3->64 stem runs at
  27/128 = 21% contraction occupancy at best);
- an HBM-bandwidth bound from activation + weight traffic (bf16, fwd
  read+write, bwd read of saved activations + cotangents, GroupNorm's
  extra normalize pass);

and take per-layer time = max(compute_bound, bandwidth_bound) — which is
exactly the shared roofline the compile-time analytics project whole
programs onto, so each layer rides
``xla_analytics.roofline_projection`` with the chip's peak derated by
its MXU occupancy.  Chip numbers come from the one
``utils/flops.CHIP_SPECS`` table (nothing duplicated here; a drift test
in ``tests/test_flops_tools.py`` pins the fold).  The sum is the best
achievable step time for THIS architecture at THIS batch — the
structural ceiling — to compare against the measured step.

Run: ``python tools/resnet_roofline.py [--batch 1024]``.  Pure math, no
accelerator needed.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from ddl25spring_tpu.obs.xla_analytics import roofline_projection  # noqa: E402
from ddl25spring_tpu.utils.flops import CHIP_SPECS  # noqa: E402

CHIP = "TPU v5e"
# module constants kept as *views* of the shared spec table (the drift
# test asserts they are the same object's numbers, not fresh literals)
PEAK_BF16 = CHIP_SPECS[CHIP]["peak_bf16_flops"]
HBM_BW = CHIP_SPECS[CHIP]["hbm_bytes_per_s"]
MXU_LANE = 128           # systolic array width (contraction + out tiles)

# (name, H, W, Cin, Cout, k, stride, count) — ResNet-18 CIFAR variant
# (ddl25spring_tpu/models/resnet.py block_plan): stem + 4 groups of 2
# blocks; 1x1 projections at each stride-2 group entry
LAYERS = [
    ("stem 3x3/1", 32, 32, 3, 64, 3, 1, 1),
    ("g1 3x3", 32, 32, 64, 64, 3, 1, 4),
    ("g2 entry 3x3/2", 32, 32, 64, 128, 3, 2, 1),
    ("g2 1x1/2 proj", 32, 32, 64, 128, 1, 2, 1),
    ("g2 3x3", 16, 16, 128, 128, 3, 1, 3),
    ("g3 entry 3x3/2", 16, 16, 128, 256, 3, 2, 1),
    ("g3 1x1/2 proj", 16, 16, 128, 256, 1, 2, 1),
    ("g3 3x3", 8, 8, 256, 256, 3, 1, 3),
    ("g4 entry 3x3/2", 8, 8, 256, 512, 3, 2, 1),
    ("g4 1x1/2 proj", 8, 8, 256, 512, 1, 2, 1),
    ("g4 3x3", 4, 4, 512, 512, 3, 1, 3),
]


def ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def conv_cost(B, H, W, Cin, Cout, k, stride, bytes_per=2):
    """Return (flops_fwd, mxu_eff, bytes_fwd) for one conv."""
    Ho, Wo = H // stride, W // stride
    flops = 2.0 * B * Ho * Wo * Cin * Cout * k * k
    # MXU occupancy: contraction dim Cin*k*k and output dim Cout both pad
    # to 128; spatial*batch rows are abundant (>= thousands) so row
    # occupancy ~1
    red = Cin * k * k
    eff = (red / ceil_to(red, MXU_LANE)) * (Cout / ceil_to(Cout, MXU_LANE))
    bytes_ = bytes_per * (B * H * W * Cin + B * Ho * Wo * Cout
                          + Cin * Cout * k * k)
    return flops, eff, bytes_


def layer_rooflines(batch: int, chip: str = CHIP) -> list[dict]:
    """Per-layer roofline rows through the shared projection: each conv
    is one ``roofline_projection`` call with the chip's peak derated by
    the layer's MXU occupancy (fwd+bwd = 3x fwd for both FLOPs and
    traffic, as before the fold)."""
    spec = CHIP_SPECS[chip]
    rows = []
    for name, H, W, Cin, Cout, k, s, cnt in LAYERS:
        f, eff, by = conv_cost(batch, H, W, Cin, Cout, k, s)
        proj = roofline_projection(
            3 * f, 3 * by, 0.0, chips=[chip],
            specs={chip: {**spec, "peak_bf16_flops":
                          spec["peak_bf16_flops"] * eff}},
        )[chip]
        rows.append({
            "name": name,
            "count": cnt,
            "flops_fwd": f,
            "mxu_eff": eff,
            "bytes_fwd": by,
            "t_comp_s": proj["t_compute_s"],
            "t_bw_s": proj["t_hbm_s"],
            "t_s": proj["projected_step_s"] * cnt,
            "bound": proj["bound"],
        })
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=1024)
    args = ap.parse_args(argv)
    B = args.batch

    print(f"{'layer':18s} {'GF(fwd)':>8s} {'MXU eff':>8s} "
          f"{'t_comp':>8s} {'t_bw':>8s} {'t(ms,f+b)':>9s}")
    rows = layer_rooflines(B)
    tot_t = sum(r["t_s"] for r in rows)
    tot_f = sum(3 * r["flops_fwd"] * r["count"] for r in rows)
    for r in rows:
        print(f"{r['name']:18s} {r['flops_fwd'] / 1e9:8.1f} "
              f"{r['mxu_eff'] * 100:7.0f}% "
              f"{r['t_comp_s'] * 1e3:8.2f} {r['t_bw_s'] * 1e3:8.2f} "
              f"{r['t_s'] * 1e3:9.2f}")

    # GroupNorm + relu + residual adds: elementwise/reduction passes over
    # the activation footprint, bandwidth-bound.  How many full passes
    # survive depends on XLA fusion: ~12 unfused (stats, normalize,
    # relu, add and their grads all separate) down to ~4 when everything
    # fusable rides a conv epilogue and only the GroupNorm reductions
    # force extra sweeps.  Report both ends of the range.
    act_bytes = 2 * B * sum(
        (H // s) * (W // s) * Cout * cnt
        for _, H, W, _, Cout, _, s, cnt in LAYERS
    )
    opt_bytes = 2 * 11.2e6 * 3 * 4  # params+grad+momentum fp32 r/w
    t_opt = opt_bytes / HBM_BW
    print(f"{'sgd+momentum':18s} {'':8s} {'':8s} {'':8s} "
          f"{t_opt*1e3:8.2f} {t_opt*1e3:9.2f}")

    xla_flops = 2.98e12 * (B / 1024)  # bench-reported cost-model FLOPs
    print(f"\nconv FLOPs counted: {tot_f/1e12:.2f} TF "
          f"-> naive 100%-MXU time {tot_f/PEAK_BF16*1e3:.2f} ms")
    for passes, label in ((4, "well-fused"), (12, "unfused")):
        t_elem = passes * act_bytes / HBM_BW
        t = tot_t + t_elem + t_opt
        print(f"{label:>10s} ({passes:2d} elementwise passes): "
              f"step >= {t*1e3:6.2f} ms -> ceiling "
              f"{tot_f / PEAK_BF16 / t * 100:5.1f}% (this count) / "
              f"{xla_flops / PEAK_BF16 / t * 100:5.1f}% (bench's XLA count)")
    print(
        "\nReading: in the bench's own MFU accounting (XLA cost-model\n"
        "FLOPs), the well-fused bound is ~48% (the step's measured\n"
        "share of it: not measured on the current installation).  The\n"
        "headroom to 55%+ MFU does not exist for THIS model at THIS\n"
        "batch on v5e in this model:\n"
        "the stem runs at ~11% MXU occupancy (27/128 contraction lanes\n"
        "x 64/128 output lanes), group-1 convs at ~45%, and the\n"
        "GroupNorm reductions are irreducibly bandwidth-bound.  The\n"
        "recoverable inefficiency was per-dispatch overhead, which the\n"
        "scan-fused primary removes."
    )


if __name__ == "__main__":
    main()
