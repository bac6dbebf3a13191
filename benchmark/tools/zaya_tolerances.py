#!/usr/bin/env python3
"""The readings behind the ``zaya`` family's tolerances, on the chip at the
cell's real size, through the cell's own engine and the family's own
``check_served`` (``families/zaya.py``, PERF.md section 6).

    chiprun -- python3 benchmark/tools/zaya_tolerances.py <cell> [seed ...]

For every seed the cell's engine is built as ``runners/serve.py`` builds it
and serves one request a client with every slot live, until the first
``CHECKED`` requests are done; what is held to the reference is what that
engine's compiled passes kept of the rows they sampled from.  Six readings,
one JSON line each, ``correct`` as the cell would print it:

1. the engine against the float32 reference: the sound reading;
2. the same served requests against a reference that lacks ONE layer's CCA:
   a planted gross fault, for the limits on the maxima, the median and the
   served tokens' margins;
3. the same served requests against the reference with its experts' weights
   rounded to 8 bits, the 3 mantissa bits of e4m3: what the nearest
   precision below the stated one gives for the weights;
4. the same served requests against a reference whose router and q/k
   normalisation run in bfloat16: the nearest precision below the stated
   one for the parts the configuration states in float32;
5. an engine that RUNS its router and its q/k normalisation in bfloat16
   against the float32 reference;
6. an engine that SERVES the rounded expert weights against the float32
   reference with the true ones.

2, 3, 4 and 6 have to come out as not correct: 2 by the limits on the error
itself, 3, 4 and 6 by ``nearer_*_share``, the share of positions whose kept
logits lie nearer to the model stated at the lower precision than to the
reference (``families/zaya.py`` ``LOWER``).  5 is printed with ``wanted:
null``: a router in bfloat16 turns other near-tie choices than the
reference's own bfloat16 router does, so its logits lie no nearer to that
statement than a sound engine's (PERF.md section 6, PR 36, has the
readings).  Measures no speed.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import jax  # noqa: E402

from benchmark.run import BENCH_DIR, load_cell, load_family, load_module  # noqa: E402

READ = ("worst_margin", "worst_margin_near_tie", "margin_mean", "logit_rel_err_p50",
        "logit_rel_err", "logit_rel_err_near_tie", "near_tie_share",
        "nearer_8_bit_experts_share", "nearer_bf16_router_share", "tokens_checked")


# the first expert family's tool, for any family: ``serve`` builds and
# drives the engine as ``runners/serve.py`` does and returns ``(pad_to, done)``
# of the first ``checked`` requests completed of one request a client, all
# sent at once; ``to_8_bits`` rounds the experts' stacks to 3 mantissa bits IN
# PLACE (a second copy does not fit; this family has no shared expert)
_first = load_module(BENCH_DIR, "tools", "mistral4_tolerances")
serve, to_8_bits = _first.serve, _first.to_8_bits


def main() -> int:
    cell, config = load_cell(BENCH_DIR, sys.argv[1])
    family = load_family(BENCH_DIR, config)
    checked = load_module(BENCH_DIR, "runners", cell["runner"]).CHECKED
    cfg = family.build(config)

    def line(seed, reading, out, want: bool | None) -> bool:
        print(json.dumps({
            "seed": seed, "reading": reading, "correct": out["ok"], "wanted": want,
            **{k: out[k] for k in READ},
        }), flush=True)
        return want is None or out["ok"] == want

    as_wanted = True
    for seed in [int(a) for a in sys.argv[2:]] or [0]:
        params = family.init_params(cfg, seed)
        pad_to, done = serve(family, cfg, params, cell, seed, checked)
        check = dict(pad_to=pad_to)
        as_wanted &= line(seed, "engine against the float32 reference",
                          family.check_served(cfg, params, done, **check), True)
        as_wanted &= line(
            seed, "the same requests against a reference without layer 1's CCA",
            family.check_served(cfg, params, done, skip_attention=(1,), **check),
            False)
        as_wanted &= line(
            seed, "the same requests against a reference whose router and q/k "
                  "normalisation run in bfloat16",
            family.check_served(cfg, params, done, high_prec="bfloat16", **check),
            False)
        low_prec = dataclasses.replace(cfg, high_prec="bfloat16")
        pad_to, held = serve(family, low_prec, params, cell, seed, checked)
        as_wanted &= line(
            seed, "an engine that runs its router and q/k normalisation in "
                  "bfloat16 against the float32 reference",
            family.check_served(cfg, params, held, pad_to=pad_to), None)
        del held
        as_wanted &= line(
            seed, "the same requests against the reference with 8-bit expert weights",
            family.check_served(cfg, params, done, expert_bits=3, **check), False)
        low = to_8_bits(params)
        pad_to, done = serve(family, cfg, low, cell, seed, checked)
        del low, params
        params = family.init_params(cfg, seed)
        as_wanted &= line(
            seed, "an engine that serves 8-bit expert weights against the float32 "
                  "reference",
            family.check_served(cfg, params, done, pad_to=pad_to), False)
        del params
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "every_reading_as_wanted": bool(as_wanted)}))
    return 0 if as_wanted else 1


if __name__ == "__main__":
    sys.exit(main())
