#!/usr/bin/env python3
"""The readings behind the ``qwen3next`` family's tolerances, on the chip at
the cell's real size, through the cell's own engine and the family's own
``check_served`` (``families/qwen3next.py``, PERF.md section 6).

    chiprun -- python3 benchmark/tools/qwen3next_tolerances.py <cell> [seed ...]

For every seed the cell's engine is built as ``runners/serve.py`` builds it
and serves one request a client with every slot live, until the first
``CHECKED`` requests are done; what is held to the reference is what that
engine's compiled passes kept of the rows they sampled from.  Six readings,
one JSON line each, ``correct`` as the cell would print it:

1. the engine against the float32 reference: the sound reading;
2. the same served requests against a reference that lacks ONE linear
   layer's mixer: a planted gross fault, for the limits on the maxima and on
   the served tokens' margins;
3. the same served requests against the reference with its experts' weights
   (routed and shared) rounded to 8 bits, the 3 mantissa bits of e4m3: what
   the nearest precision below the stated one gives for the weights;
4. an engine that SERVES the rounded weights against the float32 reference
   with the true ones: that precision in the engine alone;
5. the same served requests as (1) against a reference whose recurrent state
   is rounded to bfloat16 after every token: the nearest precision below the
   stated one for the state;
6. an engine that HOLDS its recurrent state in bfloat16 against the float32
   reference.

2, 3 and 4 have to come out as not correct.  5 and 6 are printed with
``wanted: null``: a state in bfloat16 moves the logits by a tenth of what
serving in bfloat16 moves them for every other reason (PERF.md section 6, PR
33), so no limit on logits can refuse it, and the tool says what it read.
Measures no speed.
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
        "logit_rel_err", "logit_rel_err_near_tie", "near_tie_share", "tokens_checked")


# ``(pad_to, done)``: the first ``checked`` requests the cell's engine completes
# of one request a client, all sent at once: the other expert family's tool
# builds and drives the engine as ``runners/serve.py`` does, for any family
serve = load_module(BENCH_DIR, "tools", "mistral4_tolerances").serve


def to_8_bits(params: dict) -> dict:
    """``params`` with the experts' weights at 3 mantissa bits, IN PLACE, a
    stack at a time.  An explicit op, because a convert to float8 and back
    is elided under XLA's excess-precision default."""
    low = jax.jit(
        lambda a: jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=3),
        donate_argnums=(0,),
    )
    experts, blocks = params.pop("experts"), dict(params.pop("blocks"))
    for k in list(experts):
        experts[k] = low(experts.pop(k))
    moe = [{k: low(v) if k.startswith("ws_") else v for k, v in layer.items()}
           for layer in blocks.pop("moe")]
    return {**params, "experts": experts, "blocks": {**blocks, "moe": moe}}


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
            seed, "the same requests against a reference without layer 1's mixer",
            family.check_served(cfg, params, done, skip_mixers=(1,), **check), False)
        as_wanted &= line(
            seed, "the same requests against a reference whose state is rounded "
                  "to bfloat16 after every token",
            family.check_served(cfg, params, done, state_dtype="bfloat16", **check),
            None)
        low_state = dataclasses.replace(cfg, state_dtype="bfloat16")
        pad_to, held = serve(family, low_state, params, cell, seed, checked)
        as_wanted &= line(
            seed, "an engine that holds its recurrent state in bfloat16 against "
                  "the float32 reference",
            family.check_served(cfg, params, held, pad_to=pad_to), None)
        del held
        low = to_8_bits(params)
        as_wanted &= line(
            seed, "the same requests against the reference with 8-bit expert weights",
            family.check_served(cfg, None, done, reference_params=low, **check), False)
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
