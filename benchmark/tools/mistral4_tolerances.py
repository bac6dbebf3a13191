#!/usr/bin/env python3
"""The readings behind the ``mistral4`` family's tolerances, on the chip at
the cell's real size, through the cell's own engine and the family's own
``check_served`` (``families/mistral4.py``, PERF.md section 6).

    chiprun -- python3 benchmark/tools/mistral4_tolerances.py <cell> [seed ...]

For every seed the cell's engine is built as ``runners/serve.py`` builds it
and serves one request a client with every slot live, until the first
``CHECKED`` requests are done; what is held to the reference is what that
engine's compiled passes kept of the rows they sampled from.  Four readings,
one JSON line each, ``correct`` as the cell would print it:

1. the engine against the float32 reference: the sound reading;
2. the same served requests against a reference that lacks ONE layer's
   attention: a planted gross fault, for the limits on the maxima and on the
   served tokens' margins;
3. the same served requests against the reference with its experts' weights
   (routed and shared) rounded to 8 bits, the 3 mantissa bits of e4m3: what
   the nearest precision below the stated one gives;
4. an engine that SERVES the rounded weights against the float32 reference
   with the true ones: a lower precision in the engine alone.

2, 3 and 4 have to come out as not correct.  Measures no speed.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import jax  # noqa: E402

from benchmark import traffic  # noqa: E402
from benchmark.run import BENCH_DIR, load_cell, load_family, load_module  # noqa: E402

READ = ("worst_margin", "worst_margin_near_tie", "margin_mean", "logit_rel_err_p50",
        "logit_rel_err", "logit_rel_err_near_tie", "near_tie_share", "tokens_checked")


def serve(family, cfg, params, cell: dict, seed: int, checked: int):
    """``(pad_to, done)``: the first ``checked`` requests the cell's engine
    completes of one request a client, all sent at once."""
    from ddl25spring_tpu.serve import driver

    knobs = {**driver.engine_knobs(), **cell["engine"]}
    eng = driver._build_engine(
        params, cfg, knobs, clock="wall", temperature=0.0, trace_label=None
    )
    eng.warmup()
    stream = traffic.requests(cell["traffic"], family.vocab(cfg), seed)
    for _ in range(int(cell["traffic"]["clients"])):
        prompt, max_new = next(stream)
        assert eng.submit(eng.make_request(prompt, max_new)) is None
    while len(eng.done) < checked:
        eng.step()
    return eng.max_seq_len, [(r.prompt, r.tokens) for r in eng.done[:checked]]


def to_8_bits(params: dict) -> dict:
    """``params`` with the experts' weights at 3 mantissa bits, IN PLACE, a
    stack at a time: a second copy of the experts does not fit.  An explicit
    op, because a convert to float8 and back is elided under XLA's
    excess-precision default."""
    low = jax.jit(
        lambda a: jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=3),
        donate_argnums=(0,),
    )
    experts, blocks = params.pop("experts"), dict(params.pop("blocks"))
    for k in list(experts):
        experts[k] = low(experts.pop(k))
    for k in [k for k in blocks if k.startswith("ws_")]:
        blocks[k] = low(blocks.pop(k))
    return {**params, "experts": experts, "blocks": blocks}


def main() -> int:
    cell, config = load_cell(BENCH_DIR, sys.argv[1])
    family = load_family(BENCH_DIR, config)
    checked = load_module(BENCH_DIR, "runners", cell["runner"]).CHECKED
    cfg = family.build(config)

    def line(seed, reading, out, want: bool) -> bool:
        print(json.dumps({
            "seed": seed, "reading": reading, "correct": out["ok"],
            "as_wanted": out["ok"] == want, **{k: out[k] for k in READ},
        }), flush=True)
        return out["ok"] == want

    as_wanted = True
    for seed in [int(a) for a in sys.argv[2:]] or [0]:
        params = family.init_params(cfg, seed)
        pad_to, done = serve(family, cfg, params, cell, seed, checked)
        as_wanted &= line(seed, "engine against the float32 reference",
                          family.check_served(cfg, params, done, pad_to=pad_to), True)
        blocks = params["blocks"]
        faulty = {**params, "blocks": {**blocks, "wo": blocks["wo"].at[1].set(0)}}
        as_wanted &= line(
            seed, "the same requests against a reference without layer 1's attention",
            family.check_served(cfg, params, done, pad_to=pad_to,
                                reference_params=faulty), False)
        del faulty, blocks
        low = to_8_bits(params)
        as_wanted &= line(
            seed, "the same requests against the reference with 8-bit expert weights",
            family.check_served(cfg, None, done, pad_to=pad_to,
                                reference_params=low), False)
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
