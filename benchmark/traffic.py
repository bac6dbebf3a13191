"""The one general traffic generator: every cell's traffic is a data file
of parameters that this module turns into work, from the seed.

The seed never changes the AMOUNT of work: lengths come from a pool that
is a deterministic function of the workload file (stratified quantiles of
its distributions, in an order fixed by the file's ``pool_seed``), and
``--seed`` only draws the token ids (and, in the runners, the weights).
Two seeds therefore send the same sizes in the same order.  Another ORDER
was tried first and is not enough where a window holds few requests: the
closed-loop serving cell sends ~45 requests of its pool of 64 in a window,
so a seeded order changed WHICH sizes were sent and moved its tokens/s by
5.6 % between two seeds (my chip runs, PR 25).

Host-side numpy only; no jax, no device.
"""

from __future__ import annotations

import itertools
from statistics import NormalDist
from typing import Any, Iterator

import numpy as np

# ---------------------------------------------------------------- lengths


def length_pool(dist: dict[str, Any], n: int) -> list[int]:
    """``n`` lengths from ``dist``: stratified quantiles, so the pool is
    the same for every seed.

    ``{"kind": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``
    or ``{"kind": "fixed", "value": v}``."""
    kind = dist["kind"]
    if kind == "fixed":
        return [int(dist["value"])] * n
    if kind != "lognormal":
        raise ValueError(f"length distribution {kind!r} is not known")
    z = NormalDist()
    out = []
    for i in range(n):
        x = dist["median"] * np.exp(dist["sigma"] * z.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(float(x)), dist["min"]), dist["max"])))
    return out


def request_pool(traffic: dict[str, Any]) -> list[tuple[int, int]]:
    """The cell's fixed sequence of ``(prompt_len, max_new)`` pairs.  The
    two lengths are independent and their order is shuffled, both by a
    generator seeded from the FILE (``pool_seed``), not from ``--seed``."""
    n = int(traffic["pool_size"])
    rng = np.random.default_rng(int(traffic.get("pool_seed", 0)))
    prompts = rng.permutation(length_pool(traffic["prompt_len"], n))
    news = rng.permutation(length_pool(traffic["max_new"], n))
    return [(int(p), int(m)) for p, m in zip(prompts, news)]


# ----------------------------------------------------------------- tokens


def zipf_cdf(vocab: int, a: float) -> np.ndarray:
    """CDF of a Zipf(``a``) law over the ids ``[1, vocab)`` (0 is pad by
    the repo's convention)."""
    w = 1.0 / np.arange(1, vocab, dtype=np.float64) ** a
    return np.cumsum(w / w.sum())


def draw_tokens(rng: np.random.Generator, tokens: dict[str, Any], vocab: int,
                shape, cdf: np.ndarray | None = None) -> np.ndarray:
    """Token ids in ``[1, vocab)``: ``{"kind": "uniform"}`` or
    ``{"kind": "zipf", "a": 1.1}`` (pass the cached ``cdf``)."""
    kind = tokens["kind"]
    if kind == "uniform":
        return rng.integers(1, vocab, size=shape, dtype=np.int32)
    if kind == "zipf":
        if cdf is None:
            cdf = zipf_cdf(vocab, float(tokens["a"]))
        ids = np.searchsorted(cdf, rng.random(size=shape)) + 1
        return np.minimum(ids, vocab - 1).astype(np.int32)
    raise ValueError(f"token distribution {kind!r} is not known")


def train_batches(traffic: dict[str, Any], vocab: int, batch: int, ctx: int,
                  seed: int) -> Iterator[np.ndarray]:
    """Endless ``[batch, ctx]`` int32 batches of full-length sequences."""
    rng = np.random.default_rng(seed)
    spec = traffic["tokens"]
    cdf = zipf_cdf(vocab, float(spec["a"])) if spec["kind"] == "zipf" else None
    while True:
        yield draw_tokens(rng, spec, vocab, (batch, ctx), cdf)


# --------------------------------------------------------------- requests


def requests(traffic: dict[str, Any], vocab: int, seed: int
             ) -> Iterator[tuple[list[int], int]]:
    """Endless ``(prompt, max_new)`` stream: the fixed pool in its fixed
    order, cycled, with token ids drawn from the seed.

    ``shared_prefix: {"count": c, "len": n}`` draws ``c`` system prompts
    once; every request then starts with one of them and its own
    ``prompt_len`` tokens follow."""
    rng = np.random.default_rng(seed)
    spec = traffic["tokens"]
    shared = traffic.get("shared_prefix")
    prefixes = []
    if shared:
        prefixes = [
            draw_tokens(rng, spec, vocab, (int(shared["len"]),)).tolist()
            for _ in range(int(shared["count"]))
        ]
    for p_len, max_new in itertools.cycle(request_pool(traffic)):
        own = draw_tokens(rng, spec, vocab, (p_len,)).tolist()
        head = prefixes[int(rng.integers(len(prefixes)))] if prefixes else []
        yield head + own, max_new


# --------------------------------------------------------------- arrivals


def poisson_arrivals(rate_rps: float, duration_s: float, seed: int) -> list[float]:
    """Open-loop arrival times in ``[0, duration_s)``: a Poisson process of
    constant rate (the flat profile of ``ddl25spring_tpu/serve/traffic.py``
    ``synth_trace``, whose thinning a constant rate does not need)."""
    if rate_rps <= 0 or duration_s <= 0:
        return []
    rng = np.random.default_rng(seed)
    out, t = [], 0.0
    while True:
        t += float(rng.exponential(1.0 / rate_rps))
        if t >= duration_s:
            return out
        out.append(t)
