"""The one general traffic generator: a mix file in, a schedule out.

A mix (``benchmark/traffic/<name>.json``) fixes distributions and a rate or
a worker count.  The generator never samples: it takes the evenly spaced
quantiles of each distribution, so every run of a cell offers the same
multiset of prompt lengths, output lengths and inter-arrival gaps.  The
seed permutes their order and pairing (inside each block of the stream,
where the mix cuts it into blocks) and draws the token ids; it never
changes the work.

Imports numpy only, so the tests and the rehearsal need no accelerator.
"""
from __future__ import annotations

import json
import math
import os
from statistics import NormalDist

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> dict:
    """``benchmark/traffic/<name>.json``; its ``driver`` names the module
    under ``benchmark/`` that runs it."""
    path = os.path.join(HERE, "traffic", name + ".json")
    with open(path) as f:
        mix = json.load(f)
    if "driver" not in mix:
        raise ValueError(f"{path}: a mix names its driver module")
    return mix


def quantiles(dist: dict, n: int) -> np.ndarray:
    """The ``n`` evenly spaced quantiles ``(i + 0.5) / n`` of ``dist``."""
    if n < 1:
        raise ValueError("need at least one quantile")
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(v)) for v in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "exponential":
        x = -np.log1p(-u)              # mean 1; the caller scales
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in dist and kind != "uniform":
        x = np.maximum(x, dist["min"])
    if "max" in dist and kind != "uniform":
        x = np.minimum(x, dist["max"])
    return x


def lengths(dist: dict, n: int) -> np.ndarray:
    """Quantiles rounded to whole tokens, held to the distribution's
    limits."""
    x = np.rint(quantiles(dist, n)).astype(np.int64)
    return np.clip(x, int(dist.get("min", 1)), int(dist.get("max", x.max())))


def _rng(seed: int, *salt: int) -> np.random.Generator:
    # SeedSequence takes any non-negative whole number, 2**31 and beyond
    return np.random.default_rng([int(seed), *salt])


def _phase(mix: dict, n: int, span_s: float, rng) -> list:
    """``n`` open-loop requests whose arrivals fill ``[0, span_s)``: the
    gaps are the quantiles of the gap distribution scaled to sum to the
    span, in a seed-drawn order; the first request is due at 0."""
    p = rng.permutation(lengths(mix["prompt_len"], n))
    o = rng.permutation(lengths(mix["output_len"], n))
    g = rng.permutation(quantiles(mix["gaps"], n))
    g = g * (span_s / g.sum())
    due = np.concatenate([[0.0], np.cumsum(g)[:-1]])
    return [{"due": float(due[i]), "prompt_len": int(p[i]),
             "max_new_tokens": int(o[i])} for i in range(n)]


def open_schedule(mix: dict, seed: int, seconds: float,
                  tail_s: float = 0.0) -> dict:
    """Warm phase, window and (for a traced run) a tail, each its own
    stratified set, so the window holds exactly ``rate * seconds``
    requests of the same lengths in every run.  With ``block_s`` in the
    mix a phase is cut into blocks of that many seconds, each block its
    own stratified set in its own seed-drawn order: every block then
    offers the same work, and what the seed can still move (which long
    answers are alive when the window opens and closes, how many slots
    are live when a long prompt arrives) is held to one block, not spread
    over the window.  ``due`` is in seconds from the start of the warm
    phase."""
    rate, warm_s = float(mix["rate_rps"]), float(mix["warm_s"])
    reqs, t = [], 0.0
    for salt, span in ((1, warm_s), (2, float(seconds)), (5, tail_s)):
        end, k = t + span, 0
        while end - t > 1e-9:
            part = min(float(mix.get("block_s", span)), end - t)
            block = _phase(mix, max(1, round(rate * part)), part,
                           _rng(seed, salt, k))
            for r in block:
                r["due"] += t
            reqs += block
            t, k = t + part, k + 1
        t = end
    return {"loop": "open", "warm_s": warm_s, "seconds": float(seconds),
            "requests": reqs}


def closed_schedule(mix: dict, seed: int, n_requests: int) -> dict:
    """A stream for a closed loop: whole blocks of ``block`` quantile
    pairs, each block in its own seed-drawn order and pairing, so every
    block is the same multiset of prompt lengths and of output lengths
    under every seed.  Workers take the requests in order as they come
    free, and the window runs from the end of one block's prefills to the
    end of another's (``loadgen.py``), so it holds the same work in every
    run and the seed only orders it."""
    block = int(mix["block"])
    p0 = lengths(mix["prompt_len"], block)
    o0 = lengths(mix["output_len"], block)
    rng = _rng(seed, 3)
    reqs = []
    for _ in range(math.ceil(n_requests / block)):
        p, o = rng.permutation(p0), rng.permutation(o0)
        reqs += [{"prompt_len": int(p[i]), "max_new_tokens": int(o[i])}
                 for i in range(block)]
    return {"loop": "closed", "requests": reqs}


def token_ids(seed: int, index: int, n: int, vocab: int) -> list:
    """Prompt ``index``'s token ids: uniform over ``[1, vocab)``."""
    return _rng(seed, 1000 + index).integers(1, vocab, n).tolist()
