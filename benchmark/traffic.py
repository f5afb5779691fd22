"""The one traffic generator. It reads a mix's parameters (``benchmark/traffic/<name>.json``,
overlaid with the cell's own ``benchmark/cells/<cell>.json``) and turns ``--seed`` into
requests. A new mix is a new data file, never new code here.

Lengths and gaps between arrivals are the equal-probability quantiles of their distributions,
so every seed gets the SAME multiset of sizes and arrivals and does the same amount of work.
Everything else is drawn from ``--seed``: the order of the lengths, the order of the gaps,
which requests are sampled, their seeds, and the token ids (uniform over the vocabulary).
"""

from __future__ import annotations

import json
import math
import os
import random
from statistics import NormalDist

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(traffic: str, cell: str | None = None) -> dict:
    with open(os.path.join(HERE, "traffic", traffic + ".json")) as f:
        mix = json.load(f)
    overlay = os.path.join(HERE, "cells", f"{cell}.json")
    if cell and os.path.exists(overlay):
        with open(overlay) as f:
            mix.update(json.load(f))
    return mix


def quantile_lengths(spec: dict, n: int) -> list[int]:
    """``n`` lengths at the mid-point quantiles (i + 0.5) / n of ``spec``'s distribution,
    clipped to [min, max], in rising order."""
    lo, hi = int(spec["min"]), int(spec["max"])
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        if spec["dist"] == "lognormal":
            x = float(spec["median"]) * math.exp(float(spec["sigma"]) * NormalDist().inv_cdf(u))
        elif spec["dist"] == "uniform":
            x = lo + u * (hi + 1 - lo)
        else:
            raise ValueError(f"unknown length distribution {spec['dist']!r}")
        out.append(max(lo, min(hi, int(x))))
    return out


def _shuffled(xs: list, rnd: random.Random) -> list:
    xs = list(xs)
    rnd.shuffle(xs)
    return xs


def make_requests(mix: dict, n: int, vocab_size: int, seed: int) -> list[dict]:
    """``n`` requests: ``prompt`` (token ids), ``max_tokens`` and ``sampling`` (the body fields
    the OpenAI surface takes). A ``sampled_share`` of them decode at a temperature with a seed
    of their own, the rest greedily."""
    rnd = random.Random(seed * 7919 + 17)
    plens = _shuffled(quantile_lengths(mix["prompt_len"], n), rnd)
    olens = _shuffled(quantile_lengths(mix["output_len"], n), rnd)
    n_sampled = round(float(mix.get("sampled_share", 0.0)) * n)
    sampled = set(_shuffled(range(n), rnd)[:n_sampled])
    reqs = []
    for i in range(n):
        sampling = {"temperature": 0.0}
        if i in sampled:
            sampling = {**mix["sampled"], "seed": (seed + 1000003 * (i + 1)) % (2**31 - 1)}
        reqs.append({"index": i, "prompt": [rnd.randrange(1, vocab_size - 1) for _ in range(plens[i])],
                     "max_tokens": olens[i], "sampling": sampling})
    return reqs


def open_loop_schedule(mix: dict, horizon_s: float, seed: int) -> list[float]:
    """Arrival offsets in [0, horizon) of an open loop at ``rate_per_s``: the gaps are the
    quantiles of the exponential distribution (the gaps of a Poisson process of that rate), in
    an order drawn from the seed, rescaled so that they fill the horizon exactly."""
    n = max(1, round(float(mix["rate_per_s"]) * horizon_s))
    gaps = _shuffled([-math.log(1.0 - (i + 0.5) / n) for i in range(n)], random.Random(seed * 104729 + 5))
    scale = horizon_s / sum(gaps)
    out, t = [], 0.0
    for g in gaps:
        out.append(t)
        t += g * scale
    return out


def closed_loop_plan(mix: dict, vocab_size: int, seed: int, n_per_client: int) -> list[list[dict]]:
    """For each of ``clients`` callers its own list of requests, to be sent one after another.
    Requests are made in blocks of ``clients`` x 2, each block the same multiset of lengths, so
    that any stretch of the run sees the same mix."""
    clients = int(mix["clients"])
    block = clients * 2
    n = -(-clients * n_per_client // block) * block
    reqs = []
    for b in range(n // block):
        part = make_requests(mix, block, vocab_size, seed * 31 + b)
        for r in part:
            r["index"] += b * block
        reqs.extend(part)
    return [reqs[c::clients][:n_per_client] for c in range(clients)]


def train_batch(seed: int, step: int, batch: int, seq: int, vocab_size: int) -> dict:
    """Step ``step``'s host batch: uniform token ids, targets the tokens shifted by one (the
    last target of each row is ignored, -100)."""
    import numpy as np

    rng = np.random.default_rng([seed % (2**32), step])
    tokens = rng.integers(0, vocab_size, (batch, seq), dtype=np.int32)
    targets = np.concatenate([tokens[:, 1:], np.full((batch, 1), -100, np.int32)], axis=1)
    return {"tokens": tokens, "targets": targets}
