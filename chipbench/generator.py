"""One traffic generator for every mix: it reads the mix's data file.

A mix file (``chipbench/traffic/<mix>.json``) gives the arrival process,
the prompt and answer length distributions, the pre-roll and how many
requests fill the slots before the window opens.  Lengths and gaps are
the quantiles of their distributions at evenly spaced probabilities, and
the seed draws only their order: every seed sends the same work in
another order, so runs differ by the order of the traffic and not by how
much of it there is.

Distributions: ``{"dist": "fixed", "value": v}``,
``{"dist": "uniform", "min": a, "max": b}`` and
``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``;
``"buckets": [...]`` rounds a length up to the next bucket.
"""

from __future__ import annotations

import bisect
import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np

HERE = Path(__file__).resolve().parent


@dataclass(frozen=True)
class Spec:
    rid: int
    arrival: float  # seconds after the engine starts
    prompt_len: int
    max_new_tokens: int


def load_mix(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def quantiles(dist: dict, n: int) -> list:
    """``n`` lengths at probabilities (i + 0.5) / n, in rising order."""
    us = [(i + 0.5) / n for i in range(n)]
    kind = dist["dist"]
    if kind == "fixed":
        xs = [float(dist["value"])] * n
    elif kind == "uniform":
        xs = [dist["min"] + (dist["max"] - dist["min"]) * u for u in us]
    elif kind == "lognormal":
        z = NormalDist()
        xs = [dist["median"] * math.exp(dist["sigma"] * z.inv_cdf(u))
              for u in us]
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    lo, hi = dist.get("min", -math.inf), dist.get("max", math.inf)
    out = [int(round(min(max(x, lo), hi))) for x in xs]
    buckets = dist.get("buckets")
    if buckets:
        out = [buckets[min(bisect.bisect_left(buckets, x), len(buckets) - 1)]
               for x in out]
    return out


def buckets(mix: dict) -> list:
    """Every prompt length the mix can send (the shapes set-up warms)."""
    p = mix["prompt"]
    if p.get("buckets"):
        return list(p["buckets"])
    return sorted(set(quantiles(p, 64)))


def _order(seed: int, stream: int, xs: list) -> list:
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), stream]))
    return [xs[i] for i in rng.permutation(len(xs))]


def fill_count(mix: dict, max_batch: int) -> int:
    f = mix.get("fill", 0)
    return max_batch if f == "max_batch" else int(f)


def requests(mix: dict, rate: float, seconds: float, seed: int,
             max_batch: int) -> list:
    """The fill (due at 0, answers spread over the output range) followed
    by Poisson arrivals over the pre-roll and the window."""
    nfill = fill_count(mix, max_batch)
    out = []
    if nfill:
        plens = _order(seed, 1, quantiles(mix["prompt"], nfill))
        outs = _order(seed, 2, quantiles(mix["output"], nfill))
        out += [Spec(i, 0.0, p, o) for i, (p, o) in
                enumerate(zip(plens, outs))]
    if mix["arrivals"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
    n = max(1, round(rate * (mix["preroll_s"] + seconds)))
    gaps = _order(seed, 3, [-math.log1p(-(i + 0.5) / n) / rate
                            for i in range(n)])
    plens = _order(seed, 4, quantiles(mix["prompt"], n))
    outs = _order(seed, 5, quantiles(mix["output"], n))
    t = 0.0
    for i in range(n):
        t += gaps[i]
        out.append(Spec(nfill + i, t, plens[i], outs[i]))
    return out
