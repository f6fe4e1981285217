"""The one traffic generator: a mix file's parameters plus a seed -> requests.

The Poisson arrival clock and the length sampler are copied from the
program's ``repro.serve.workload`` (``_Arrivals``, ``_sample_length``)
so that a later change to the program cannot move the yardstick.

Every seed gets the same work.  The mix's ``shape_seed`` draws the set of
(prompt length, output budget) pairs and, open loop, the set of gaps
between arrivals.  ``--seed`` draws the prompt tokens (and the weights)
and, open loop, the order in which those pairs and those gaps come: the
same offered load and the same last arrival, on another path through it.
A closed loop keeps the mix's order: every request is queued at once, so
the order decides which requests drain last, and a permuted order moved
the window's end by up to 14% between seeds.

Mix file keys::

    source       where the lengths come from (a paper or a public trace)
    loop         "open" (Poisson arrivals at rate_rps) or "closed"
                 (one client per slot, a fixed count of requests)
    rate_rps     open loop: the offered rate
    requests_per_s   closed loop: requests per second of window; a run of
                 ``seconds`` serves ceil(requests_per_s * seconds) of them
    slots        slot pool size
    bucket       prompt bucket the scheduler left-pads to
    max_new      per-slot generation capacity
    prompt, output   {"dist": "uniform" | "lognormal", "min", "max",
                      lognormal: "mean", "sigma"}
    shape_seed   seed of the sizes and of the arrival gaps
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Offer:
    """What a run offers the server: prompts, budgets and, open loop,
    arrival times in seconds from the start of the window."""

    prompts: tuple  # of np.ndarray int32
    budgets: tuple  # of int
    arrivals: Optional[tuple]  # of float, non-decreasing; None = closed loop


def sample_length(rng: np.random.Generator, d: dict) -> int:
    lo, hi, dist = d["min"], d["max"], d["dist"]
    if dist == "uniform":
        return int(rng.integers(lo, hi + 1))
    if dist != "lognormal":
        raise ValueError(f"unknown length distribution {dist!r}")
    # the mean of a lognormal is exp(mu + sigma^2 / 2)
    mu = math.log(d["mean"]) - d["sigma"] ** 2 / 2
    draw = int(round(rng.lognormal(mu, d["sigma"])))
    return min(max(draw, lo), hi)


def sizes(mix: dict, n: int) -> list:
    """The mix's first ``n`` (prompt length, output budget) pairs."""
    rng = np.random.default_rng([mix["shape_seed"], 1])
    return [(sample_length(rng, mix["prompt"]), sample_length(rng, mix["output"]))
            for _ in range(n)]


def arrival_gaps(mix: dict, seconds: float) -> list:
    """The mix's Poisson gaps whose running sum stays inside ``[0, seconds)``."""
    rng = np.random.default_rng([mix["shape_seed"], 2])
    gaps, t = [], 0.0
    while True:
        gap = float(rng.exponential(1.0 / mix["rate_rps"]))
        if t + gap >= seconds:
            return gaps
        t += gap
        gaps.append(gap)


def build(mix: dict, vocab: int, seed: int, seconds: float) -> Offer:
    """Requests for one run of ``seconds``: open loop, those that arrive
    inside the window; closed loop, the mix's fixed count for the window."""
    if mix["prompt"]["max"] > mix["bucket"] or mix["output"]["max"] > mix["max_new"]:
        raise ValueError("mix lengths exceed its bucket or its slot capacity")
    rng = np.random.default_rng([seed % 2 ** 64, 0x7AFF])
    if mix["loop"] == "open":
        gaps = arrival_gaps(mix, seconds)
        pairs = sizes(mix, len(gaps))
        gaps = [gaps[i] for i in rng.permutation(len(gaps))]
        pairs = [pairs[i] for i in rng.permutation(len(pairs))]
        arrivals = tuple(np.cumsum(gaps).tolist())
    elif mix["loop"] == "closed":
        pairs = sizes(mix, int(math.ceil(mix["requests_per_s"] * seconds)))
        arrivals = None
    else:
        raise ValueError(f"loop must be 'open' or 'closed', got {mix['loop']!r}")
    prompts = tuple(rng.integers(0, vocab, size=p).astype(np.int32) for p, _ in pairs)
    return Offer(prompts=prompts, budgets=tuple(b for _, b in pairs), arrivals=arrivals)
