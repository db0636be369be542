"""Open-loop request schedules, generated from a traffic file and a seed.

A traffic file (``bench/traffic/<name>.json``) gives the parameters: the
arrival rate, a lognormal law for prompt and output lengths with its
clipping range, and a grid that prompt lengths snap to.  The schedule holds
``round(rate * seconds)`` requests.  The *set* of inter-arrival gaps,
prompt lengths and output lengths is fixed by the parameters alone: each is
the law's quantiles at ``(i + 0.5) / n``.  The seed only shuffles each set,
independently, and draws the prompt tokens.  So every seed asks for the
same work, in another order, and the spread between seeds is that of the
system and not of the draw.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Sequence

import numpy as np

from bench.harness.seeds import rng_for


@dataclass(frozen=True)
class Due:
    """One request of the schedule: due ``due_s`` after the window opens."""
    index: int
    due_s: float
    prompt_len: int
    out_len: int


def lognormal_quantiles(n: int, median: float, sigma: float,
                        lo: float, hi: float) -> List[float]:
    """``n`` quantiles of a lognormal law, clipped to ``[lo, hi]``."""
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        out.append(min(max(median * math.exp(sigma * z), lo), hi))
    return out


def exponential_quantiles(n: int, rate: float) -> List[float]:
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def snap(value: float, grid: Sequence[int]) -> int:
    """The grid point nearest ``value`` on a log scale."""
    return min(grid, key=lambda g: abs(math.log(g) - math.log(value)))


def schedule(traffic: dict, seed: int, seconds: float) -> List[Due]:
    """The cell's schedule for one window of ``seconds``."""
    rate = float(traffic["rate_per_s"])
    n = max(1, round(rate * seconds))
    p, o = traffic["prompt"], traffic["output"]
    grid = sorted(int(g) for g in p["grid"])
    prompts = [snap(v, grid) for v in lognormal_quantiles(
        n, p["median"], p["sigma"], grid[0], grid[-1])]
    outputs = [int(round(v)) for v in lognormal_quantiles(
        n, o["median"], o["sigma"], o["min"], o["max"])]
    gaps = exponential_quantiles(n, rate)
    cache_len = int(traffic["cache_len"])
    order = [rng_for(seed, k).permutation(n) for k in range(3)]
    prompts = [prompts[i] for i in order[0]]
    outputs = [outputs[i] for i in order[1]]
    gaps = [gaps[i] for i in order[2]]
    out, t = [], 0.0
    for i in range(n):
        # the first gap runs from the window's start, so the window opens
        # on an arrival no more often than a Poisson process would
        t += gaps[i]
        if prompts[i] + outputs[i] > cache_len:
            raise ValueError(
                f"traffic asks for {prompts[i]} + {outputs[i]} tokens, more "
                f"than cache_len {cache_len}")
        out.append(Due(i, t, prompts[i], outputs[i]))
    return out


def prompt_lengths(traffic: dict) -> List[int]:
    """Every prompt length the traffic can send: the shapes to warm up."""
    return sorted(int(g) for g in traffic["prompt"]["grid"])


def prompt_tokens(seed: int, sched: Sequence[Due], vocab: int
                  ) -> List[np.ndarray]:
    """Prompt token ids for each request, uniform over the vocabulary."""
    rng = rng_for(seed, 3)
    return [rng.integers(0, vocab, d.prompt_len, dtype=np.int32)
            for d in sched]
