"""Open-loop request schedule, the one generator every serving mix uses.

A mix file gives the arrival rate, the arrival kind (``poisson``, the one
there is), and lognormal prompt and output lengths (median, sigma, clip
range).  Every seed gets the same set of gaps and
lengths, drawn as evenly spaced quantiles of those distributions; the seed
orders them and draws the token ids.  So two seeds offer the same work in
another order, and the number of requests due in a window is
``rate x seconds`` on every seed.

The window's requests are followed by a tail drawn the same way, which
keeps the load on while the window's last requests finish; the tail is
not counted."""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Planned:
    offset_s: float          # due time after the window opens
    prompt: np.ndarray       # (P,) int32
    max_tokens: int
    counted: bool            # due inside the window


def _lognormal(n: int, spec: Dict, rng: np.random.Generator) -> np.ndarray:
    nd = NormalDist()
    q = [nd.inv_cdf((i + 0.5) / n) for i in range(n)]
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * np.asarray(q))
    x = np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    return rng.permutation(x)


def _gaps(n: int, span_s: float, mix: Dict,
          rng: np.random.Generator) -> np.ndarray:
    """n due times in [0, span_s): exponential quantiles between
    arrivals, scaled to fill the span exactly."""
    if mix["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    g = -np.log1p(-(np.arange(n) + 0.5) / n)
    g = rng.permutation(g)
    g = g / g.sum() * span_s
    return np.concatenate([[0.0], np.cumsum(g)[:-1]])


def _block(n: int, span_s: float, start: float, mix: Dict, vocab: int,
           rng: np.random.Generator, counted: bool) -> List[Planned]:
    times = start + _gaps(n, span_s, mix, rng)
    plens = _lognormal(n, mix["prompt"], rng)
    outs = _lognormal(n, mix["output"], rng)
    return [Planned(float(t), rng.integers(0, vocab, size=int(p),
                                           dtype=np.int32), int(o), counted)
            for t, p, o in zip(times, plens, outs)]


def schedule(mix: Dict, seed: int, seconds: float,
             vocab: int) -> List[Planned]:
    """The window's requests, then the uncounted tail."""
    rng = np.random.default_rng([int(seed), 0x5e4e])
    rate = float(mix["rate_rps"])
    n = max(1, int(round(rate * seconds)))
    tail_s = float(mix.get("tail_s", 60.0))
    m = max(1, int(round(rate * tail_s)))
    return (_block(n, seconds, 0.0, mix, vocab, rng, True)
            + _block(m, tail_s, seconds, mix, vocab, rng, False))

