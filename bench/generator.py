"""The one traffic generator: reads a mix's parameters and draws requests
from ``--seed``.

A mix file (``bench/traffic/<mix>.json``) states

* ``loop``: ``"closed"`` (``clients`` callers, each sending its next
  request when the last one ends) or ``"open"`` (Poisson arrivals at
  ``rate_per_s``, on the wall clock, whatever the server does);
* ``prompt`` and ``output``: length distributions, each one of
  ``{"dist": "loguniform", "lo", "hi"}``, ``{"dist": "uniform", "lo",
  "hi"}`` or ``{"dist": "lognormal", "median", "sigma", "lo", "hi"}``
  (clipped to ``[lo, hi]``);
* ``round``: how many requests share one set of lengths (below);
* ``trace``: the stretch of the window a ``--trace 1`` run records (its
  last ``seconds``; read by ``bench/harness.py``);
* ``who``: which users send such traffic.

Every seed gets the same set of sizes and arrivals in another order:
requests come in rounds of ``round``; in each round the prompt lengths,
the output lengths and (open loop) the gaps between arrivals are the
distribution's quantiles at the midpoints ``(i + 0.5) / round``, each
list shuffled by the seed on its own. So a window sees the same mix of
work on every seed, and the seed changes only the order, the pairing of
prompt and output lengths, and the token ids (uniform over the
vocabulary).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Iterator, List

import numpy as np


@dataclasses.dataclass
class Request:
    prompt: List[int]
    max_new: int
    due_s: float            # open loop: seconds after the window opens


def quantile(dist: Dict, u: np.ndarray) -> np.ndarray:
    """Lengths at probabilities ``u`` (whole tokens, within [lo, hi])."""
    kind, lo, hi = dist["dist"], dist["lo"], dist["hi"]
    if kind == "uniform":
        x = lo + u * (hi + 1 - lo) - 0.5
    elif kind == "loguniform":
        x = lo * np.exp(u * math.log(hi / lo))
    elif kind == "lognormal":
        from statistics import NormalDist
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), lo, hi).astype(int)


def requests(mix: Dict, seed: int, vocab: int) -> Iterator[Request]:
    """Endless request stream of ``mix`` for ``seed``."""
    rng = np.random.default_rng(seed)
    n = int(mix["round"])
    u = (np.arange(n) + 0.5) / n
    prompts, outputs = quantile(mix["prompt"], u), quantile(mix["output"], u)
    gaps = (-np.log1p(-u) / mix["rate_per_s"]) if mix["loop"] == "open" \
        else np.zeros(n)
    due = 0.0
    while True:
        p, o, g = rng.permutation(prompts), rng.permutation(outputs), \
            rng.permutation(gaps)
        for i in range(n):
            yield Request(rng.integers(0, vocab, int(p[i])).tolist(),
                          int(o[i]), due)
            due += float(g[i])
