"""The one traffic generator.  A traffic mix is a JSON file of parameters
under ``perfbench/traffic/``; everything is drawn from ``--seed``.

Keys of a mix:

* ``arrival``: ``{"kind": "closed", "requests": N}`` — N requests queued at
  once (an offline batch that keeps every freed lane busy); or
  ``{"kind": "poisson", "rate_per_s": r}`` — an open loop: the window of
  ``seconds`` gets ``round(r * seconds)`` arrivals, placed as a Poisson
  process conditioned on that count (sorted uniform due times).
* ``prompt_len``, ``max_new``: a length distribution, ``{"dist":
  "lognormal", "median": m, "sigma": s, "min": lo, "max": hi}`` or ``{"dist":
  "loguniform", "min": lo, "max": hi}``, clipped to [min, max].
* ``width``: chains per request (they share the prompt and its budget).
* ``eos``: whether requests stop at an end token (``null`` = never).

Lengths are stratified and their order fixed: the n requests take the n
quantile midpoints of each distribution, in one order drawn once
(``SIZE_ORDER_SEED``), so every seed serves the same work in the same order
and only its arrivals (open mixes) and token ids differ.  With the order
drawn from the run's seed, which requests finish inside a closed mix's
window, and so the tokens it emits, changed from seed to seed.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Optional

import numpy as np

FIRST_TOKEN = 3          # ids below are kept for special tokens
SIZE_ORDER_SEED = 0


@dataclass
class Req:
    uid: int
    due_s: float             # offset from the window's start (0 when closed)
    prompt: np.ndarray       # (P,) int32
    max_new: int
    width: int


def _quantiles(d: dict, n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    lo, hi = float(d["min"]), float(d["max"])
    if d["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = float(d["median"]) * np.exp(float(d["sigma"]) * z)
    elif d["dist"] == "loguniform":
        x = np.exp(np.log(lo) + u * (np.log(hi) - np.log(lo)))
    else:
        raise ValueError(f"unknown length distribution {d['dist']!r}")
    return np.clip(np.round(x), lo, hi).astype(np.int64)


def count(mix: dict, seconds: float) -> int:
    """Requests in a run of ``seconds``."""
    a = mix["arrival"]
    if a["kind"] == "closed":
        return int(a["requests"])
    if a["kind"] == "poisson":
        return int(round(float(a["rate_per_s"]) * seconds))
    raise ValueError(f"unknown arrival kind {a['kind']!r}")


def _due(mix: dict, n: int, seconds: float,
         rng: np.random.Generator) -> np.ndarray:
    if mix["arrival"]["kind"] == "closed":
        return np.zeros(n)
    return np.sort(rng.uniform(0.0, seconds, n))


def generate(mix: dict, seed: int, vocab: int, seconds: float,
             max_len: Optional[int] = None) -> List[Req]:
    """The requests of one run, sorted by due time."""
    n = count(mix, seconds)
    order = np.random.default_rng(SIZE_ORDER_SEED)
    plen = order.permutation(_quantiles(mix["prompt_len"], n))
    new = order.permutation(_quantiles(mix["max_new"], n))
    rng = np.random.default_rng(seed)
    due = _due(mix, n, seconds, rng)
    width = int(mix.get("width", 1))
    out = []
    for i in range(n):
        p = int(plen[i])
        if max_len is not None and p + int(new[i]) > max_len:
            raise ValueError(f"request {i}: {p} + {int(new[i])} tokens exceed "
                             f"max_len {max_len}")
        prompt = rng.integers(FIRST_TOKEN, vocab, size=(p,))
        out.append(Req(uid=i, due_s=float(due[i]),
                       prompt=prompt.astype(np.int32), max_new=int(new[i]),
                       width=width))
    return out
