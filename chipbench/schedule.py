"""The open-loop request schedule of a traffic mix: one fixed cycle of
requests, replayed periodically, entered at a phase that the seed picks.

A mix file states the distributions of prompt length, output length and
gap between arrivals, and its ``cycle``: a number of requests and the
period in which they arrive.  The cycle is the ``n`` quantiles
``Q((i + 0.5) / n)`` of each distribution, interleaved once by
``order_seed`` (the same in every run), with the gaps scaled to sum to
exactly the period.  It does not depend on the length of the window: a
window of one period holds each request of the cycle once, a shorter one
a run of consecutive requests of it, a longer one the cycle again.

``--seed`` picks the phase: the window opens at request ``k`` of the
cycle.  The lead-in replays the cycle backwards from ``k - 1`` for
``lead_in_s`` seconds, so that the window opens on the periodic steady
state.  The seed also draws every token id.  A periodic stream in steady
state produces the tokens of one period in any window of one period,
whatever the phase, so the window's work does not depend on the seed.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass
class Request:
    idx: int
    due: float              # seconds from the window's opening
    prompt: np.ndarray      # int32 token ids
    max_new: int
    in_window: bool


def quantiles(spec: dict, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of one distribution of a mix file."""
    u = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
        return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)
    if dist == "exponential":
        return -np.log1p(-u)                     # unit mean
    raise ValueError(f"unknown distribution {dist!r}")


def cycle(mix: dict):
    """(prompt lengths, output lengths, gap after each request) of the
    cycle; the gaps sum to its period."""
    n, period = mix["cycle"]["requests"], mix["cycle"]["period_s"]
    rng = np.random.default_rng(mix["order_seed"])
    p = rng.permutation(quantiles(mix["prompt_tokens"], n))
    o = rng.permutation(quantiles(mix["output_tokens"], n))
    g = rng.permutation(quantiles(mix["gap_s"], n))
    return p, o, g * (period / g.sum())


def build(mix: dict, seconds: float, seed: int, vocab: int,
          length_scale: float = 1.0) -> list[Request]:
    """Every request of a run, lead-in first, in order of due time."""
    p, o, g = cycle(mix)
    n, period = len(p), mix["cycle"]["period_s"]
    rng = np.random.default_rng(seed)
    k = int(rng.integers(n))
    order = [(k + j) % n for j in range(n)]
    offset = np.concatenate([[0.0], np.cumsum(g[order])[:-1]])
    slots = []                                   # (due, cycle index, window)
    for m in range(int(np.ceil(seconds / period))):
        slots += [(m * period + t, i, True) for t, i in zip(offset, order)
                  if m * period + t < seconds]
    t, j = 0.0, 0
    while True:
        i = (k - 1 - j) % n
        t -= g[i]
        if t < -mix["lead_in_s"]:
            break
        slots.append((t, i, False))
        j += 1
    slots.sort()
    reqs = []
    for due, i, in_window in slots:
        plen = max(1, int(round(p[i] * length_scale)))
        reqs.append(Request(
            idx=len(reqs), due=float(due),
            prompt=rng.integers(1, vocab, size=plen, dtype=np.int64)
            .astype(np.int32),
            max_new=max(1, int(round(o[i] * length_scale))),
            in_window=in_window))
    return reqs
