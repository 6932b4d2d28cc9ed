"""Seeded open-loop arrival schedules (benchmark copy of
``repro.serve.traffic``), plus a stratified Poisson schedule.

All return absolute arrival times in seconds from the schedule origin,
non-decreasing.

* ``poisson`` draws exponential gaps at ``rate``;
* ``poisson_stratified`` uses the exponential distribution's quantiles at
  (i + ½)/n as the gaps, shuffled by the seed and scaled so that the n
  arrivals fill the window exactly: the same gaps and the same number of
  arrivals for every seed, in another order;
* ``onoff`` maps a Poisson schedule onto ON bursts of ``on_s`` separated by
  ``off_s`` of silence.
"""
from __future__ import annotations

import numpy as np


def poisson(n: int, rate: float, *, seed: int) -> np.ndarray:
    if rate <= 0:
        raise ValueError("rate must be positive")
    rng = np.random.default_rng([seed, 3])
    return np.cumsum(rng.exponential(1.0 / rate, size=n))


def poisson_stratified(n: int, rate: float, *, seed: int,
                       seconds: float) -> np.ndarray:
    if rate <= 0:
        raise ValueError("rate must be positive")
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    t = np.cumsum(np.random.default_rng([seed, 3]).permutation(gaps))
    return t * (seconds / t[-1]) * (n - 0.5) / n


def onoff(n: int, rate: float, *, on_s: float, off_s: float,
          seed: int) -> np.ndarray:
    if on_s <= 0 or off_s < 0:
        raise ValueError("need on_s > 0 and off_s >= 0")
    busy = poisson(n, rate, seed=seed)
    return busy + np.floor(busy / on_s) * off_s


SCHEDULES = {"poisson": poisson, "poisson_stratified": poisson_stratified,
             "onoff": onoff}


def schedule(kind: str, seconds: float, rate: float, *, seed: int,
             **kw) -> np.ndarray:
    """Every arrival of ``kind`` due in the first ``seconds``."""
    if kind == "poisson_stratified":
        return poisson_stratified(max(int(round(rate * seconds)), 1), rate,
                                  seed=seed, seconds=seconds)
    n = int(np.ceil(rate * seconds * 1.5)) + 16
    t = SCHEDULES[kind](n, rate, seed=seed, **kw)
    return t[t < seconds]
