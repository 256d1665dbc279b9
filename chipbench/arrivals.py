"""Open-loop arrival times and zipf popularity, drawn from a run's seed.

Copies of the program's ``scenarios/arrivals.py`` generators (Poisson and
the two-state MMPP), so that a change to the program cannot change the
benchmark's traffic.
"""
from __future__ import annotations

import numpy as np


def poisson(rate: float, horizon_s: float, rng: np.random.Generator) -> np.ndarray:
    """Sorted arrival offsets in ``[0, horizon_s)``: exponential gaps."""
    n = max(8, int(rate * horizon_s * 1.5) + 8)
    t = np.cumsum(rng.exponential(1.0 / rate, size=n))
    while t[-1] < horizon_s:
        t = np.concatenate([t, t[-1] + np.cumsum(rng.exponential(1.0 / rate, size=n))])
    return t[t < horizon_s]


def mmpp(
    rate: float,
    horizon_s: float,
    rng: np.random.Generator,
    *,
    burst_factor: float = 8.0,
    burst_fraction: float = 0.15,
    mean_burst_s: float = 1.5,
) -> np.ndarray:
    """Two-state Markov-modulated Poisson arrivals with mean rate ``rate``."""
    mean_quiet = mean_burst_s * (1.0 - burst_fraction) / burst_fraction
    quiet = rate / (burst_fraction * burst_factor + (1.0 - burst_fraction))
    times, t = [], 0.0
    bursting = rng.random() < burst_fraction
    while t < horizon_s:
        end = min(t + rng.exponential(mean_burst_s if bursting else mean_quiet), horizon_s)
        n = rng.poisson((burst_factor * quiet if bursting else quiet) * (end - t))
        if n:
            times.append(t + np.sort(rng.random(n)) * (end - t))
        t, bursting = end, not bursting
    return np.concatenate(times) if times else np.empty(0)


PROCESSES = {"poisson": poisson, "mmpp": mmpp}


def zipf_items(n: int, count: int, skew: float, rng: np.random.Generator) -> np.ndarray:
    """``count`` draws from Zipf(``skew``) over ``n`` items in a random
    rank order."""
    w = np.arange(1, n + 1, dtype=np.float64) ** (-skew)
    rank_order = rng.permutation(n)
    return rank_order[rng.choice(n, size=count, p=w / w.sum())]
