"""Traffic generation from a seed: open-loop arrival schedules and the
host-side request pools that clients send.

Every seed gets the same amount of work: the same number of requests, the
same count of each request shape and the same set of inter-arrival gaps
(quantiles of the exponential distribution at the mix's rate, so arrivals
are Poisson-like).  The seed only permutes them.  Runs with different
seeds then differ by what the order does to the system, not by how much
they ask of it.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one named stream of a run's seed (any non-negative
    whole number, wider than 32 bits included)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


@dataclasses.dataclass(frozen=True)
class Arrival:
    index: int
    at_s: float          # scheduled time from the start of the window
    label: str           # request shape label (a key of the config's shapes)


def split_counts(shares: dict[str, float], n: int) -> dict[str, int]:
    """``n`` requests split by ``shares`` (largest remainder), every label
    with a positive share getting at least one where ``n`` allows."""
    total = sum(shares.values())
    exact = {k: n * v / total for k, v in shares.items()}
    counts = {k: int(math.floor(x)) for k, x in exact.items()}
    order = sorted(exact, key=lambda k: (exact[k] - counts[k], k), reverse=True)
    for k in order[: n - sum(counts.values())]:
        counts[k] += 1
    return counts


def open_loop(rate_per_s: float, seconds: float, shares: dict[str, float],
              seed: int) -> list[Arrival]:
    """Arrivals due in ``[0, seconds)``: ``round(rate * seconds)`` requests
    whose gaps are the exponential quantiles at ``rate_per_s``, rescaled
    to fill the window, in an order drawn from the seed."""
    n = max(int(round(rate_per_s * seconds)), 1)
    rng = rng_for(seed, 1)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = rng.permutation(gaps) * (seconds / gaps.sum())
    starts = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    labels = [k for k, c in sorted(split_counts(shares, n).items())
              for _ in range(c)]
    labels = [labels[i] for i in rng.permutation(n)]
    return [Arrival(i, float(t), lab)
            for i, (t, lab) in enumerate(zip(starts, labels))]


def checked_sample(arrivals: list[Arrival], count: int, seed: int,
                   largest: str) -> list[int]:
    """Indices of the requests whose answers are compared: one request of
    every label (so the largest shape is always in), the rest drawn
    uniformly, all from the seed."""
    rng = rng_for(seed, 2)
    picked = set()
    for label in sorted({a.label for a in arrivals}, key=lambda l: l != largest):
        idx = [a.index for a in arrivals if a.label == label]
        picked.add(int(idx[rng.integers(len(idx))]))
    rest = [a.index for a in arrivals if a.index not in picked]
    extra = max(min(count - len(picked), len(rest)), 0)
    picked.update(int(i) for i in rng.choice(rest, size=extra, replace=False))
    return sorted(picked)


def request_pool(shapes: dict[str, tuple[int, ...]], per_shape: int,
                 seed: int) -> dict[str, list[np.ndarray]]:
    """``per_shape`` distinct uniform [0, 1) float32 grids of every shape,
    made on the host from the seed (clients send host arrays)."""
    pool = {}
    for j, label in enumerate(sorted(shapes)):
        rng = rng_for(seed, 3, j)
        pool[label] = [rng.random(tuple(shapes[label]), dtype=np.float32)
                       for _ in range(per_shape)]
    return pool
