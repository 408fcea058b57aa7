"""Seeded traffic: prompt and output lengths, arrival times, token ids.

A mix (``traffic/<name>.json``) gives each length as a lognormal with a
median, a shape ``sigma`` and clip bounds, and arrivals as a Poisson
process at the rate the cell fixes.  The lengths and the gaps between
arrivals are taken at evenly spaced quantiles of their distributions, so
every seed sends the same multiset of sizes and gaps.  The seed draws the
order of the prompt and output lengths and the token ids; the order of
the gaps is one draw shared by every seed, so all runs of a cell see the
same arrival times.  Runs with different seeds then do the same work at
the same moments, and differ only in which request arrives when: with a
few dozen requests in a window, a 95th percentile of TTFT moved more
with the bursts a seed drew than with anything the server did.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np


# the one draw of the order of the gaps between arrivals
ARRIVALS_SEED = 0


@dataclass(frozen=True)
class Arrival:
    due_s: float          # seconds after the schedule starts
    prompt_len: int
    output_len: int


def quantile_points(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(n: int, spec: dict) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of a clipped lognormal.

    ``spec``: ``median``, ``sigma``, ``min``, ``max``.
    """
    z = np.array([NormalDist().inv_cdf(q) for q in quantile_points(n)])
    raw = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(n: int, rate: float) -> np.ndarray:
    """``n`` gaps at evenly spaced quantiles of Exp(rate)."""
    return -np.log1p(-quantile_points(n)) / rate


def rng(seed: int, stream: str) -> np.random.Generator:
    """A generator for one named use of the seed; any whole number works."""
    tag = int.from_bytes(stream.encode(), "little")
    return np.random.default_rng([int(seed) & (2**64 - 1), tag])


def schedule(mix: dict, rate: float, n: int, seed: int) -> List[Arrival]:
    """``n`` open-loop arrivals of ``mix`` at ``rate`` requests/s.

    The first request is due at 0.
    """
    r = rng(seed, "schedule")
    prompts = r.permutation(lognormal_lengths(n, mix["prompt"]))
    outputs = r.permutation(lognormal_lengths(n, mix["output"]))
    gaps = rng(ARRIVALS_SEED, "arrivals").permutation(
        exponential_gaps(n, rate))
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    return [Arrival(float(t), int(p), int(o))
            for t, p, o in zip(due, prompts, outputs)]


def token_ids(seed: int, stream: str, n: int, vocab_size: int) -> np.ndarray:
    """``n`` token ids drawn uniformly from the vocabulary."""
    return rng(seed, stream).integers(0, vocab_size, n, dtype=np.int64) \
        .astype(np.int32)
