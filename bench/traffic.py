"""The one traffic generator: every mix is a data file of parameters.

A mix file (``traffic/<mix>.json``) names its ``arrivals`` and sizes:

    backlog   offline scoring: the queue is kept at ``queue_per_slot``
              requests per slot for the whole window
    poisson   open loop at ``rate_per_s``: independent users

Every request is a prompt of ``prompt_len`` uniform random token ids.
Request ``i`` of a run draws its ids from ``(seed, i)``, so the reference
can rebuild any prompt after the window.

Open-loop schedules give every seed the same set of gaps in another
order: the gaps are the quantiles of the exponential distribution at the
mix's rate, shuffled by the seed. A seed then changes which request
waits behind which, never how much work the window holds. (The Poisson
process follows ``launch/workload.py``'s ``poisson_trace``, moved onto
the wall clock.)
"""
from __future__ import annotations

import numpy as np

ARRIVALS = ("backlog", "poisson")


def check(mix: dict) -> None:
    if mix["arrivals"] not in ARRIVALS:
        raise SystemExit(f"bench: arrivals {mix['arrivals']!r} is not one of "
                         f"{ARRIVALS}")


def prompt(mix: dict, vocab: int, seed: int, i: int) -> np.ndarray:
    """Token ids of request ``i``."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, i])
    return rng.integers(0, vocab, size=(int(mix["prompt_len"]),),
                        dtype=np.int32)


def schedule(mix: dict, seed: int, seconds: float) -> np.ndarray:
    """Due times in seconds from the window's start, for open-loop mixes
    (requests due after ``seconds`` are dropped)."""
    rate = float(mix["rate_per_s"])
    n = int(np.ceil(seconds * rate)) + 1
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0x5EED])
    t = np.cumsum(rng.permutation(gaps))
    return t[t < seconds]
