"""Open-loop arrival schedules from a traffic file's parameters.

Independent callers: arrivals keep their own clock, whatever the server
does. For a window of ``seconds`` at ``rate_per_s`` the schedule holds
exactly round(rate * seconds) arrivals at uniformly drawn offsets, which
is a Poisson process conditioned on its count, so every seed offers the
same number of requests in another order. ``burst`` ({"every_s",
"size"}) adds that many simultaneous arrivals at each multiple of
``every_s``. Each arrival names a row of the request pool. Adapted from
``benchmarks/bench_overload._arrivals``.
"""
from __future__ import annotations

import numpy as np


def schedule(traffic: dict, seconds: float, seed: int, pool: int):
    """(offsets_s (N,) sorted, rows (N,) int) for one window."""
    rng = np.random.default_rng([abs(int(seed)), 2])
    n = int(round(float(traffic["rate_per_s"]) * seconds))
    offs = np.sort(rng.uniform(0.0, seconds, size=n))
    burst = traffic.get("burst")
    if burst:
        spikes = np.repeat(np.arange(burst["every_s"], seconds,
                                     burst["every_s"]), int(burst["size"]))
        offs = np.sort(np.concatenate([offs, spikes]))
    rows = rng.integers(0, pool, size=len(offs))
    return offs, rows
