"""Open-loop Poisson arrivals at a traffic mix's absolute rate.

A mix (``bench/traffic/<mix>.json``) gives the aggregate ``rate_hz`` of
the fleet.  Every seed gets the same work: a window of ``d`` seconds at
``r`` Hz holds ``round(r * d)`` arrivals whose gaps are the exponential
distribution's quantiles at ``(i + 1/2) / n``, scaled to fill the window
exactly; the seed only shuffles their order.  So the gaps are
exponential, as in the i.i.d. draws of ``repro.sim.scenarios``'
``PoissonArrivals``, while the count and the set of gaps stay fixed from
seed to seed: a seed changes when a message is due, never how much there
is to do, and the spread between runs is the system's, not the
generator's.

Arrivals are dealt round-robin over the source devices, so each
device's stream stays sorted and the interleaving reproduces the mix.
"""
from __future__ import annotations

from typing import List

import numpy as np


def gaps(rate_hz: float, duration_s: float) -> np.ndarray:
    """The gap set of a window: exponential quantiles summing to
    ``duration_s``."""
    if rate_hz <= 0.0 or duration_s <= 0.0:
        raise ValueError("arrivals need rate_hz > 0 and a duration > 0")
    n = max(int(round(rate_hz * duration_s)), 1)
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    return g * (duration_s / g.sum())


def arrival_times(rate_hz: float, seconds: float, seed: int,
                  lead_s: float = 0.0) -> np.ndarray:
    """Sorted absolute due times (seconds from the start of the run); the
    last is due at ``lead_s + seconds``."""
    rng = np.random.default_rng(seed)
    return lead_s + np.cumsum(rng.permutation(gaps(rate_hz, seconds)))


def arrival_plan(rate_hz: float, seconds: float, seed: int, n_devices: int,
                 lead_s: float = 0.0) -> List[np.ndarray]:
    """One sorted due-time stream per source device."""
    times = arrival_times(rate_hz, seconds, seed, lead_s)
    return [times[i::n_devices] for i in range(n_devices)]
