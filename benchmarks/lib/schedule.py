"""Arrival schedules, from the seed. Imports nothing of the program."""

from __future__ import annotations

import numpy as np


def open_loop(seed: int, rate: float, lead_in: float, seconds: float
              ) -> list[float]:
    """Due times (seconds after "go") of an open loop: a Poisson process
    *conditioned on its count*. The window holds exactly ``round(rate *
    seconds)`` arrivals and the lead-in ``round(rate * lead_in)``, at sorted
    uniform times (which is what a Poisson process is, given its count): the
    offered load is the same number for every seed, and only the gaps differ.
    """
    rng = np.random.default_rng([int(seed), 0xA7])
    n_lead, n_win = round(rate * lead_in), round(rate * seconds)
    lead = np.sort(rng.uniform(0.0, lead_in, n_lead))
    win = np.sort(rng.uniform(lead_in, lead_in + seconds, n_win))
    return [float(t) for t in np.concatenate([lead, win])]


def closed_loop_starts(clients: int, lead_in: float) -> list[float]:
    """When each client of a closed loop sends its first request: one after
    another over the lead-in, so that no burst meets the listen queue."""
    return [lead_in * k / max(clients, 1) for k in range(clients)]
