"""Open-loop arrival schedules, drawn from the run's seed.

A traffic file (traffic/<name>.json) gives the shape and the total
rate; `schedule` turns them into every request's due time and model.
The arrivals are Poisson, as the program's `OpenLoopClient` draws them, but conditioned on
their count: the window holds exactly round(rate x seconds) requests, each
due at a uniform time in it (a Poisson process given its count), and the
copies share them evenly. So every seed sends the same work in another
order, and two seeds differ only by the system's own noise.
"""
from __future__ import annotations

import numpy as np


def _phase(rng, n: int, start: float, length: float, n_models: int):
    times = np.sort(start + rng.random(n) * length)
    models = rng.permutation(np.arange(n) % n_models)
    return times, models


def schedule(seed: int, rate: float, warm_s: float, window_s: float,
             n_models: int, popularity: str = "uniform"):
    """[(due offset from the start of warm-up in seconds, model index,
    in the window)] in due order: warm-up traffic, then the window's."""
    if popularity != "uniform":
        raise ValueError(f"unknown popularity {popularity!r}")
    rng = np.random.default_rng(seed)
    out = []
    for start, length, window in ((0.0, warm_s, False),
                                  (warm_s, window_s, True)):
        n = int(round(rate * length))
        times, models = _phase(rng, n, start, length, n_models)
        out += [(float(t), int(m), window) for t, m in zip(times, models)]
    return out


def rate_of(traffic: dict) -> float:
    """The cell's total offered rate (requests/s), a number the traffic
    file takes from the sweep on the chip that PERF.md records."""
    return float(traffic["rate_rps"])
