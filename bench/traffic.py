"""Arrival schedules for a mix, drawn from the run's seed.

``PoissonProcess`` and ``OnOffProcess`` are copies of the system's
seeded arrival processes (``repro.serving.traffic``), kept here so the
benchmark's traffic cannot change with the program.

Every seed gets the same amount of work: :func:`open_loop_schedule`
draws ``n + 1`` gaps from the mix's process and scales the first ``n``
arrival times into the window.  For a Poisson process that is exactly
the process conditioned on ``n`` arrivals in the window (the arrival
times are then ``n`` sorted uniform draws), so seeds change the order
of arrivals and the questions asked, never how many there are.
"""
from __future__ import annotations

from typing import Dict, Iterator, List

import numpy as np


class PoissonProcess:
    """Exponential inter-arrival times at ``rate`` requests/second."""

    def __init__(self, rate: float, *, seed: int = 0):
        if rate <= 0:
            raise ValueError(f"rate must be > 0, got {rate}")
        self.rate = float(rate)
        self.rng = np.random.default_rng(seed)

    def inter_arrivals(self) -> Iterator[float]:
        while True:
            yield float(self.rng.exponential(1.0 / self.rate))


class OnOffProcess:
    """Interrupted Poisson: ON phases arriving at ``burst_rate``, OFF
    phases of silence, both of exponential length; the mean rate is
    ``burst_rate * on_s / (on_s + off_s)``."""

    def __init__(self, burst_rate: float, *, on_s: float = 0.5,
                 off_s: float = 0.5, seed: int = 0):
        if burst_rate <= 0:
            raise ValueError(f"burst_rate must be > 0, got {burst_rate}")
        self.burst_rate = float(burst_rate)
        self.on_s = float(on_s)
        self.off_s = float(off_s)
        self.rng = np.random.default_rng(seed)

    @property
    def mean_rate(self) -> float:
        return self.burst_rate * self.on_s / (self.on_s + self.off_s)

    def inter_arrivals(self) -> Iterator[float]:
        while True:
            phase = float(self.rng.exponential(self.on_s))
            t = 0.0
            while True:
                gap = float(self.rng.exponential(1.0 / self.burst_rate))
                if t + gap > phase:
                    break
                t += gap
                yield gap
            yield (phase - t) + float(self.rng.exponential(self.off_s))


def make_process(arrivals: Dict, rng: np.random.Generator):
    """The mix's ``arrivals`` object -> an arrival process."""
    seed = int(rng.integers(0, 2**63 - 1))
    kind = arrivals["process"]
    if kind == "poisson":
        return PoissonProcess(float(arrivals["rate"]), seed=seed)
    if kind == "onoff":
        return OnOffProcess(float(arrivals["burst_rate"]),
                            on_s=float(arrivals["on_s"]),
                            off_s=float(arrivals["off_s"]), seed=seed)
    raise ValueError(f"unknown arrival process {kind!r}")


def mean_rate(arrivals: Dict) -> float:
    if arrivals["process"] == "poisson":
        return float(arrivals["rate"])
    a = arrivals
    return float(a["burst_rate"]) * a["on_s"] / (a["on_s"] + a["off_s"])


def open_loop_schedule(arrivals: Dict, seconds: float,
                       rng: np.random.Generator) -> List[float]:
    """Arrival offsets (s) in ``[0, seconds)``: ``round(rate *
    seconds)`` of them whatever the seed.  Poisson gaps are independent,
    so every seed gets the same gaps, drawn from the mix's
    ``gap_seed``, in an order of its own; other processes draw theirs
    from the run's seed (their order is their burst structure)."""
    n = max(1, int(round(mean_rate(arrivals) * seconds)))
    poisson = arrivals["process"] == "poisson"
    src = (np.random.default_rng(int(arrivals["gap_seed"])) if poisson
           else rng)
    gaps = make_process(arrivals, src).inter_arrivals()
    drawn = np.array([next(gaps) for _ in range(n + 1)])
    t = np.cumsum(rng.permutation(drawn) if poisson else drawn)
    return list(t[:n] * (seconds / t[n]))
