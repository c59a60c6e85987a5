"""Open-loop load: arrival schedules, due-time latency and percentiles.

The benchmark's own copy of the percentile arithmetic (numpy's linear
interpolation, as the program's latency recorder uses) and of the Poisson
arrival generator, so that later changes to the program cannot move the
yardstick.

Each request is timed from when it was DUE, not from when the generator
got round to submitting it, so a stall delays every request behind it in
the latency, and the generator's own lateness (`lag`) is reported beside.
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time
from typing import Callable

import numpy as np


def percentile(values, q: float) -> float:
    """The q-th percentile (0–100), linearly interpolated."""
    v = np.asarray(values, dtype=np.float64)
    if v.size == 0:
        return math.nan
    return float(np.percentile(v, q))


def poisson_arrivals(rate: float, seconds: float,
                     rng: np.random.Generator) -> np.ndarray:
    """Arrival offsets [n] in [0, seconds) of a Poisson process of `rate`
    per second, conditioned on n = round(rate·seconds) arrivals: sorted
    uniform times. Every seed then offers the same number of requests,
    at different instants."""
    n = int(round(rate * seconds))
    return np.sort(rng.uniform(0.0, seconds, size=n))


@dataclasses.dataclass
class Request:
    """One open-loop request: when it was due, when it was submitted and
    when its answer was complete (nan until then)."""

    due: float
    submitted: float = math.nan
    done: float = math.nan


class OpenLoop:
    """Drive `submit(i, due)` at each due time, on the calling thread.

    `clock` and `sleep` are injectable (tests drive a fake clock).
    """

    def __init__(self, clock: Callable[[], float] = time.perf_counter,
                 sleep: Callable[[float], None] = time.sleep):
        self.clock = clock
        self.sleep = sleep
        self.requests: list[Request] = []
        self._lock = threading.Lock()

    def run(self, offsets, submit: Callable[[int, float], None]) -> float:
        """Submit request i at t0 + offsets[i]; returns t0."""
        t0 = self.clock()
        self.requests = [Request(due=t0 + float(o)) for o in offsets]
        for i, req in enumerate(self.requests):
            wait = req.due - self.clock()
            if wait > 0:
                self.sleep(wait)
            submit(i, req.due)
            req.submitted = self.clock()
        return t0

    def complete(self, i: int, t: float | None = None) -> None:
        """Mark request i answered (thread-safe)."""
        t = self.clock() if t is None else t
        with self._lock:
            self.requests[i].done = t

    def pending(self) -> int:
        with self._lock:
            return sum(1 for r in self.requests if math.isnan(r.done))

    def latencies(self) -> np.ndarray:
        """done − due of every answered request (seconds)."""
        return np.array([r.done - r.due for r in self.requests
                         if not math.isnan(r.done)])

    def lags(self) -> np.ndarray:
        """submitted − due of every request (seconds)."""
        return np.array([r.submitted - r.due for r in self.requests])
