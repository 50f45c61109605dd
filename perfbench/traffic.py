"""The yardstick's own arrival process and latency arithmetic.

One general generator reads a traffic file's parameters.  For an open loop
they are ``rate_rps`` and, optionally, ``phases``: a list of
``{"seconds": s, "rate_x": m}`` repeated to the end of the window, so that a
bursty mix is a data file and no code.

Every seed gets the SAME set of gaps between arrivals, in another order: the
gaps of a phase are the quantiles of the exponential distribution of its
rate (a Poisson process's gaps), scaled to fill the phase exactly, and the
seed only permutes them.  So the number of requests, their mean rate and the
sizes of the bursts are equal in every run of a cell, and what differs is
where in the window the bursts fall.  A latency runs from the request's DUE
time on this schedule, never from the time it was actually sent: a generator
that falls behind lengthens the latencies and shows in ``late_ms``.
"""
from __future__ import annotations

import math

import numpy as np


def schedule(params: dict, seed: int, seconds: float) -> np.ndarray:
    """Due times in seconds from the start of the window, ascending, all
    below ``seconds``.  A function of the parameters, the seed and the
    window alone."""
    rate = float(params["rate_rps"])
    if rate <= 0 or seconds <= 0:
        raise ValueError("rate_rps and seconds must be positive")
    phases = params.get("phases") or [{"seconds": seconds, "rate_x": 1.0}]
    rng = np.random.default_rng(int(seed))
    due, t0, i = [], 0.0, 0
    while t0 < seconds:
        phase = phases[i % len(phases)]
        t1 = min(seconds, t0 + float(phase["seconds"]))
        n = int(round(rate * float(phase["rate_x"]) * (t1 - t0)))
        if n > 0:
            gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
            gaps *= (t1 - t0) / gaps.sum() * n / (n + 0.5)
            due.append(t0 + np.cumsum(rng.permutation(gaps)))
        t0, i = t1, i + 1
    out = np.concatenate(due) if due else np.zeros(0)
    return out[out < seconds]


def latencies_ms(due_s, done_s) -> np.ndarray:
    """Completion minus DUE time, in ms, for the requests that completed."""
    return 1e3 * (np.asarray(done_s, float) - np.asarray(due_s, float))


def late_ms(due_s, submitted_s) -> np.ndarray:
    """How late the generator sent each request, in ms (never negative)."""
    return 1e3 * np.maximum(
        np.asarray(submitted_s, float) - np.asarray(due_s, float), 0.0)


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the two
    closest ranks; written out so that no library's default decides it."""
    v = sorted(float(x) for x in values)
    if not v:
        raise ValueError("percentile of no values")
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)
