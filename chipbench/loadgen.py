"""The one load generator: arrival schedules and latency arithmetic.

A traffic mix is a data file under ``traffic/`` that this module reads:

``loop``
    ``"closed"``: ``clients`` callers, each sending its next request when
    the last one returned.  ``"open"``: requests due on a schedule fixed
    before the window, whatever the server does.
``phases`` (open loop)
    A list of ``{"seconds": s, "rate": r}`` repeated over the window:
    a constant ``rate`` is one phase, an on/off burst two.  Each phase
    holds exactly ``round(rate * seconds)`` arrivals, uniform within it,
    so every seed offers the same number of requests and only their
    order in time changes.
``precision``, ``target_batch``, ``max_wait_s``, ``pool``
    The served bucket's precision and batch, the server's wait-or-flush
    deadline, and how many distinct inputs the requests cycle through.

Latency runs from a request's due time to its completion.  In the open
loop the due time is the schedule's, so a stall that delays sending
counts against every request behind it; in the closed loop it is the send.
A request that failed, was shed, was served below the top rung, or never
returned is missing: it counts as the longest wait of the window.
"""

from __future__ import annotations

import math

import numpy as np


def open_schedule(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Sorted due offsets (seconds from the window's start)."""
    rng = np.random.default_rng(int(seed) % 2 ** 64)
    phases = traffic["phases"]
    period = sum(p["seconds"] for p in phases)
    out, t0 = [], 0.0
    while t0 < seconds:
        for p in phases:
            span = min(p["seconds"], seconds - t0)
            if span <= 0:
                break
            n = int(round(p["rate"] * span))
            out.append(t0 + np.sort(rng.uniform(0.0, span, n)))
            t0 += span
        if period <= 0:
            break
    return np.concatenate(out) if out else np.zeros(0)


def offered_rate(traffic: dict) -> float:
    """Mean offered requests per second of an open-loop mix."""
    phases = traffic["phases"]
    return (sum(p["rate"] * p["seconds"] for p in phases)
            / sum(p["seconds"] for p in phases))


def quantile(values, q: float) -> float:
    """The q-quantile as one of the values (no interpolation), so that a
    tail is a latency some request really had."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return math.nan
    return float(v[min(max(math.ceil(q * v.size) - 1, 0), v.size - 1)])


def latencies(due, done, ok, give_up: float) -> np.ndarray:
    """Per-request latency in seconds; a missing request (``ok`` false or
    ``done`` NaN) counts as having waited until ``give_up``."""
    due = np.asarray(due, np.float64)
    done = np.asarray(done, np.float64)
    ok = np.asarray(ok, bool) & np.isfinite(done)
    return np.where(ok, done - due, give_up - due)


def window_rate(done, ok, t0: float, t1: float) -> float:
    """Requests completed correctly inside [t0, t1), per second."""
    done = np.asarray(done, np.float64)
    ok = np.asarray(ok, bool) & np.isfinite(done)
    return float(np.sum(ok & (done >= t0) & (done < t1)) / (t1 - t0))


def lateness(due, sent) -> np.ndarray:
    """How late the generator sent each request, seconds (>= 0)."""
    return np.maximum(np.asarray(sent, np.float64)
                      - np.asarray(due, np.float64), 0.0)
