"""Seeded open-loop arrival schedules.

A copy of the sound part of ``operator_tpu/loadgen/arrivals.py`` (its
``ArrivalSpec`` rate functions and Lewis-Shedler thinning), kept here so
that no later PR can change the yardstick.  Every draw is taken when the
schedule is built, from one ``random.Random(seed)``: the same seed and
parameters give the same list of due times, whatever the system does.

Parameters (the ``arrivals`` object of a traffic file):

- ``name``: ``poisson`` (constant rate) or ``storm`` (a base rate with
  ``burst_factor`` x bursts of ``burst_len_s`` every ``burst_every_s``); the
  original's ``diurnal`` has no cell within a minute's window and was left;
- ``rate_per_s``: the MEAN offered rate over a whole period, bursts
  included — the number a knee sweep compares with what the system
  sustains.  For ``storm`` the base rate follows from it.
- ``counts``: ``poisson`` (the default: thinning, so the number of
  arrivals varies from seed to seed as a Poisson count does) or ``fixed``
: every stretch of constant rate gets
  its expected number of arrivals, fractions carried over, one in each
  equal part of the stretch at a seeded time within it.  Every seed then
  offers the same amount of work in the same bursts, which is what keeps
  a median over some fifty requests comparable between two runs.
"""

from __future__ import annotations

import random


def _base_rate(params: dict) -> float:
    mean = float(params["rate_per_s"])
    if params.get("name", "poisson") == "storm":
        duty = float(params["burst_len_s"]) / float(params["burst_every_s"])
        return mean / (1.0 + (float(params["burst_factor"]) - 1.0) * duty)
    return mean


def rate_at(params: dict, t: float) -> float:
    """Offered rate, requests a second, at ``t`` seconds into the window."""
    base = _base_rate(params)
    name = params.get("name", "poisson")
    if name == "storm":
        in_burst = (t % float(params["burst_every_s"])) < float(params["burst_len_s"])
        return base * (float(params["burst_factor"]) if in_burst else 1.0)
    if name == "poisson":
        return base
    raise ValueError(f"unknown arrival process {name!r}")


def _peak_rate(params: dict) -> float:
    base = _base_rate(params)
    name = params.get("name", "poisson")
    if name == "storm":
        return base * max(1.0, float(params["burst_factor"]))
    return base


def _stretches(params: dict, duration_s: float) -> list[tuple]:
    """``(start, end)`` of the stretches of constant rate in the window."""
    if params.get("name", "poisson") != "storm":
        return [(0.0, duration_s)]
    every, length = float(params["burst_every_s"]), float(params["burst_len_s"])
    edges = {0.0, duration_s}
    k = 0
    while k * every < duration_s:
        edges.update(t for t in (k * every, k * every + length) if t < duration_s)
        k += 1
    ordered = sorted(edges)
    return list(zip(ordered, ordered[1:]))


def _fixed(rng: random.Random, params: dict, duration_s: float) -> list[float]:
    due: list[float] = []
    owed = 0.0
    for start, end in _stretches(params, duration_s):
        owed += rate_at(params, (start + end) / 2) * (end - start)
        count = int(owed + 0.5)
        owed -= count
        # one arrival in each count-th of the stretch, at a seeded time in it
        width = (end - start) / max(count, 1)
        due.extend(start + (i + rng.random()) * width for i in range(count))
    return due


def make(seed: int, params: dict, duration_s: float) -> list[float]:
    """Due times in ``[0, duration_s)``, ascending."""
    rng = random.Random(f"arrivals:{seed}")
    if params.get("counts", "poisson") == "fixed":
        return _fixed(rng, params, duration_s)
    peak = _peak_rate(params)
    rate_at(params, 0.0)  # an unknown name fails here, not mid-schedule
    due: list[float] = []
    t = 0.0
    while peak > 0.0:
        t += rng.expovariate(peak)
        if t >= duration_s:
            break
        # thinning: one more build-time draw per candidate
        if rng.random() * peak <= rate_at(params, t):
            due.append(t)
    return due
