"""The one traffic generator: a mix file's parameters plus a configuration's
classes, made into requests from ``--seed``.

A mix file (``bench/traffic/<mix>.json``) gives the arrival law:

* ``{"loop": "open", "request_rate_hz": R, "group_size": g}`` -- groups of
  g requests arrive together, R / g groups per second;
* ``{"loop": "closed", "in_flight": n}`` -- n requests outstanding; each
  completion is replaced at once.

The configuration gives the classes: workload, share and degree range.
Every seed gets the same work in another order: the same multiset of
(class, degree) pairs, stratified over each class's degree range, and for
an open loop the same multiset of inter-arrival gaps (quantiles of the
exponential law at the given rate), each shuffled by the seed.  So runs on
different seeds differ in order and payload values, never in amount.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> dict:
    with open(os.path.join(ROOT, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    if mix.get("loop") not in ("open", "closed"):
        raise ValueError(f"traffic {name}: loop must be 'open' or 'closed'")
    return mix


@dataclasses.dataclass
class Schedule:
    """Requests in submission order.  ``due`` (seconds from the window's
    start) is set for an open loop; a closed loop submits as slots free."""
    workloads: list          # class name per request
    degrees: np.ndarray      # int64 per request
    due: np.ndarray | None   # float64 per request, non-decreasing


def class_sizes(classes: list, n: int, rng: np.random.Generator):
    """``n`` (workload, degree) pairs: counts by share, degrees stratified
    over [degree_low, degree_high], shuffled by ``rng``."""
    shares = np.array([c["share"] for c in classes], float)
    counts = np.floor(shares / shares.sum() * n).astype(int)
    counts[0] += n - counts.sum()
    names, degs = [], []
    for c, k in zip(classes, counts):
        lo, hi = c["degree_low"], c["degree_high"]
        strata = lo + np.floor((np.arange(k) + 0.5) * (hi - lo + 1) / max(k, 1))
        names += [c["workload"]] * int(k)
        degs.append(np.minimum(strata.astype(np.int64), hi))
    degs = np.concatenate(degs) if degs else np.zeros(0, np.int64)
    order = rng.permutation(n)
    return [names[i] for i in order], degs[order]


def exp_gaps(n: int, mean_s: float, rng: np.random.Generator) -> np.ndarray:
    """n inter-arrival gaps: the exponential law's quantiles at (k + 1/2)/n,
    scaled to sum to n * mean_s, in an order drawn from ``rng``."""
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    gaps *= n * mean_s / gaps.sum()
    return gaps[rng.permutation(n)]


def open_schedule(mix: dict, classes: list, seconds: float,
                  rng: np.random.Generator) -> Schedule:
    """Arrivals over [0, seconds): round(R * seconds / g) groups of g."""
    g = int(mix.get("group_size", 1))
    n_groups = max(1, round(mix["request_rate_hz"] * seconds / g))
    gaps = exp_gaps(n_groups, seconds / n_groups, rng)
    starts = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    if g > 1 and len(classes) > 1:
        # A group is one caller's burst of one class (an ML-DSA signing
        # attempt's l NTTs).
        raise ValueError("group_size > 1 needs a configuration of one class")
    names, degs = class_sizes(classes, n_groups * g, rng)
    due = np.repeat(starts, g)
    return Schedule(workloads=names, degrees=degs, due=due)


def closed_pool(classes: list, n: int, rng: np.random.Generator) -> Schedule:
    """The first ``n`` requests of a closed loop (the loop cycles them)."""
    names, degs = class_sizes(classes, n, rng)
    return Schedule(workloads=names, degrees=degs, due=None)


def pool_size(mix: dict) -> int:
    """Distinct payloads a closed loop cycles through: at least four times
    the requests in flight, and a power of two."""
    return 1 << math.ceil(math.log2(max(4 * int(mix["in_flight"]), 1024)))
