"""The one traffic generator. A traffic mix is a data file,
``bench/traffic/<name>.json``, whose parameters this module reads:

``{"kind": "closed", "clients": C}``
    C clients, each with one query outstanding: a client submits its
    next index as soon as its answer arrives.
``{"kind": "open", "rate_per_s": R}``
    Single-index arrivals due on a fixed schedule, whatever the system
    does. The gaps between arrivals follow an exponential distribution of
    mean 1/R, taken as its stratified quantiles and put in an order drawn
    from the seed: every seed offers the same set of gaps, so seeds change
    the order of the arrivals and not the amount of work.

Indices are uniform over the database (``"index": "uniform"``, the only
distribution: a PIR scan is oblivious, its cost cannot depend on the
index). Everything is drawn from the seed, so the same seed gives the same
schedule and the same indices.
"""
from __future__ import annotations

import math

import numpy as np

KINDS = ("closed", "open")
#: sub-stream ids under the run's seed
STREAM_INDEX, STREAM_GAPS = 1, 2


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one sub-stream of ``seed`` (any Python int)."""
    return np.random.default_rng([seed % (1 << 64), *stream])


def validate(spec: dict) -> dict:
    kind = spec.get("kind")
    if kind not in KINDS:
        raise ValueError(f"traffic kind {kind!r} is not one of {KINDS}")
    if spec.get("index", "uniform") != "uniform":
        raise ValueError(f"index distribution {spec['index']!r} unknown")
    if kind == "closed" and int(spec["clients"]) < 1:
        raise ValueError("a closed mix needs clients >= 1")
    if kind == "open" and float(spec["rate_per_s"]) <= 0:
        raise ValueError("an open mix needs rate_per_s > 0")
    return spec


def client_indices(seed: int, client: int, n_items: int):
    """The endless index stream of one closed-loop client."""
    g = rng(seed, STREAM_INDEX, client)
    while True:
        yield int(g.integers(0, n_items))


def arrival_gaps(rate: float, n: int, seed: int) -> np.ndarray:
    """``n`` gaps: the stratified quantiles of an exponential of mean
    ``1/rate``, in an order drawn from the seed."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    return gaps[rng(seed, STREAM_GAPS).permutation(n)]


def open_schedule(spec: dict, seed: int, seconds: float, n_items: int):
    """``(due_s, index)`` pairs of an open mix, due offsets from the
    window's start, all inside ``[0, seconds)``."""
    rate = float(spec["rate_per_s"])
    gaps = arrival_gaps(rate, max(int(math.ceil(rate * seconds)) + 1, 2),
                        seed)
    due = np.cumsum(gaps) - gaps[0]           # the first arrives at 0
    due = due[due < seconds]
    idx = rng(seed, STREAM_INDEX).integers(0, n_items, size=len(due))
    return [(float(t), int(i)) for t, i in zip(due, idx)]
