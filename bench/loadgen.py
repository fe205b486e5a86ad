"""The one load generator: a traffic mix file in, a request schedule out.

A mix (``bench/traffic/<name>.json``) is data only:

* ``loop``: ``"open"`` (independent users on a schedule: ``rate_rps``) or
  ``"closed"`` (``clients`` callers, each sending its next request when
  its last result comes back);
* ``length``: ``{"min", "max"}`` of a log-uniform request length;
* ``samples``: ``{"values", "weights"}``, samples per request;
* ``block`` (closed loop): size of the population each block of requests
  is a permutation of;
* ``slo_ms``, ``idle_timeout_s``: the flush policy's settings.

Every seed gets the same multiset of (length, samples) requests and of
inter-arrival gaps, drawn at stratified quantiles, in a different order:
the seed changes which request comes when and the request PRNG streams,
never the amount of work. A run of ``seconds`` therefore carries the
same work on every seed.
"""

from __future__ import annotations

import dataclasses
import math
import time as _time
from typing import Callable, List

import numpy as np

# requests a closed loop can send in one run
CLOSED_POOL = 65536


@dataclasses.dataclass(frozen=True)
class Req:
    index: int
    seq_len: int
    num_samples: int
    seed: int           # the request's PRNG seed, in [0, 2**31)
    due_s: float        # open loop: due time from the window's start


def _lengths(mix: dict, n: int) -> np.ndarray:
    lo, hi = math.log(mix["length"]["min"]), math.log(mix["length"]["max"])
    q = (np.arange(n) + 0.5) / n
    return np.clip(np.round(np.exp(lo + q * (hi - lo))).astype(np.int64),
                   mix["length"]["min"], mix["length"]["max"])


def _samples(mix: dict, n: int) -> np.ndarray:
    values = np.asarray(mix["samples"]["values"], np.int64)
    w = np.asarray(mix["samples"]["weights"], np.float64)
    # exact proportions: value i fills the quantile band of its weight
    edges = np.cumsum(w / w.sum())
    q = (np.arange(n) + 0.5) / n
    return values[np.searchsorted(edges, q)]


def _gaps(rate: float, n: int) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def population(mix: dict, n: int):
    """``n`` (length, samples) pairs at stratified quantiles, paired by a
    fixed permutation: the same for every seed."""
    pairing = np.random.default_rng(0).permutation(n)
    return np.stack([_lengths(mix, n), _samples(mix, n)[pairing]], axis=1)


def schedule(mix: dict, seed: int, seconds: float) -> List[Req]:
    """The run's requests in the order they are sent.

    Open loop: ``round(rate * seconds)`` requests, the whole population in
    an order drawn from the seed, due at the permuted gaps' running sum,
    scaled to end inside ``[0, seconds)``. Closed loop: clients take
    requests in order from consecutive blocks, each block the same
    population of ``block`` requests in an order drawn from the seed, so
    any window holds whole blocks but for one; due times are set when the
    requests are sent.
    """
    rng = np.random.default_rng(int(seed))
    if mix["loop"] == "open":
        n = max(1, int(round(mix["rate_rps"] * seconds)))
        pairs = population(mix, n)[rng.permutation(n)]
    elif mix["loop"] == "closed":
        block = population(mix, mix["block"])
        pairs = np.concatenate([block[rng.permutation(len(block))]
                                for _ in range(-(-CLOSED_POOL // len(block)))])
        n = len(pairs)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    seeds = rng.integers(0, 2 ** 31, size=n)
    due = np.zeros(n)
    if mix["loop"] == "open":
        gaps = rng.permutation(_gaps(mix["rate_rps"], n))
        # the first request is due at 0; scale so the last is due before
        # the end of the window (n requests in `seconds`: the rate exactly)
        due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
        due *= seconds * (n - 0.5) / n / max(due[-1] + gaps[-1], 1e-9)
    return [Req(i, int(pairs[i, 0]), int(pairs[i, 1]), int(seeds[i]),
                float(due[i])) for i in range(n)]


class MonotonicClock:
    time = staticmethod(_time.monotonic)
    sleep = staticmethod(_time.sleep)


def open_loop(reqs: List[Req], origin: float,
              send: Callable[[Req, float], None], clock=MonotonicClock):
    """Send each request at ``origin + due_s`` on ``clock``: ``send(req,
    due)`` gets the due time, never the (possibly later) send time, so a
    stall of the sender or of the server is charged to every request that
    fell due during it."""
    for r in reqs:
        due = origin + r.due_s
        wait = due - clock.time()
        if wait > 0:
            clock.sleep(wait)
        send(r, due)
