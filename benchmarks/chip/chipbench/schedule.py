"""The open-loop request schedule: when each request is due, for which
session, for how many rounds, with which draw seed.

The traffic file fixes the arrivals: its `schedule_seed` draws one
sample of the mix, the same for every run, so that runs differ in their
inputs and not in how their requests happen to bunch (the tail of a
queue swings with the order of its arrivals more than with anything
the service does). The gaps are the n quantiles of an exponential at
the traffic's rate (a Poisson process, stratified), the round counts
and the sessions exact shares of their weights (largest remainders),
put in an order drawn from `schedule_seed`. The run's seed draws what
each request asks for (its draw seed). A stall of the server never
moves a due time.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def shares(weights: Sequence[float], n: int) -> np.ndarray:
    """n items split over `weights` by largest remainders (sums to n)."""
    w = np.asarray(weights, np.float64)
    exact = n * w / w.sum()
    out = np.floor(exact).astype(np.int64)
    out[np.argsort(-(exact - out), kind="stable")[:n - out.sum()]] += 1
    return out


def zipf_weights(n: int, s: float) -> np.ndarray:
    """Popularity of ranks 1..n under Zipf(s)."""
    return 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s


def open_loop(seed: int, seconds: float, traffic: Dict) -> List[Dict]:
    """The requests due in [0, `seconds`): rate `rate_hz`, round counts
    `rounds` with `round_weights`, `sessions` sessions of Zipf `zipf_s`
    popularity, in the order `schedule_seed` draws. Each request:
    {"due_s", "session", "n_rounds", "seed"} in due order; `seed` is a
    31-bit draw seed from the run's `seed`."""
    rate = float(traffic["rate_hz"])
    n = max(1, int(round(rate * seconds)))
    order = np.random.default_rng(int(traffic["schedule_seed"]))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps *= seconds / gaps.sum()
    due = np.concatenate([[0.0], np.cumsum(order.permutation(gaps))[:-1]])
    rounds = np.repeat(traffic["rounds"],
                       shares(traffic["round_weights"], n))
    n_sess = int(traffic["sessions"])
    sess = np.repeat(np.arange(n_sess),
                     shares(zipf_weights(n_sess, traffic["zipf_s"]), n))
    rounds, sess = order.permutation(rounds), order.permutation(sess)
    seeds = np.random.default_rng(int(seed)).integers(0, 2 ** 31 - 1, n)
    return [{"due_s": float(due[i]), "session": session_name(int(sess[i])),
             "n_rounds": int(rounds[i]), "seed": int(seeds[i])}
            for i in range(n)]


def session_name(i: int) -> str:
    return f"rsu-{i:04d}"
