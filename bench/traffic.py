"""The one traffic generator: reads a mix file of ``bench/traffic/``.

A mix is data. Keys:

  - ``rate_qps``:    offered rate of the open loop (queries/s), fixed;
  - ``arrivals``:    "poisson" — exponential gaps at ``rate_qps``;
  - ``query_noise``: norm of the per-column noise around a table row;
  - ``vid_probs``:   optional; else the configuration's ``vid_probs``.

Every seed gets the same set of sizes and arrivals in another order, so
that the seed changes which rows are asked for and not how much work a
window holds: the gaps are the exponential distribution's quantiles at
(i + 1/2) / n, and each vid's count is its probability times n; the seed
permutes both. Rows are drawn uniformly from the seed.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Schedule:
    arrivals: np.ndarray   # (n,) seconds from the window's start, ascending
    vid_index: np.ndarray  # (n,) index into the configuration's vids
    rows: np.ndarray       # (n,) table row each query is drawn around


def counts(probs, n: int) -> np.ndarray:
    """Largest-remainder split of ``n`` by ``probs``."""
    p = np.asarray(probs, dtype=np.float64)
    p = p / p.sum()
    exact = p * n
    out = np.floor(exact).astype(np.int64)
    rest = n - int(out.sum())
    out[np.argsort(-(exact - out), kind="stable")[:rest]] += 1
    return out


def window(traffic: dict, config: dict, seed: int, seconds: float,
           rate: float | None = None) -> Schedule:
    if traffic.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    rate = float(traffic["rate_qps"] if rate is None else rate)
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng([int(seed) % 2 ** 64, 7])
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    # scaled so that all n gaps fill the window: the last query is due
    # one gap before its close
    gaps = rng.permutation(gaps) * (seconds / gaps.sum())
    t = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    probs = traffic.get("vid_probs", config["vid_probs"])
    vid_index = rng.permutation(
        np.repeat(np.arange(len(probs)), counts(probs, n)))
    rows = rng.integers(0, int(config["rows"]), size=n)
    return Schedule(arrivals=t, vid_index=vid_index, rows=rows)


def extra_rows(config: dict, seed: int, n: int) -> np.ndarray:
    """Rows for queries outside the window (warm-up, the tuner's
    workload), from their own stream of the seed."""
    rng = np.random.default_rng([int(seed) % 2 ** 64, 11])
    return rng.integers(0, int(config["rows"]), size=n)
