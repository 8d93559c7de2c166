"""Open-loop arrival schedules drawn from a traffic file and a seed.

A traffic file (``bench/traffic/<name>.json``) holds parameters only:

- ``arrival``: ``"paced"`` — one arrival every 1 / ``rate_qps`` seconds
  from the window's opening, the same instants for every seed; or
  ``"poisson"`` — a Poisson process at ``rate_qps`` conditioned on its
  count, exactly round(rate_qps x seconds) arrivals at uniform times drawn
  from the seed (the knee sweep, ``bench/sweep.py``, offers it);
- ``rate_qps``: the offered rate;
- ``sizes_seed`` (optional): the generator draws the pool's query shapes
  from it (``gen.generate``), so every seed offers the same set of query
  sizes and ``--seed`` draws the data.

Every arrival takes its own query of a pool as large as the count, in an
order drawn from the seed.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Schedule:
    offsets: np.ndarray    # (n,) seconds after the window opens, ascending
    query: np.ndarray      # (n,) index into the query pool
    pool: int              # queries the pool must hold


def schedule(traffic: dict, seconds: float, rng: np.random.Generator
             ) -> Schedule:
    rate = float(traffic["rate_qps"])
    n = max(1, int(round(rate * seconds)))
    kind = traffic["arrival"]
    if kind == "paced":
        offsets = np.arange(n) / rate
    elif kind == "poisson":
        offsets = np.sort(rng.uniform(0.0, seconds, n))
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    return Schedule(offsets=offsets, query=rng.permutation(n).astype(np.int64),
                    pool=n)
