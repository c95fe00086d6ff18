"""The one traffic generator: a traffic file's parameters and a seed give
the requests of a run.

A traffic file (``traffic/<name>.json``) holds only parameters:

* ``op``: which driver in ``ops/`` serves the requests (``cluster``,
  ``assign``);
* ``loop``: ``closed``: one caller, the next request when the last has
  returned;
* ``sizes``: points per request, ``{"dist": "loguniform", "min", "max"}``
  or ``{"dist": "fixed", "n"}``;
* ``pass_requests``, ``passes``: requests are served in passes of
  ``pass_requests``; set-up makes ``passes`` of them, and a window that
  outlasts them serves them again from the first;
* anything an op reads besides (warm-up length, checked sample size).

Every pass holds requests of the same sizes, the quantiles of the stated
distribution. A pass's requests are the same for every seed; the seed
orders them and the rows inside each, so seeds change the order of the
work and not its amount.
"""
from __future__ import annotations

import math

import numpy as np


def _quantiles(m: int) -> np.ndarray:
    return (np.arange(m) + 0.5) / m


def sizes(spec: dict, m: int, rng: np.random.Generator | None = None
          ) -> np.ndarray:
    """``m`` request sizes: the distribution's quantiles, shuffled by
    ``rng`` (rising without one)."""
    if spec["dist"] == "fixed":
        return np.full(m, int(spec["n"]), np.int64)
    if spec["dist"] != "loguniform":
        raise ValueError(f"unknown size distribution {spec['dist']!r}")
    lo, hi = int(spec["min"]), int(spec["max"])
    u = _quantiles(m)
    s = np.floor(np.exp(math.log(lo) + u * (math.log(hi + 1) - math.log(lo))))
    s = np.clip(s, lo, hi).astype(np.int64)
    return s if rng is None else rng.permutation(s)


def passes(traffic: dict, seed: int) -> list:
    """A closed loop's ``passes``: per pass, the order in which it serves
    its requests, a permutation of ``range(pass_requests)`` drawn from
    ``seed``. Request ``k`` of every pass has ``sizes(...)[k]`` points."""
    if traffic["loop"] != "closed":
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    rng = np.random.default_rng(seed)
    return [rng.permutation(int(traffic["pass_requests"]))
            for _ in range(int(traffic["passes"]))]
