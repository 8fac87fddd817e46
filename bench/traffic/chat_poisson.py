"""Open-loop chat requests for a serving cell.

Parameters (from the traffic file):

* ``rate_per_s``: the mean arrival rate, set from a sweep on the chip
  (``bench.calibrate --sweep``); a file without it is refused;
* ``prompt`` and ``output``: lognormal lengths, ``{"median", "sigma",
  "min", "max"}`` in tokens;
* ``sampling``: ``{"temperature", "top_k", "top_p"}``, for every
  request.

Every seed sends the same work in another order: the window of
``seconds`` holds n = round(rate * seconds) requests whose gaps are the
n quantiles of the exponential distribution and whose lengths are the n
quantiles of the lognormals, shuffled by the seed. Prompt tokens and
the per-request sampling seeds are drawn from the seed.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def _quantiles(n: int):
    return (np.arange(n) + 0.5) / n


def _lognormal(spec: dict, q) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
    v = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def requests(params: dict, seed: int, seconds: float, vocab: int) -> list:
    if "rate_per_s" not in params:
        raise ValueError("the traffic has no rate_per_s: set it from a "
                         "sweep on the chip (bench.calibrate --sweep)")
    n = max(1, int(round(params["rate_per_s"] * seconds)))
    rng = np.random.default_rng(int(seed))
    q = _quantiles(n)
    gaps = -np.log1p(-q) / params["rate_per_s"]
    gaps *= seconds / gaps.sum()
    due = np.cumsum(rng.permutation(gaps)) - gaps.min() * 0.5
    prompt_len = rng.permutation(_lognormal(params["prompt"], q))
    out_len = rng.permutation(_lognormal(params["output"], q))
    sp = params["sampling"]
    out = []
    for i in range(n):
        out.append({
            "due_s": float(due[i]),
            "prompt": rng.integers(0, vocab, int(prompt_len[i]),
                                   dtype=np.int64).astype(np.int32),
            "max_new": int(out_len[i]),
            "sampling": dict(sp, seed=int(rng.integers(0, 2 ** 31))),
        })
    return out


def prompt_buckets(params: dict, max_seq_len: int) -> list:
    """Prefill lengths the engine's power-of-two bucketing can produce."""
    lo, hi = params["prompt"]["min"], params["prompt"]["max"]
    return sorted({min(1 << (n - 1).bit_length(), max_seq_len)
                   for n in range(lo, hi + 1)})
