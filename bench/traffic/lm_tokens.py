"""Token batches for a training cell.

Parameters (from the traffic file): ``batch``, ``seq``. Step ``i`` of
seed ``s`` is a pure function of (s, i): uniform token ids over the
published vocabulary, labels the next token, no padding. The batch of
every step differs from every other.
"""
from __future__ import annotations

import numpy as np


def batch(params: dict, seed: int, step: int, vocab: int) -> dict:
    B, S = int(params["batch"]), int(params["seq"])
    rng = np.random.default_rng([int(seed), int(step)])
    toks = rng.integers(0, vocab, (B, S + 1), dtype=np.int64)
    return {"tokens": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:].astype(np.int32),
            "mask": np.ones((B, S), np.float32)}
