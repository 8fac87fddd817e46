"""Least work of the top-k/top-p logit filter (``kernels/topk_mask.py``):
one read of the float32 ``[rows, V]`` logits and one write of the
filtered ``[rows, V]`` float32 logits."""
from __future__ import annotations


def call_bytes(rows: int, vocab: int) -> int:
    return 2 * rows * vocab * 4


def least_seconds(rows: int, vocab: int, peaks: dict) -> float:
    return call_bytes(rows, vocab) / peaks["hbm_bytes_per_s"]
