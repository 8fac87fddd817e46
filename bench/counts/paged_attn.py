"""Least work of paged decode attention (``kernels/paged_attn.py``).

Per decoded token and layer the query must read the K and V of every
key it sees (bf16) and do QK^T and PV for every query head. The count
is of that needed work, not of what the kernel does, so it is the same
whatever implements it.
"""
from __future__ import annotations

from . import dense_lm


def token_bytes(c: dict, keys: int, itemsize: int = 2) -> int:
    return c["num_hidden_layers"] * 2 * keys * c["num_key_value_heads"] \
        * c["head_dim"] * itemsize


def token_flops(c: dict, keys: int) -> int:
    return c["num_hidden_layers"] * dense_lm.attn_flops(c, keys)


def least_seconds(c: dict, keys: int, peaks: dict) -> float:
    return max(token_flops(c, keys) / peaks["bf16_flops"],
               token_bytes(c, keys) / peaks["hbm_bytes_per_s"])
