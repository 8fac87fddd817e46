"""Operations a dense decoder-only LM needs, from its sizes.

Counts are of the work the mathematics needs: 2 FLOPs per multiply-add
of every matmul, causal attention over the keys each query may see, the
output head over the published vocabulary. Recomputation and padding
are not counted. ``c`` is a configuration file's dict.
"""
from __future__ import annotations


def layer_params(c: dict) -> int:
    """Matmul parameters of one layer (projections and SwiGLU)."""
    d, H, K, Dh, ff = (c["hidden_size"], c["num_attention_heads"],
                       c["num_key_value_heads"], c["head_dim"],
                       c["intermediate_size"])
    return d * (H + 2 * K) * Dh + H * Dh * d + 3 * d * ff


def attn_flops(c: dict, keys: int) -> int:
    """One query against `keys` keys in one layer: QK^T and PV."""
    return 4 * keys * c["num_attention_heads"] * c["head_dim"]


def head_flops(c: dict) -> int:
    return 2 * c["hidden_size"] * c["vocab_size"]


def causal_keys(seq: int) -> float:
    """Mean keys a query sees over a causal sequence of `seq` tokens."""
    return (seq + 1) / 2


def forward_flops_per_token(c: dict, seq: int, layers: int = None) -> float:
    """Forward FLOPs per token of a training sequence, head included."""
    L = c["num_hidden_layers"] if layers is None else layers
    return L * (2 * layer_params(c) + attn_flops(c, causal_keys(seq))) \
        + head_flops(c)


def elastic_zo_flops_per_token(c: dict, seq: int, probes: int,
                               tail_layers: int) -> float:
    """Lane FLOPs of one elastic-ZO step per token: each probe runs two
    whole forwards (theta +/- eps z), each followed by the backward of
    the BP tail (the last `tail_layers` layers and the head), which is
    twice the tail's forward."""
    tail_fwd = tail_layers * (2 * layer_params(c)
                              + attn_flops(c, causal_keys(seq))) \
        + head_flops(c)
    return 2 * probes * (forward_flops_per_token(c, seq) + 2 * tail_fwd)


def prefill_flops(c: dict, prompt: int) -> float:
    """One prompt's prefill: every layer over every prompt token, causal
    attention, and the head at the last position only."""
    L = c["num_hidden_layers"]
    return L * prompt * (2 * layer_params(c)
                         + attn_flops(c, causal_keys(prompt))) \
        + head_flops(c)


def decode_flops(c: dict, context: int) -> float:
    """One decoded token whose query sees `context` keys."""
    return c["num_hidden_layers"] * (2 * layer_params(c)
                                     + attn_flops(c, context)) \
        + head_flops(c)
