"""Operations a latent-attention MoE decoder (DeepSeek-V2) needs, from
its sizes and the rows its held experts were routed.

Counts are of the work the mathematics needs, 2 FLOPs per multiply-add:
latent attention in its decompressed form (the q, latent, key-value and
output projections, QK^T over nope + rope dims and PV over the value dims
for the keys each causal query sees), the leading dense layers' SwiGLU,
each MoE layer's router over all experts and its shared experts on every
token, the held experts' SwiGLU on the rows routed to them, and the
output head over the published vocabulary. Recomputation and padding are
not counted. ``c`` is a configuration file's dict; ``held_rows`` is the
step's ``moe.held_rows`` count, the rows routed to held experts over the
ZO-forward MoE layers of both probes.
"""
from __future__ import annotations

from . import dense_lm


def mla_params(c: dict) -> int:
    d, H, r = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    nope, rot, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                    c["v_head_dim"])
    return d * H * (nope + rot) + d * (r + rot) + r * H * (nope + v) \
        + H * v * d


def attn_flops(c: dict, keys: float) -> float:
    """One query against `keys` keys in one layer: QK^T and PV."""
    return 2 * keys * c["num_attention_heads"] \
        * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"])


def dense_layer_flops(c: dict, seq: int) -> float:
    """Per token, a leading dense layer."""
    return 2 * (mla_params(c) + 3 * c["hidden_size"]
                * c["intermediate_size"]) \
        + attn_flops(c, dense_lm.causal_keys(seq))


def moe_layer_flops(c: dict, seq: int) -> float:
    """Per token, an MoE layer without its routed experts: attention,
    the router over all experts and the shared experts."""
    d = c["hidden_size"]
    shared = 3 * d * c["n_shared_experts"] * c["moe_intermediate_size"]
    router = d * c["published"]["n_routed_experts"]
    return 2 * (mla_params(c) + shared + router) \
        + attn_flops(c, dense_lm.causal_keys(seq))


def row_flops(c: dict) -> int:
    """One row through one routed expert's SwiGLU (the grouped
    matmuls)."""
    return 6 * c["hidden_size"] * c["moe_intermediate_size"]


def expert_bytes(c: dict, itemsize: int = 2) -> int:
    """The held experts' weights of one MoE layer."""
    return 3 * c["n_routed_experts"] * c["hidden_size"] \
        * c["moe_intermediate_size"] * itemsize


def elastic_zo_step_flops(c: dict, batch: int, seq: int, probes: int,
                          tail_layers: int, held_rows: float) -> float:
    """Lane FLOPs of one elastic-ZO step: each probe runs two whole
    forwards (theta +/- eps z), each followed by the backward of the BP
    tail (its MoE layers and the head), twice the tail's forward. The
    tail's routed rows, which ``held_rows`` leaves out, are taken at the
    ZO MoE layers' mean per layer and pass."""
    tokens = batch * seq
    dense = c["first_k_dense_replace"]
    moe = c["num_hidden_layers"] - dense
    passes = 2 * probes
    rows_per_layer = held_rows / ((moe - tail_layers) * passes)
    head = dense_lm.head_flops(c)
    forward = tokens * (dense * dense_layer_flops(c, seq)
                        + moe * moe_layer_flops(c, seq) + head) \
        + (held_rows / passes + tail_layers * rows_per_layer) * row_flops(c)
    tail = tokens * (tail_layers * moe_layer_flops(c, seq) + head) \
        + tail_layers * rows_per_layer * row_flops(c)
    return passes * (forward + 2 * tail)


def zo_gmm_least_seconds(c: dict, held_rows: float, layer_passes: int,
                         peaks: dict) -> float:
    """Least time of the ZO forward's grouped matmuls: `held_rows` rows
    through the expert SwiGLU, each row read and written once (bf16), and
    the held experts' weights read once per MoE layer and pass."""
    flops = held_rows * row_flops(c)
    nbytes = layer_passes * expert_bytes(c) \
        + held_rows * 2 * c["hidden_size"] * 2
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
