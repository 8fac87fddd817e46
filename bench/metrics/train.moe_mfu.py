"""Whole elastic-ZO step's share of the chip's bf16 peak for a
latent-attention MoE model: the window's lane FLOPs
(bench/counts/mla_moe_lm.py, the routed rows from ``moe.held_rows``) per
second of the window."""
from bench.counts import mla_moe_lm


def read(layer):
    rows = layer.get("moe_held_rows")
    if not rows:
        return None
    c, tr, steps = layer["config"], layer["traffic"], layer["steps"]
    flops = steps * mla_moe_lm.elastic_zo_step_flops(
        c, tr["batch"], tr["seq"], tr["lane"]["zo_num_probes"],
        tr["lane"]["bp_tail_layers"], rows / steps)
    return 100.0 * flops / layer["window_s"] / layer["peaks"]["bf16_flops"]
