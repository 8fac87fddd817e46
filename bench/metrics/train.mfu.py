"""Whole elastic-ZO step's share of the chip's bf16 peak: lane FLOPs per
token (bench/counts/dense_lm.py) x tokens per second of the window."""
from bench.counts import dense_lm


def read(layer):
    c, tr = layer["config"], layer["traffic"]
    per_tok = dense_lm.elastic_zo_flops_per_token(
        c, tr["seq"], tr["lane"]["zo_num_probes"],
        tr["lane"]["bp_tail_layers"])
    rate = layer["tokens"] / layer["window_s"]
    return 100.0 * per_tok * rate / layer["peaks"]["bf16_flops"]
