"""Least time of the paged decode attention that the window's decoded
tokens needed (bench/counts/paged_attn.py, from their context lengths),
over the device time of the paged-attention kernel in the trace."""
from bench.counts import paged_attn


def read(layer):
    kernel_s = layer["trace"]["kernels"].get("paged_attn", 0.0)
    if kernel_s <= 0:
        return None
    c, pk = layer["config"], layer["peaks"]
    least = 0.0
    for r in layer["requests"]:
        for j in range(1, r["decoded_in_window"] + 1):
            least += paged_attn.least_seconds(c, r["prompt_len"] + j, pk)
    return 100.0 * least / kernel_s
