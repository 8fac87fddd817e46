"""Model FLOPs of the prompts prefilled and the tokens decoded in the
window, over the window at the chip's bf16 peak."""
from bench.counts import dense_lm


def read(layer):
    c = layer["config"]
    flops = 0.0
    for r in layer["requests"]:
        if r["first_in_window"]:
            flops += dense_lm.prefill_flops(c, r["prompt_len"])
        for j in range(1, r["decoded_in_window"] + 1):
            flops += dense_lm.decode_flops(c, r["prompt_len"] + j)
    return 100.0 * flops / (layer["window_s"] * layer["peaks"]["bf16_flops"])
