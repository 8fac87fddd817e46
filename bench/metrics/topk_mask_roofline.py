"""Least time of one read of the float32 logits and one write of the
filtered logits for every top-k/top-p kernel call in the trace (rows and
vocabulary from the call's operand shape in the compiled HLO), over the
kernel's device time."""
from bench.counts import topk_mask


def read(layer):
    calls = layer["trace"]["kernel_calls"].get("topk_mask", [])
    kernel_s = sum(dur for dur, _ in calls)
    if kernel_s <= 0 or any(shape is None for _, shape in calls):
        return None
    least = sum(topk_mask.least_seconds(rows, vocab, layer["peaks"])
                for _, (rows, vocab) in calls)
    return 100.0 * least / kernel_s
