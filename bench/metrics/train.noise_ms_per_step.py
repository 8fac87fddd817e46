"""Device time per step of the fusions that make the ZO noise: those
whose computation in the step's compiled HLO holds the hash's u32 xor
and logical right shift (bench/trace.py), matched to the trace's ops.
A fusion that holds the hash and a matmul counts whole, so noise that
XLA fuses into a matmul stays in the metric rather than leaving it."""


def read(layer):
    t = layer["trace"]
    if not t.get("noise_matched"):
        return None
    return 1e3 * (t["noise_s"] + t["noise_matmul_s"]) / layer["steps"]
