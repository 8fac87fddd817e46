"""The ZO forward's grouped matmuls against their roofline: the least
time of the window's routed rows (``moe.held_rows``, summed over the
window's steps; bench/counts/mla_moe_lm.py) over the device time of the
``ragged-dot`` kernels in the ``zo_forward`` phase (bench/scopes.py)."""
from bench.counts import mla_moe_lm


def read(layer):
    s, rows = layer.get("scopes"), layer.get("moe_held_rows")
    if not s or not rows:
        return None
    kernel_s = s["gmm"].get("zo_forward", 0.0)
    if kernel_s <= 0:
        return None
    c, tr = layer["config"], layer["traffic"]
    passes = 2 * tr["lane"]["zo_num_probes"] * layer["steps"]
    zo_moe = layer["zo_periods"]
    least = mla_moe_lm.zo_gmm_least_seconds(c, rows, passes * zo_moe,
                                            layer["peaks"])
    return 100.0 * least / kernel_s
