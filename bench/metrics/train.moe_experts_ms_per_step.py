"""Device time per step of the operations under the ``moe_experts``
scope: the held experts' grouped matmuls (the compiler's ``ragged-dot``
kernels) and their SwiGLU, every phase together (bench/scopes.py)."""
from bench import scopes


def read(layer):
    s = layer.get("scopes")
    if not s:
        return None
    return 1e3 * scopes.seconds(s, "moe_experts") / layer["steps"]
