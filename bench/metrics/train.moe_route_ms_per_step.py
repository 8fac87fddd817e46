"""Device time per step of the operations under the ``moe_route`` scope
(router, top-k, sort, gather and scatter, the combine), every phase
together (bench/scopes.py)."""
from bench import scopes


def read(layer):
    s = layer.get("scopes")
    if not s:
        return None
    return 1e3 * scopes.seconds(s, "moe_route") / layer["steps"]
