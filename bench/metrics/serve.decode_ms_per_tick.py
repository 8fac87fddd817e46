"""Host time of the engine's ``serve/decode`` spans (one fused megastep
each, blocked on its tokens) per decode tick."""


def read(layer):
    spans = [s for s in layer["spans"] if s["name"] == "serve/decode"]
    ticks = sum(s.get("args", {}).get("ticks", 0) for s in spans)
    if not ticks:
        return None
    return sum(s["dur"] for s in spans) / 1e6 / ticks
