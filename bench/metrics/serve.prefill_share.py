"""Share of the window the engine spent in its ``serve/prefill`` spans
(admission: prefill, pool scatter and first-token sampling)."""


def read(layer):
    ns = sum(s["dur"] for s in layer["spans"] if s["name"] == "serve/prefill")
    if not layer["spans"]:
        return None
    return 100.0 * ns / 1e9 / layer["window_s"]
