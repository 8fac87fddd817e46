"""Programs lowered (compiled or loaded from the cache) inside the
window, counted from JAX's monitoring events. Should read 0."""


def read(layer):
    return layer["compiles"]
