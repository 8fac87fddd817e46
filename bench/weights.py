"""Random model weights made on the device from the run's seed.

Every value is a pure function of (seed, leaf name, flat index) through
an integer hash, so a stacked leaf made in one call and a single layer
made later (for the reference, layer by layer) hold bit-identical
numbers. Projections are uniform with the variance 1 / fan_in, norm
scales are 1, and the rows of the embedding and the columns of the
output head past the published vocabulary are 0, as in a checkpoint
whose vocabulary was padded for the chip.
"""
from __future__ import annotations

import math
import zlib

import numpy as np

import jax
import jax.numpy as jnp

_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)
_PHI = np.uint32(0x9E3779B9)


def _fmix32(h):
    h = h ^ (h >> np.uint32(16))
    h = h * _M1
    h = h ^ (h >> np.uint32(13))
    h = h * _M2
    return h ^ (h >> np.uint32(16))


def salt(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def sub_seed(seed: int, tag: str) -> int:
    """A 31-bit seed for one purpose, drawn from the run's seed."""
    h = (int(seed) * 0x9E3779B97F4A7C15 + zlib.crc32(tag.encode())) \
        & 0xFFFFFFFFFFFFFFFF
    h ^= h >> 31
    h = (h * 0xBF58476D1CE4E5B9) & 0xFFFFFFFFFFFFFFFF
    h ^= h >> 29
    return int(h & 0x7FFFFFFF)


def uniform(seed, name: str, shape, offset=0) -> jax.Array:
    """Uniform float32 in [-1, 1) for every element of `shape`; element
    i equals element ``offset + i`` of a larger draw of the same name.
    `seed` and `offset` may be traced (uint32), so one compiled program
    serves every seed and layer."""
    n = math.prod(shape)
    idx = jax.lax.iota(jnp.uint32, max(n, 1)) \
        + jnp.asarray(offset).astype(jnp.uint32)
    h = _fmix32(idx * _PHI + jnp.uint32(salt(name)))
    h = _fmix32(h ^ jnp.asarray(seed).astype(jnp.uint32))
    u = (h >> np.uint32(8)).astype(jnp.float32) * np.float32(2.0 ** -23) - 1.0
    return u.reshape(shape)


def cast(x, dtype) -> jax.Array:
    """Float32 `x` rounded to `dtype`, the rounding made explicit. Inside
    one program XLA's TPU compiler may keep float32's excess precision
    across a convert to bfloat16 and back, so a value rounded to
    bfloat16 and read again as float32 can come out unrounded;
    ``reduce_precision`` is the op it has to honour."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.bfloat16:
        x = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x.astype(dtype)


def fan_in(name: str, layer_shape) -> int:
    """Inputs feeding one output of the leaf (as the model's own init)."""
    leaf = name.rsplit("/", 1)[-1]
    if leaf == "wo":                                 # (H, Dh, d)
        return layer_shape[0] * layer_shape[1]
    if leaf == "embed":                              # (Vp, d): rows are read
        return layer_shape[1]
    return layer_shape[0]


def is_norm(name: str) -> bool:
    leaf = name.rsplit("/", 1)[-1]
    return leaf.startswith("ln_") or leaf.endswith("norm")


def layer_leaf(seed, name: str, layer_shape, dtype, *, layer=0,
               vocab: int = 0) -> jax.Array:
    """One layer's slice of leaf `name` (`layer` counts from the first
    layer of the model; for the embedding, a block of `layer_shape[0]`
    rows). `vocab` > 0 zeroes the padded vocabulary."""
    if is_norm(name):
        return jnp.ones(layer_shape, dtype)
    size = math.prod(layer_shape)
    layer = jnp.asarray(layer).astype(jnp.uint32)
    w = uniform(seed, name, layer_shape, offset=layer * jnp.uint32(size))
    w = w * np.float32(math.sqrt(3.0 / fan_in(name, layer_shape)))
    if vocab and name == "embed":
        rows = jnp.arange(layer_shape[0], dtype=jnp.uint32) \
            + layer * jnp.uint32(layer_shape[0])
        w = jnp.where(rows[:, None] < vocab, w, 0.0)
    if vocab and name == "unembed":
        w = jnp.where(jnp.arange(layer_shape[1])[None, :] < vocab, w, 0.0)
    return cast(w, dtype)


def logical(path) -> tuple:
    """(group, name) of a program parameter path: group is 'periods_zo',
    'periods_bp' or '' and name is 'blk0/attn/wq', 'embed', ..."""
    keys = [str(getattr(k, "key", k)) for k in path]
    if keys[0] in ("periods_zo", "periods_bp"):
        return keys[0], "/".join(keys[1:])
    return "", "/".join(keys)


def program_leaf(seed, path, abstract, first_bp_layer: int,
                 vocab: int) -> jax.Array:
    group, name = logical(path)
    if not group:
        return layer_leaf(seed, name, abstract.shape, abstract.dtype,
                          vocab=vocab)
    n, per = abstract.shape[0], abstract.shape[1:]
    first = 0 if group == "periods_zo" else first_bp_layer
    if is_norm(name):
        return jnp.ones(abstract.shape, abstract.dtype)
    w = uniform(seed, name, (n,) + per, offset=first * math.prod(per))
    w = w * np.float32(math.sqrt(3.0 / fan_in(name, per)))
    return cast(w, abstract.dtype)


def make_params(abstract_params, seed: int, first_bp_layer: int,
                vocab: int):
    """The whole parameter tree of the program, on the device, in one
    jitted call, each leaf in the dtype the program holds it in."""
    def build(s):
        return jax.tree_util.tree_map_with_path(
            lambda p, a: program_leaf(s, p, a, first_bp_layer, vocab),
            abstract_params)
    return jax.jit(build)(jnp.uint32(seed))


def change_norms(params, seed: int, first_bp_layer: int, vocab: int):
    """Per leaf, the float32 norm of (leaf - its initial value), with the
    initial value made anew from the seed inside the same program."""
    def f(tree, s):
        def one(p, leaf):
            a = jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
            w0 = program_leaf(s, p, a, first_bp_layer, vocab)
            d = leaf.astype(jnp.float32) - w0.astype(jnp.float32)
            return jnp.sqrt(jnp.sum(d * d))
        return jax.tree_util.tree_map_with_path(one, tree)
    out = jax.jit(f)(params, jnp.uint32(seed))
    return {"/".join(x for x in logical(p) if x): float(v)
            for p, v in jax.tree_util.tree_leaves_with_path(out)}
