"""Counter-based, shardable, mesh-independent Gaussian noise.

``jax.random.normal`` ops are replicated by GSPMD (every device generates
the full array, then slices its shard) — for ZO that means full-parameter
fp32 noise resident per device. Instead we derive noise elementwise from a
murmur3-style integer hash of (global index, seed): pure elementwise ops on
a ``broadcasted_iota``, which GSPMD partitions like any other op.

Properties the framework relies on:
  * regeneration-stable: same (seed, shape) -> bitwise-same z (the MeZO
    seed-replay trick);
  * mesh-independent: z depends on the *global* index only, so elastic
    restarts on a different mesh reproduce the same perturbations —
    plain `jax.random` sharded generation cannot do this;
  * cheap: ~10 int ops + Box-Muller per element, fused into the parameter
    update stream (see kernels/zo_perturb.py for the Pallas twin).
"""
from __future__ import annotations

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
    h = h ^ (h >> np.uint32(16))
    return h


def _hash(seed: jax.Array, salt, idx) -> jax.Array:
    h = idx * _PHI + jnp.asarray(salt, jnp.uint32)
    h = _fmix32(h ^ seed.astype(jnp.uint32))
    h = _fmix32(h + seed.astype(jnp.uint32) * _M2)
    return h


def _flat_index(shape, offset) -> jax.Array:
    n = 1
    for d in shape:
        n *= int(d)
    idx = jax.lax.iota(jnp.uint32, max(n, 1))
    return (idx + jnp.asarray(offset, jnp.uint32)).reshape(shape or ())


def uniform_bits(seed: jax.Array, salt, shape, offset=0) -> jax.Array:
    """uint32 hash bits for every element of `shape`.

    seed: uint32 scalar (traced ok); salt: python int / uint32 stream id.
    offset: flat-index offset (traced ok) — ``bits(shape, off)[i] ==
    bits(bigger_shape)[off + i]``, which is what lets a layer-scan slice
    reproduce exactly the noise of the stacked parameter leaf.
    """
    return _hash(seed, salt, _flat_index(shape, offset))


def normal(seed: jax.Array, salt, shape, offset=0) -> jax.Array:
    """Standard normal fp32 via Box-Muller on two hashed uniform streams."""
    return normal_at(seed, salt, _flat_index(shape, offset))


def normal_at(seed: jax.Array, salt, idx: jax.Array) -> jax.Array:
    """``normal`` at explicit flat indices (uint32 array, any shape):
    ``normal_at(s, salt, idx) == normal(s, salt, shape).ravel()[idx]``
    bitwise, which lets gathered embedding rows take their table's
    noise without the table's z being generated."""
    idx = idx.astype(jnp.uint32)
    b1 = _hash(seed, 2 * np.uint32(salt) + np.uint32(1), idx)
    b2 = _hash(seed, 2 * np.uint32(salt) + np.uint32(2), idx)
    # u1 in (0,1]: top 24 bits, offset so log() is finite
    u1 = (b1 >> np.uint32(8)).astype(jnp.float32) * np.float32(2 ** -24) \
        + np.float32(2 ** -25)
    u2 = (b2 >> np.uint32(8)).astype(jnp.float32) * np.float32(2 ** -24)
    r = jnp.sqrt(-2.0 * jnp.log(u1))
    return r * jnp.cos(np.float32(2.0 * np.pi) * u2)


def seed_from_key(key: jax.Array) -> jax.Array:
    """uint32 scalar from a jax PRNG key (traced-safe)."""
    data = jax.random.key_data(key).astype(jnp.uint32)
    return (data[..., 0] ^ (data[..., -1] * _M1)).reshape(())
