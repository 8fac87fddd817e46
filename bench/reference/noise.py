"""The ZO noise as the configuration defines it: z ~ N(0, 1) by
Box-Muller over two murmur3-finalised uint32 streams of (flat index,
leaf salt, probe seed); the probe seed comes from the step's PRNG key.
Written out here so that the reference does not take it from the
program."""
from __future__ import annotations

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


def _bits(seed, salt, n, offset):
    idx = jax.lax.iota(jnp.uint32, n) + offset
    h = idx * _PHI + jnp.uint32(salt)
    h = _fmix32(h ^ seed)
    return _fmix32(h + seed * _M2)


def leaf_salt(group_path: str) -> int:
    """Salt of a parameter leaf from its path in the ZO parameter group,
    written as JAX prints it, e.g. "['periods_zo']['blk0']['attn']['wq']"."""
    return zlib.crc32(group_path.encode()) & 0x3FFFFFFF


def normal(seed, salt: int, shape, offset=0):
    n = int(np.prod(shape))
    off = jnp.asarray(offset).astype(jnp.uint32)
    s = jnp.asarray(seed).astype(jnp.uint32)
    b1 = _bits(s, (2 * salt + 1) & 0xFFFFFFFF, n, off)
    b2 = _bits(s, (2 * salt + 2) & 0xFFFFFFFF, n, off)
    u1 = (b1 >> np.uint32(8)).astype(jnp.float32) * np.float32(2 ** -24) \
        + np.float32(2 ** -25)
    u2 = (b2 >> np.uint32(8)).astype(jnp.float32) * np.float32(2 ** -24)
    z = jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(np.float32(2 * np.pi) * u2)
    return z.reshape(shape)


def probe_seed(train_seed: int, step: int, probe: int = 0):
    """uint32 probe seed of step `step` (0-based) and probe `probe`."""
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.key(train_seed), step), probe)
    data = jax.random.key_data(key).astype(jnp.uint32)
    return (data[..., 0] ^ (data[..., -1] * _M1)).reshape(())
