"""Pallas TPU fused ZO perturb/update: theta' = theta + scale * z.

z is regenerated *inside* the kernel from the murmur-style counter hash
(core/prng.py) on the element's global flat index — the identical math, so
the Pallas path is bitwise-equal to the XLA path in interpret mode. HBM
traffic is exactly 1R + 1W of theta; z never exists outside VREGs. This is
the roofline-optimal form of Alg. 1's PerturbParameters/ZOUpdateParameters
(the op is purely memory-bound, so eliminating the z stream is the whole
game; the paper's NEON implementation makes the same observation for CPU).

The int8 variant fuses Alg. 2's sparse-uniform perturbation with the clamp.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import prng

LANES = 128
SUBL = 8
BLOCK_ROWS = 64          # (64, 128) fp32 tile = 32KB VMEM


def _hash_block(row0, shape, seed, salt):
    """uint32 hash bits for a (rows, LANES) block starting at flat row row0."""
    r = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    idx = (row0.astype(jnp.uint32) + r) * np.uint32(LANES) + c
    h = idx * prng._PHI + jnp.asarray(salt, jnp.uint32)
    h = prng._fmix32(h ^ seed.astype(jnp.uint32))
    return prng._fmix32(h + seed.astype(jnp.uint32) * prng._M2)


def _u32_to_f32(x):
    """uint32 -> float32 rounded to nearest even, as XLA's convert does.

    The high half is exact in float32 and the low half is below 2^16,
    so the one rounding happens in the add, on the exact sum."""
    hi = (x >> np.uint32(16)).astype(jnp.int32).astype(jnp.float32)
    lo = (x & np.uint32(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
    return hi * np.float32(65536.0) + lo


def _normal_block(row0, shape, seed, salt):
    b1 = _hash_block(row0, shape, seed, 2 * salt + np.uint32(1))
    b2 = _hash_block(row0, shape, seed, 2 * salt + np.uint32(2))
    # 24-bit values convert exactly through int32; Mosaic has no direct
    # uint32 -> float32 cast
    u1 = _u32_to_f32(b1 >> np.uint32(8)) * np.float32(2 ** -24) \
        + np.float32(2 ** -25)
    u2 = _u32_to_f32(b2 >> np.uint32(8)) * np.float32(2 ** -24)
    r = jnp.sqrt(-2.0 * jnp.log(u1))
    return r * jnp.cos(np.float32(2.0 * np.pi) * u2)


def _int8_noise_block(row0, shape, seed, salt, r_max, p_zero):
    """Alg. 2 sparse uniform int8 noise for a (rows, LANES) block —
    bitwise core/int8.int8_noise on the same flat layout."""
    bits_u = _hash_block(row0, shape, seed, 3 * salt + np.uint32(1))
    bits_m = _hash_block(row0, shape, seed, 3 * salt + np.uint32(2))
    u = (bits_u % (2 * r_max + 1).astype(jnp.uint32)).astype(jnp.int32) \
        - r_max.astype(jnp.int32)
    keep = (_u32_to_f32(bits_m)
            < (1.0 - p_zero) * np.float32(2 ** 32)).astype(jnp.int32)
    return u * keep


def _perturb_kernel(seed_ref, salt_ref, scale_ref, t_ref, o_ref):
    rows = t_ref.shape[0]
    row0 = pl.program_id(0) * rows
    z = _normal_block(jnp.uint32(row0), t_ref.shape, seed_ref[0], salt_ref[0])
    o_ref[...] = (t_ref[...].astype(jnp.float32)
                  + scale_ref[0] * z).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("salt", "interpret"))
def zo_perturb(theta: jax.Array, seed: jax.Array, salt: int,
               scale: jax.Array, *, interpret: bool = False):
    """theta (+) scale*z, any shape; z from the global flat index.

    Equals ref.zo_perturb_ref bitwise in interpret mode. scale may be a
    traced scalar (eta*g for the fused update, +/-eps for perturbation).
    """
    shape, dtype = theta.shape, theta.dtype
    n = theta.size
    rows = -(-n // LANES)
    rows_pad = -(-rows // BLOCK_ROWS) * BLOCK_ROWS
    flat = jnp.zeros((rows_pad * LANES,), dtype).at[:n].set(theta.reshape(-1))
    grid = rows_pad // BLOCK_ROWS
    out = pl.pallas_call(
        _perturb_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_pad, LANES), dtype),
        interpret=interpret,
    )(seed.reshape(1).astype(jnp.uint32),
      jnp.asarray([salt], jnp.uint32),
      jnp.asarray(scale, jnp.float32).reshape(1),
      flat.reshape(rows_pad, LANES))
    return out.reshape(-1)[:n].reshape(shape)


# ------------------------------------------------------------------ #
# int8 (Alg. 2): theta' = clamp(theta + k * m(.)u, -127, 127)
# ------------------------------------------------------------------ #
def _int8_kernel(seed_ref, salt_ref, k_ref, rmax_ref, pz_ref, t_ref, o_ref):
    rows = t_ref.shape[0]
    row0 = pl.program_id(0) * rows
    z = _int8_noise_block(jnp.uint32(row0), t_ref.shape, seed_ref[0],
                          salt_ref[0], rmax_ref[0], pz_ref[0])
    o_ref[...] = jnp.clip(t_ref[...].astype(jnp.int32) + k_ref[0] * z,
                          -127, 127).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("salt", "interpret"))
def int8_perturb(theta: jax.Array, seed: jax.Array, salt: int, k: jax.Array,
                 r_max: jax.Array, p_zero: jax.Array, *,
                 interpret: bool = False):
    shape = theta.shape
    n = theta.size
    rows = -(-n // LANES)
    rows_pad = -(-rows // BLOCK_ROWS) * BLOCK_ROWS
    flat = jnp.zeros((rows_pad * LANES,), jnp.int8).at[:n].set(theta.reshape(-1))
    out = pl.pallas_call(
        _int8_kernel,
        grid=(rows_pad // BLOCK_ROWS,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * 5
        + [pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_pad, LANES), jnp.int8),
        interpret=interpret,
    )(seed.reshape(1).astype(jnp.uint32), jnp.asarray([salt], jnp.uint32),
      jnp.asarray(k, jnp.int32).reshape(1),
      jnp.asarray(r_max, jnp.int32).reshape(1),
      jnp.asarray(p_zero, jnp.float32).reshape(1),
      flat.reshape(rows_pad, LANES))
    return out.reshape(-1)[:n].reshape(shape)
