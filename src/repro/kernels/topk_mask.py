"""Pallas TPU sort-free top-k/top-p filter (threshold search).

One grid step per batch row: the row's logits live in VMEM as a
[V / 128, 128] tile stack, and the thresholds of kernels/ref.py
``topk_topp_mask_ref`` are found by bitwise binary search over the
32-bit monotone float key, one masked full-row reduction per bit:

  * top-k: the largest key t with count(key >= t) >= k — the exact k-th
    largest value, the radix descent's ``kth``;
  * top-p: the largest key T with mass(key >= T) >= p — the radix
    descent's nucleus boundary ``tkey`` — then the tied run at T is
    split in index order by a binary search over the element index.

Only elementwise ops and full reductions run in the kernel: no scatter,
sort, cumsum or 1-D vector, none of which the TPU compiler takes at a
151,936-entry vocabulary. k and p ride as scalar-prefetch operands.
Keep semantics are the jnp ref's, including its tie-splitting and
boundary-rounding contract (see its docstring).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
_SIGN = -2 ** 31                     # int32 with only the sign bit set


def _key(x):
    """float32 -> int32 key, monotone under signed compare (-0.0 -> +0.0).

    It is the ref's uint32 key with the sign bit flipped."""
    x = x.astype(jnp.float32)
    # a select, not x + 0.0, which the compiler may fold away
    x = jnp.where(x == 0.0, jnp.float32(0.0), x)
    s = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(s < 0, s ^ jnp.int32(0x7FFFFFFF), s)


def _search(n_bits, pred):
    """Largest n_bits-bit unsigned pattern u with pred(u), built greedily
    from the top bit; pred must be monotone (true up to some u)."""
    def body(i, u):
        cand = u | jax.lax.shift_left(jnp.int32(1), n_bits - 1 - i)
        return jnp.where(pred(cand), cand, u)
    return jax.lax.fori_loop(0, n_bits, body, jnp.int32(0))


def _kernel(k_ref, p_ref, x_ref, o_ref, *, n_valid):
    b = pl.program_id(0)
    x = x_ref[0]                                   # [V / 128, 128]
    k = k_ref[b]
    p = p_ref[b]
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) * LANES \
        + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = idx < n_valid

    def count(mask):                   # float32 is exact below 2^24
        return jnp.sum(jnp.where(mask, 1.0, 0.0))

    # ---- top-k: the exact k-th largest key ----------------------- #
    keys = _key(x)
    krem = jnp.clip(k, 1, n_valid).astype(jnp.float32)
    kth = _search(32, lambda u: count(valid & (keys >= (u ^ _SIGN)))
                  >= krem) ^ _SIGN
    xk = jnp.where((keys >= kth) | (k <= 0), x, NEG_INF)

    # ---- top-p: the nucleus boundary key ------------------------- #
    e = jnp.exp(xk - jnp.max(xk))
    probs = e / jnp.sum(e)
    keys = _key(xk)
    tkey = _search(32, lambda u: jnp.sum(
        jnp.where(keys >= (u ^ _SIGN), probs, 0.0)) >= p) ^ _SIGN
    above = jnp.sum(jnp.where(keys > tkey, probs, 0.0))
    eq = valid & (keys == tkey)
    p_t = jnp.max(jnp.where(eq, probs, 0.0))
    # the tie of rank r is kept iff above + r * p_t < p, a prefix of the
    # ranks: keep the first n_keep ties in index order
    r = idx.astype(jnp.float32)
    n_keep = count((r < count(eq)) & (above + r * p_t < p))
    cut = _search(int(x.size).bit_length(),
                  lambda u: count(eq & (idx < u)) <= n_keep)
    keep = (keys > tkey) | (eq & (idx < cut)) | (p >= 1.0)
    o_ref[0] = jnp.where(keep, xk, NEG_INF)


@functools.partial(jax.jit, static_argnames=("interpret",))
def topk_topp_mask(logits, k, p, *, interpret: bool = False):
    """logits [B, V] f32, k [B] int32, p [B] f32 -> masked logits [B, V]."""
    B, V = logits.shape
    Vp = -(-V // LANES) * LANES
    x = logits.astype(jnp.float32)
    if Vp != V:              # pad entries are excluded from every count
        x = jnp.pad(x, ((0, 0), (0, Vp - V)), constant_values=NEG_INF)
    R = Vp // LANES
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, R, LANES), lambda b, k, p: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, R, LANES), lambda b, k, p: (b, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, n_valid=V),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, R, LANES), jnp.float32),
        interpret=interpret,
    )(k.astype(jnp.int32), p.astype(jnp.float32), x.reshape(B, R, LANES))
    return out.reshape(B, Vp)[:, :V]
