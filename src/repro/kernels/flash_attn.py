"""Pallas TPU flash attention over grouped queries (causal / windowed).

The XLA attention (models/layers.py ``_attend_block``) writes the whole
float32 score block of a layer to HBM, reads it back for the softmax's max,
sum and normalisation, and writes the probabilities for the PV product:
at 1,024 tokens that is HBM traffic on a tensor that never needs to leave
VMEM. This kernel keeps each score tile in VMEM with the online-softmax
recurrence, so attention's HBM traffic is the q/k/v/o streams alone:

  * grouped queries: a grid step loads one K/V tile of one KV head and
    scores all G query heads of that group against it, each head a
    lane-aligned Dh-wide column slice of the query tile;
  * the model's own layout: q [B, S, KV, G, Dh] and k/v [B, S, KV, Dh]
    are viewed as [B, S, KV*G*Dh] and [B, S, KV*Dh], the row-major layout
    of the projections' matmuls, so no head-major transpose of q is made;
  * tile skipping: a (q-tile, k-tile) pair that the causal mask (or the
    window) hides entirely computes nothing, and its K/V ``index_map`` is
    clamped to a tile the q-tile does read, so no new block is copied in;
    only the tiles that straddle the mask's edge apply it;
  * the XLA path's precision: operands reach the MXU in their own dtype
    with float32 accumulation, the scale is applied in float32, the
    running max and sum are float32, the probabilities are cast to
    ``v.dtype`` for PV and the output to ``v.dtype``.

The kv tiles are the innermost grid dim, so the accumulator stays resident
across them. Sequence tiles are the largest of 512, 256 and 128 that
divide the length at least twice (``blocks``), chosen on a v5e (PERF.md).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30                  # the mask value of _attend_block
BLOCKS = (512, 256, 128)         # sequence tiles, largest first


def blocks(seq_len: int):
    """The sequence tile for `seq_len`: the largest of ``BLOCKS`` that
    divides it into at least two tiles, or None (one tile: nothing to
    skip, and the score block fits VMEM as it is)."""
    for b in BLOCKS:
        if seq_len % b == 0 and seq_len >= 2 * b:
            return b
    return None


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale, causal, window, groups, head_dim, block_q, block_k):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def tile(masked):
        k = k_ref[...]                                   # [bk, Dh]
        v = v_ref[...]
        if masked:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = jnp.ones((block_q, block_k), jnp.bool_)
            if causal:
                mask &= k_pos <= q_pos
            if window > 0:
                mask &= k_pos > q_pos - window
        for g in range(groups):
            cols = pl.ds(g * head_dim, head_dim)
            s = jax.lax.dot_general(
                q_ref[:, cols], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if masked:
                s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[g]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)                       # [bq, bk]
            alpha = jnp.exp(m_prev - m_new)              # rescales the old
            l_ref[g] = l_ref[g] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[:, cols] = acc_ref[:, cols] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[g] = m_new

    # first and last query / key position of this pair of tiles
    q_lo, q_hi = qi * block_q, qi * block_q + block_q - 1
    k_lo, k_hi = ki * block_k, ki * block_k + block_k - 1
    seen, whole = True, True          # some pair visible / every pair
    if causal:
        seen, whole = k_lo <= q_hi, k_hi <= q_lo
    if window > 0:
        seen &= k_hi > q_lo - window
        whole &= k_lo > q_hi - window
    if causal or window > 0:
        pl.when(whole)(lambda: tile(False))
        pl.when(seen & jnp.logical_not(whole))(lambda: tile(True))
    else:
        tile(False)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _done():
        for g in range(groups):
            cols = pl.ds(g * head_dim, head_dim)
            o_ref[:, cols] = (acc_ref[:, cols] / l_ref[g]).astype(o_ref.dtype)


def _kv_index(causal, window, block_q, block_k, n_k):
    """index_map of the K/V tiles: the k-tile index clamped into the
    span the q-tile reads, so a skipped step re-uses the block already in
    VMEM instead of copying a new one in."""
    def index(b, h, i, j):
        if causal:
            j = jnp.minimum(j, ((i + 1) * block_q - 1) // block_k)
        if window > 0:
            lo = jnp.maximum(i * block_q - window + 1, 0) // block_k
            j = jnp.maximum(j, jnp.minimum(lo, n_k - 1))
        return b, j, h
    return index


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float | None = None, interpret: bool = False):
    """q [B, Sq, KV, G, Dh], k/v [B, Sk, KV, Dh] -> o [B, Sq, KV, G, Dh]
    in ``v.dtype``: query i attends to keys j <= i (causal) and
    j > i - window (window > 0).

    Each length is tiled by ``blocks`` (one tile where that gives none);
    on the TPU, Dh (or KV * Dh) is a multiple of 128.
    """
    B, Sq, KV, G, Dh = q.shape
    Sk = k.shape[1]
    block_q = blocks(Sq) or Sq
    block_k = blocks(Sk) or Sk
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)
    n_k = Sk // block_k
    kern = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, groups=G,
        head_dim=Dh, block_q=block_q, block_k=block_k)
    kv_index = _kv_index(causal, window, block_q, block_k, n_k)
    o = pl.pallas_call(
        kern,
        grid=(B, KV, Sq // block_q, n_k),
        in_specs=[
            pl.BlockSpec((None, block_q, G * Dh),
                         lambda b, h, i, j: (b, i, h)),
            pl.BlockSpec((None, block_k, Dh), kv_index),
            pl.BlockSpec((None, block_k, Dh), kv_index),
        ],
        out_specs=pl.BlockSpec((None, block_q, G * Dh),
                               lambda b, h, i, j: (b, i, h)),
        out_shape=jax.ShapeDtypeStruct((B, Sq, KV * G * Dh), v.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, G * Dh), jnp.float32),
            pltpu.VMEM((G, block_q, 1), jnp.float32),
            pltpu.VMEM((G, block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q.reshape(B, Sq, KV * G * Dh), k.reshape(B, Sk, KV * Dh),
      v.reshape(B, Sk, KV * Dh))
    return o.reshape(B, Sq, KV, G, Dh)
