"""NITI-style int8 training substrate (ElasticZO-INT8, Alg. 2).

Tensors are ``QTensor``s: int8 data + int32 scaling exponent, representing
``data * 2^exp``. Matmuls/convs accumulate in int32 (TPU MXU-native; see
kernels/int8_matmul.py for the Pallas tile), activations are rescaled back
to 8 bits with NITI's dynamic-bitwidth rule, and updates use
pseudo-stochastic rounding where the discarded low bits of the value itself
act as the randomness source — fully deterministic, integer-only.

The ZO pieces follow Alg. 2 exactly:
  * perturbation: sparse uniform int8 noise z = m (.) u, m ~ Bern(1-p_zero),
    u ~ U(-r_max, r_max), replayed from a counter-based hash (core/prng.py)
    instead of stored;
  * ternary projected gradient g = sgn(l+ - l-) from integer logits
    (core/int_loss.py);
  * update: theta <- clamp(theta - psr(g*z, b_zo), -127, 127), in-place.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from . import prng


class QTensor(NamedTuple):
    data: jax.Array            # int8
    exp: jax.Array             # int32 scalar


def qtensor(data, exp):
    return QTensor(jnp.asarray(data, jnp.int8), jnp.asarray(exp, jnp.int32))


def dequant(q: QTensor) -> jax.Array:
    return q.data.astype(jnp.float32) * jnp.exp2(q.exp.astype(jnp.float32))


def _pow2(n: jax.Array) -> jax.Array:
    """2.0**n as float32 for integer n in [-126, 127], built from its
    bits — exact on every backend, unlike ``exp2``."""
    n = jnp.asarray(n, jnp.int32)
    return jax.lax.bitcast_convert_type((n + 127) << 23, jnp.float32)


def quant_from_float(x, bits=7):
    """Quantize fp32 -> QTensor with max-|x| scaling (init / input path).

    Only exact float ops: the exponent ceil(log2 max|x|) comes from
    frexp, and scaling by a power of two and round-half-even are exact,
    so every backend produces the same bits."""
    m = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    frac, e = jnp.frexp(m)                   # m = frac * 2^e, frac in [.5, 1)
    exp = e - (frac == 0.5).astype(jnp.int32) - bits
    data = jnp.clip(jnp.round(x * _pow2(-exp)), -127, 127).astype(jnp.int8)
    return QTensor(data, exp.astype(jnp.int32))


# ------------------------------------------------------------------ #
# pseudo-stochastic rounding (NITI §IV): the bits below the cut are the
# randomness; E[psr(x, s)] = x / 2^s.
# ------------------------------------------------------------------ #
def psr_shift(x: jax.Array, s: jax.Array) -> jax.Array:
    """Round x (int32) right by s bits, pseudo-stochastically."""
    s = jnp.asarray(s, jnp.int32)
    sign = jnp.sign(x)
    mag = jnp.abs(x)
    base = jax.lax.shift_right_logical(mag, s)
    rem = mag - jax.lax.shift_left(base, s)
    # hash the remainder to get the pseudo-random threshold
    h = (rem.astype(jnp.uint32) * np.uint32(0x9E3779B9)) ^ mag.astype(jnp.uint32)
    h = h ^ (h >> np.uint32(16))
    thresh = jax.lax.shift_right_logical(
        h, jnp.asarray(32, jnp.uint32) - s.astype(jnp.uint32)).astype(jnp.int32)
    up = (thresh < rem).astype(jnp.int32)
    out = jnp.where(s > 0, base + up, mag)
    return sign * out


def bitwidth(x_max: jax.Array) -> jax.Array:
    """floor(log2(max)) + 1 via integer compares (no float ops)."""
    x_max = jnp.maximum(x_max.astype(jnp.int32), 1)
    b = jnp.zeros((), jnp.int32)
    for k in range(31):
        b = b + (x_max >= (1 << k)).astype(jnp.int32)
    return b


def rescale_int32(acc: jax.Array, exp: jax.Array) -> QTensor:
    """NITI forward rescale: int32 accumulator -> int8 + adjusted exponent."""
    b = bitwidth(jnp.max(jnp.abs(acc)))
    shift = jnp.maximum(b - 7, 0)
    data = jnp.clip(psr_shift(acc, shift), -127, 127).astype(jnp.int8)
    return QTensor(data, exp + shift)


# ------------------------------------------------------------------ #
# int8 compute ops (XLA path; kernels/ops.py dispatches the Pallas twin)
# ------------------------------------------------------------------ #
def int8_matmul(a: jax.Array, w: jax.Array) -> jax.Array:
    """int8 x int8 -> int32 (a: [..., K], w: [K, N])."""
    return jax.lax.dot_general(
        a.astype(jnp.int32), w.astype(jnp.int32),
        (((a.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


def qdense(x: QTensor, w: QTensor) -> QTensor:
    acc = int8_matmul(x.data, w.data)
    return rescale_int32(acc, x.exp + w.exp)


def qconv2d(x: QTensor, w: QTensor, stride=1) -> QTensor:
    """int8 conv via im2col + int8 GEMM (TPU adaptation, docs/design.md §4).

    x: [B,H,W,C] int8; w: [kh,kw,C,O] int8.
    """
    kh, kw, C, O = w.data.shape
    B, H, W, _ = x.data.shape
    Ho, Wo = (H - kh) // stride + 1, (W - kw) // stride + 1
    patches = []
    for i in range(kh):
        for j in range(kw):
            patches.append(jax.lax.slice(
                x.data, (0, i, j, 0),
                (B, i + Ho * stride, j + Wo * stride, C),
                (1, stride, stride, 1)))
    col = jnp.stack(patches, axis=3).reshape(B, Ho, Wo, kh * kw * C)
    acc = int8_matmul(col, w.data.reshape(kh * kw * C, O))
    return rescale_int32(acc, x.exp + w.exp)


def qrelu(x: QTensor) -> QTensor:
    return QTensor(jnp.maximum(x.data, 0), x.exp)


def qmaxpool2(x: QTensor) -> QTensor:
    d = x.data
    B, H, W, C = d.shape
    d = d.reshape(B, H // 2, 2, W // 2, 2, C).max(axis=(2, 4))
    return QTensor(d, x.exp)


def qglobal_maxpool(x: QTensor, axis=1) -> QTensor:
    return QTensor(jnp.max(x.data, axis=axis), x.exp)


# ------------------------------------------------------------------ #
# ZO perturbation / update (Alg. 2 lines 12-24)
# ------------------------------------------------------------------ #
def int8_noise(seed: jax.Array, salt: int, shape,
               r_max: int, p_zero: jax.Array) -> jax.Array:
    """Sparse uniform int8 perturbation z = m (.) u (replayable)."""
    bits_u = prng.uniform_bits(seed, 3 * np.uint32(salt) + np.uint32(1), shape)
    bits_m = prng.uniform_bits(seed, 3 * np.uint32(salt) + np.uint32(2), shape)
    u = (bits_u % np.uint32(2 * r_max + 1)).astype(jnp.int32) - r_max
    keep_thresh = ((1.0 - p_zero) * (2.0 ** 32)).astype(jnp.float32)
    m = (bits_m.astype(jnp.float32) < keep_thresh).astype(jnp.int32)
    return (u * m).astype(jnp.int32)


def perturb_int8(params, seed, k: int, r_max: int, p_zero) -> Any:
    """theta <- clamp(theta + k*z, -127, 127) on every QTensor leaf."""
    def f(path, leaf):
        if not isinstance(leaf, QTensor):
            return leaf
        import zlib
        salt = zlib.crc32(jax.tree_util.keystr(path).encode()) & 0x3FFFFFFF
        z = int8_noise(seed, salt, leaf.data.shape, r_max, p_zero)
        d = jnp.clip(leaf.data.astype(jnp.int32) + k * z, -127, 127)
        return QTensor(d.astype(jnp.int8), leaf.exp)
    return jax.tree_util.tree_map_with_path(
        f, params, is_leaf=lambda x: isinstance(x, QTensor))


def zo_update_int8(params, seed, g, r_max: int, p_zero, b_zo: int) -> Any:
    """theta <- clamp(theta - psr(g*z, b_zo), -127, 127) (Alg. 2 line 23-24)."""
    shift = jnp.maximum(bitwidth(jnp.asarray(r_max)) - b_zo, 0)

    def f(path, leaf):
        if not isinstance(leaf, QTensor):
            return leaf
        import zlib
        salt = zlib.crc32(jax.tree_util.keystr(path).encode()) & 0x3FFFFFFF
        z = int8_noise(seed, salt, leaf.data.shape, r_max, p_zero)
        upd = psr_shift(g * z, shift)
        d = jnp.clip(leaf.data.astype(jnp.int32) - upd, -127, 127)
        return QTensor(d.astype(jnp.int8), leaf.exp)
    return jax.tree_util.tree_map_with_path(
        f, params, is_leaf=lambda x: isinstance(x, QTensor))


# ------------------------------------------------------------------ #
# int8 backward for FC tails (NITI backward, used by ElasticZO-INT8's BP part)
# ------------------------------------------------------------------ #
def output_error_int8(logits: QTensor, labels: jax.Array) -> jax.Array:
    """e_L ~ softmax - onehot, quantized to int8 range [-127,127] (int32).

    NITI approximates the softmax gradient in integer arithmetic; we use the
    same power-of-two trick as the loss (core/int_loss.py) to get integer
    pseudo-probabilities.
    """
    from .int_loss import pow2_scores
    scores = pow2_scores(logits)               # int32 [B, C], <= 2^10
    tot = jnp.sum(scores, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(labels, logits.data.shape[-1], dtype=jnp.int32)
    # e = 127 * (p - y); p ~ scores/tot
    e = (127 * scores) // jnp.maximum(tot, 1) - 127 * onehot
    return jnp.clip(e, -127, 127)


def fc_backward_int8(w: QTensor, a_in: QTensor, e_out: jax.Array,
                     b_bp: int) -> Tuple[QTensor, jax.Array]:
    """One FC layer's NITI backward: returns (updated w, e_in int32[-127,127]).

    e_out: int32 in int8 range. Gradient g = a_in^T e_out (int32), rounded to
    b_bp bits; update applied in the weight's own scale (exponent fixed).
    """
    g = jax.lax.dot_general(
        a_in.data.astype(jnp.int32), e_out.astype(jnp.int32),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    b = bitwidth(jnp.max(jnp.abs(g)))
    shift = jnp.maximum(b - b_bp, 0)
    upd = psr_shift(g, shift)
    new_w = QTensor(jnp.clip(w.data.astype(jnp.int32) - upd,
                             -127, 127).astype(jnp.int8), w.exp)
    e_in = jax.lax.dot_general(
        e_out.astype(jnp.int32), w.data.astype(jnp.int32),
        (((e_out.ndim - 1,), (1,)), ((), ())), preferred_element_type=jnp.int32)
    b_e = bitwidth(jnp.max(jnp.abs(e_in)))
    e_in = psr_shift(e_in, jnp.maximum(b_e - 7, 0))
    return new_w, jnp.clip(e_in, -127, 127)
