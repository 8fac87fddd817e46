"""Backend dispatch for the Pallas kernels.

On TPU the Pallas kernels run natively; on CPU (tests, this container's
dry-run) the pure-jnp refs are used, with ``interpret=True`` Pallas
execution available for correctness work. The public entry points keep one
signature regardless of backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .flash_attn import blocks as flash_blocks
from .flash_attn import flash_attention as _pallas_flash_attention
from .int8_matmul import int8_matmul as _pallas_int8_matmul
from .paged_attn import paged_attention_step as _pallas_paged_attention_step
from .topk_mask import topk_topp_mask as _pallas_topk_topp_mask
from .zo_fused_replay import zo_fused_replay as _pallas_zo_fused_replay
from .zo_fused_replay import \
    zo_fused_replay_int8 as _pallas_zo_fused_replay_int8
from .zo_perturb import int8_perturb as _pallas_int8_perturb
from .zo_perturb import zo_perturb as _pallas_zo_perturb


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float | None = None, force_pallas: bool = False,
                    interpret: bool = False):
    """Online-softmax attention over grouped queries, q [B,Sq,KV,G,Dh],
    k/v [B,Sk,KV,Dh] — Pallas on TPU (tiles dividing each length; see
    ``flash_engages``), dense-softmax ref elsewhere."""
    if _on_tpu() or force_pallas:
        return _pallas_flash_attention(q, k, v, causal=causal, window=window,
                                       scale=scale, interpret=interpret)
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                   scale=scale)


def flash_engages(seq_len: int, head_dim: int) -> bool:
    """Whether causal self-attention over `seq_len` tokens runs in the
    Pallas flash kernel: on TPU only (elsewhere the model keeps its own
    XLA attention, and its numerics), at head widths the TPU's lanes tile,
    and over at least two key tiles, so that a masked tile is skipped."""
    return (_on_tpu() and head_dim % 128 == 0
            and flash_blocks(seq_len) is not None)


def int8_matmul(a, w, *, force_pallas: bool = False, interpret: bool = False):
    """(out int32, maxabs) — Pallas on TPU, ref elsewhere."""
    if _on_tpu() or force_pallas:
        M, K = a.shape
        _, N = w.shape
        if M % 128 or K % 128 or N % 128:
            # pad to MXU alignment; zeros are exact in integer arithmetic
            Mp, Kp, Np = (-(-M // 128) * 128, -(-K // 128) * 128,
                          -(-N // 128) * 128)
            ap = jnp.zeros((Mp, Kp), a.dtype).at[:M, :K].set(a)
            wp = jnp.zeros((Kp, Np), w.dtype).at[:K, :N].set(w)
            out, mx = _pallas_int8_matmul(ap, wp, interpret=interpret)
            return out[:M, :N], mx
        return _pallas_int8_matmul(a, w, interpret=interpret)
    return ref.int8_matmul_ref(a, w)


def zo_perturb(theta, seed, salt: int, scale, *, force_pallas: bool = False,
               interpret: bool = False):
    if _on_tpu() or force_pallas:
        return _pallas_zo_perturb(theta, seed, salt, scale,
                                  interpret=interpret)
    return ref.zo_perturb_ref(theta, seed, salt, jnp.asarray(scale))


def zo_fused_replay(theta, seeds, coeffs, salt: int, *,
                    force_pallas: bool = False, interpret: bool = False):
    """Apply S ledger steps of P (seed, coeff) ZO records in one pass.

    Pallas on TPU (single 1R+1W sweep over theta for the whole catch-up),
    ref elsewhere. Both paths share the canonical per-step accumulate-then-
    cast order, so live stepping (S=1 per step) and multi-step replay agree
    bitwise within a backend — the fleet's catch-up guarantee.
    """
    if _on_tpu() or force_pallas:
        return _pallas_zo_fused_replay(theta, seeds, coeffs, salt,
                                       interpret=interpret)
    return ref.zo_fused_replay_ref(theta, jnp.asarray(seeds, jnp.uint32),
                                   jnp.asarray(coeffs, jnp.float32), salt)


def zo_fused_replay_int8(theta, seeds, gs, salt: int, r_max: int, p_zero,
                         shift: int, *, force_pallas: bool = False,
                         interpret: bool = False):
    """int8-lane fused ledger replay: S steps x P (seed, ternary g)
    records in one pass over an int8 leaf. Integer arithmetic, so the
    Pallas kernel and the eager ref agree bitwise on every backend."""
    if _on_tpu() or force_pallas:
        return _pallas_zo_fused_replay_int8(theta, seeds, gs, salt,
                                            int(r_max), p_zero, int(shift),
                                            interpret=interpret)
    return ref.zo_fused_replay_int8_ref(
        theta, jnp.asarray(seeds, jnp.uint32), jnp.asarray(gs, jnp.int32),
        salt, int(r_max), p_zero, int(shift))


def int8_perturb(theta, seed, salt: int, k, r_max, p_zero, *,
                 force_pallas: bool = False, interpret: bool = False):
    if _on_tpu() or force_pallas:
        return _pallas_int8_perturb(theta, seed, salt, k, r_max, p_zero,
                                    interpret=interpret)
    return ref.int8_perturb_ref(theta, seed, salt, int(k), int(r_max), p_zero)


def paged_attention_step(q, k_new, v_new, k_pool, v_pool, page_table,
                         seq_lens, *, scale, window: int = 0,
                         force_pallas: bool = False,
                         interpret: bool = False):
    """Fused paged decode megastep — Pallas on TPU, write+gather+dense ref
    elsewhere. Returns (o, k_pool, v_pool): the token's K/V write rides
    inside the step (in-place via input_output_aliases on TPU), so callers
    never scatter into the pool themselves.

    The ref path is bitwise the dense decode attention (see
    ref.paged_attn_step_ref) so CPU serve output is exactly comparable to
    the dense cache path.
    """
    if _on_tpu() or force_pallas:
        return _pallas_paged_attention_step(
            q, k_new, v_new, k_pool, v_pool, page_table, seq_lens,
            scale=scale, window=window, interpret=interpret)
    return ref.paged_attn_step_ref(q, k_new, v_new, k_pool, v_pool,
                                   page_table, seq_lens, scale=scale,
                                   window=window)


def topk_topp_mask(logits, k, p, *, force_pallas: bool = False,
                   interpret: bool = False):
    """Sort-free top-k/top-p logit filter (threshold-refine selection).

    logits [B, V] f32; k [B] int32 (<=0 disables); p [B] f32 (>=1
    disables). Returns logits with filtered entries at NEG_INF. Pallas on
    TPU, jnp radix ref elsewhere — both replace the sampler's two
    full-vocab argsorts with a 4-round byte-radix descent; see
    ref.topk_topp_mask_ref for the keep-set contract and the one
    boundary-rounding caveat vs. the full-sort reference.
    """
    if _on_tpu() or force_pallas:
        return _pallas_topk_topp_mask(logits, k, p, interpret=interpret)
    return ref.topk_topp_mask_ref(logits, jnp.asarray(k, jnp.int32),
                                  jnp.asarray(p, jnp.float32))
