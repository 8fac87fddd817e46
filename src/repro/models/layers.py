"""Core transformer layers: norms, RoPE (plain and YaRN), GQA attention
(TP-planned), latent attention (MLA), SwiGLU.

Attention supports two sharding plans chosen by ``ShardingRules``:

- ``tp``  : heads sharded over ``model``; KV heads physically duplicated
            ``kv_dup``x at compute time (weights stay logical) and Q heads
            activation-padded to a multiple of the TP degree. Zero
            attention-internal collectives (Megatron pattern).
- ``seq`` : weights replicated over ``model``; the sequence dim of the
            attention activations is sharded over ``model`` instead
            (for archs whose head counts don't divide the TP degree).

All score computation is query-chunked (block-causal) so that 32k-token
prefill never materializes an SxS score tensor, and sliding-window archs
only compute the banded blocks. Chunking is a python-level unrolled loop:
no ``lax.scan``, so ``cost_analysis`` sees every FLOP (docs/design.md §7).
"""
from __future__ import annotations

import math
import zlib
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..core.zo import MLA

Q_CHUNK = 4096          # query block size for chunked attention


# --------------------------------------------------------------------- #
# init helpers
# --------------------------------------------------------------------- #
def dense_init(key, shape, dtype, fan_in=None):
    fan_in = fan_in if fan_in is not None else shape[0]
    scale = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def subkey(key, *path):
    # crc32, not builtin hash(): str hashes are salted per process, which
    # would make init streams irreproducible across runs (design.md §9)
    for p in path:
        d = p if isinstance(p, int) \
            else zlib.crc32(str(p).encode()) % (2**31)
        key = jax.random.fold_in(key, d)
    return key


# --------------------------------------------------------------------- #
# norms
# --------------------------------------------------------------------- #
def rms_norm(x, scale, eps=1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


def layer_norm(x, scale, bias, eps=1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def group_norm_heads(x, scale, eps=1e-5):
    """Per-head group norm over the last dim; x: [..., H, Dh]."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


# --------------------------------------------------------------------- #
# RoPE
# --------------------------------------------------------------------- #
def rope(x, positions, theta):
    """x: [B, S, H, Dh], positions: [B, S] (int32)."""
    if theta <= 0:
        return x
    dh = x.shape[-1]
    half = dh // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (math.log(theta) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs            # [B,S,half]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def yarn_correction_range(cfg: ModelConfig, dim: int):
    """(low, high) rotary-pair indices between which YaRN ramps from
    extrapolated to interpolated frequencies (DeepSeek-V2's
    ``yarn_find_correction_range``)."""
    def corr(rot):
        return dim * math.log(cfg.yarn_original_max_pos
                              / (rot * 2 * math.pi)) \
            / (2 * math.log(cfg.rope_theta))
    low = math.floor(corr(cfg.yarn_beta_fast))
    high = math.ceil(corr(cfg.yarn_beta_slow))
    return max(low, 0), min(high, dim - 1)


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rope_inv_freq(cfg: ModelConfig, dim: int) -> np.ndarray:
    """Inverse frequencies of `dim` rotary dims: plain RoPE, or YaRN's
    blend of the extrapolated and the factor-interpolated ones."""
    base = np.float32(cfg.rope_theta) \
        ** (np.arange(0, dim, 2, dtype=np.float32) / np.float32(dim))
    extra = np.float32(1.0) / base
    if cfg.yarn_factor <= 0:
        return extra
    low, high = yarn_correction_range(cfg, dim)
    ramp = (np.arange(dim // 2, dtype=np.float32) - np.float32(low)) \
        / np.float32(max(high - low, 1e-3))
    keep = np.float32(1.0) - np.clip(ramp, 0.0, 1.0)      # 1 = extrapolate
    inter = np.float32(1.0) / (np.float32(cfg.yarn_factor) * base)
    return (inter * (np.float32(1.0) - keep) + extra * keep) \
        .astype(np.float32)


def rope_mscale(cfg: ModelConfig) -> float:
    """YaRN's factor on cos and sin (1 without YaRN)."""
    if cfg.yarn_factor <= 0:
        return 1.0
    return yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale) \
        / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """(qk_nope + qk_rope)^-1/2, times YaRN's mscale squared."""
    s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn_factor > 0 and cfg.yarn_mscale_all_dim:
        s *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return s


def rope_rotate(x, positions, inv_freq, mscale: float = 1.0):
    """Rotate-half RoPE of x [B, S, H, D] at `positions` [B, S] with the
    given inverse frequencies (D / 2 of them); cos and sin times
    `mscale`."""
    ang = positions[..., None].astype(jnp.float32) * jnp.asarray(inv_freq)
    cos = (jnp.cos(ang) * mscale)[:, :, None, :]
    sin = (jnp.sin(ang) * mscale)[:, :, None, :]
    half = x.shape[-1] // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


# --------------------------------------------------------------------- #
# latent attention (MLA; DeepSeek-V2, arXiv:2405.04434 section 2.1)
# --------------------------------------------------------------------- #
def init_mla(key, cfg: ModelConfig, dtype):
    d, H, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    nope, rot, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.v_head_dim)
    return {
        "wq": dense_init(subkey(key, "wq"), (d, H, nope + rot), dtype),
        "wkv_a": dense_init(subkey(key, "wkv_a"), (d, r + rot), dtype),
        "kv_norm": jnp.ones((r,), dtype),
        "wkv_b": dense_init(subkey(key, "wkv_b"), (r, H, nope + vd), dtype),
        "wo": dense_init(subkey(key, "wo"), (H, vd, d), dtype,
                         fan_in=H * vd),
    }


@jax.named_scope(MLA)
def mla_attention(p, x, cfg: ModelConfig, rules, positions):
    """Causal latent attention over the whole sequence, keys and values
    decompressed from the latent: x [B, S, d] -> [B, S, d].

    q = x Wq splits per head into nope and rope dims; the latent c and
    one rope key shared by the heads come from x Wkv_a; c is normalised
    and Wkv_b gives each head's nope key and value. The rope dims rotate
    by YaRN's frequencies (rotate-half pairing)."""
    B, S, _ = x.shape
    H, r = cfg.num_heads, cfg.kv_lora_rank
    nope, rot = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    ckv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    c = rms_norm(ckv[..., :r], p["kv_norm"], cfg.norm_eps)
    kv = jnp.einsum("bsr,rhk->bshk", c, p["wkv_b"])
    inv, msc = rope_inv_freq(cfg, rot), rope_mscale(cfg)
    q_pe = rope_rotate(q[..., nope:], positions, inv, msc)
    k_pe = rope_rotate(ckv[..., None, r:], positions, inv, msc)
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (B, S, H, rot))], axis=-1)
    v = kv[..., nope:]
    s = jnp.einsum("bqhk,bthk->bhqt", q, k,
                   preferred_element_type=jnp.float32) \
        * mla_softmax_scale(cfg)
    causal = positions[:, None, None, :] <= positions[:, None, :, None]
    w = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
    o = jnp.einsum("bhqt,bthv->bqhv", w.astype(v.dtype), v,
                   preferred_element_type=jnp.float32).astype(v.dtype)
    return rules.act_btd(jnp.einsum("bqhv,hvd->bqd", o, p["wo"]))


# --------------------------------------------------------------------- #
# attention
# --------------------------------------------------------------------- #
def init_attention(key, cfg: ModelConfig, dtype):
    d, H, KV, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = {
        "wq": dense_init(subkey(key, "wq"), (d, H, Dh), dtype),
        "wk": dense_init(subkey(key, "wk"), (d, KV, Dh), dtype),
        "wv": dense_init(subkey(key, "wv"), (d, KV, Dh), dtype),
        "wo": dense_init(subkey(key, "wo"), (H, Dh, d), dtype, fan_in=H * Dh),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((Dh,), dtype)
        p["k_norm"] = jnp.ones((Dh,), dtype)
    return p


def _grouped(q, kv_dup, q_pad):
    """[B,S,H,Dh] -> [B,S,KVd,G,Dh] with activation-level Q padding."""
    B, S, H, Dh = q.shape
    if q_pad:
        q = jnp.concatenate(
            [q, jnp.zeros((B, S, q_pad, Dh), q.dtype)], axis=2)
        H += q_pad
    return q, H


def _attend_block(q, k, v, mask, scale):
    """q: [B,Sq,KVd,G,Dh], k/v: [B,T,KVd,Dh], mask: [B or 1, Sq, T]."""
    scores = jnp.einsum("bskgh,btkh->bksgt", q, k,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask[:, None, :, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bksgt,btkh->bskgh", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(v.dtype)


def attention(p, x, cfg: ModelConfig, rules, positions,
              *, causal=True, window=0,
              cache: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
              cache_len=None, write_cache=False,
              kv_override: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
              paged: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None):
    """Returns (y, new_cache_or_None).

    cache: (k_cache, v_cache) each [B, T, KVd, Dh] (already kv-duplicated).
    kv_override: precomputed (k, v) for cross-attention (encoder outputs).
    paged: (page_table [B, P], seq_lens [B]) — decode against a paged KV
      pool; ``cache`` then holds (k_pool, v_pool) [N_pages, ps, KVd, Dh]
      shared by all sequences, and per-row positions come from seq_lens.
    """
    B, S, d = x.shape
    H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    plan = rules.attn
    scale = 1.0 / math.sqrt(Dh)

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if kv_override is None:
        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    else:
        k, v = kv_override

    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        if kv_override is None:
            k = rms_norm(k, p["k_norm"], cfg.norm_eps)

    if cfg.rope_theta > 0 and kv_override is None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    # duplicate KV heads for the tp plan
    dup = plan.kv_dup if plan.kind == "tp" else 1
    if kv_override is None and dup > 1:
        k = jnp.repeat(k, dup, axis=2)
        v = jnp.repeat(v, dup, axis=2)
    KVd = KV * dup if kv_override is None else k.shape[2]

    q_pad = plan.q_pad if plan.kind == "tp" else 0
    q, Hp = _grouped(q, dup, q_pad)
    G = Hp // KVd
    q = q.reshape(B, S, KVd, G, Dh)
    q = rules.act_heads(q.reshape(B, S, KVd, G * Dh)).reshape(B, S, KVd, G, Dh) \
        if plan.kind == "tp" else q

    new_cache = None
    if cache is not None and paged is not None:
        from ..kernels import ops
        page_table, seq_lens = paged
        k_pool, v_pool = cache
        pos = seq_lens.astype(jnp.int32)                      # [B]
        # the token's K/V write is fused into the megastep (inactive rows
        # — seq_len 0, table all-null — land in the reserved null page,
        # which is never attended), so no pool-wide scatter happens here.
        y, k_pool, v_pool = ops.paged_attention_step(
            q[:, 0], k[:, 0], v[:, 0], k_pool, v_pool, page_table, pos,
            scale=scale, window=window)
        y = y[:, None]
        new_cache = (k_pool, v_pool)
    elif cache is not None:
        k_cache, v_cache = cache
        T = k_cache.shape[1]
        if window > 0:
            pos_w = jnp.mod(cache_len, T)
            k_cache = _ring_write(k_cache, k, pos_w)
            v_cache = _ring_write(v_cache, v, pos_w)
        else:
            k_cache = jax.lax.dynamic_update_slice(
                k_cache, k.astype(k_cache.dtype), (0, cache_len, 0, 0))
            v_cache = jax.lax.dynamic_update_slice(
                v_cache, v.astype(v_cache.dtype), (0, cache_len, 0, 0))
        new_cache = (k_cache, v_cache)
        k_full, v_full = k_cache, v_cache
        t_pos = jnp.arange(T, dtype=jnp.int32)
        if window > 0:
            # ring buffer: slot t holds absolute position cache_len - ((pos_w - t) mod T)
            rel = jnp.mod(pos_w - t_pos, T)
            abs_pos = cache_len - rel
            valid = (abs_pos >= 0) & (abs_pos <= cache_len) \
                & (abs_pos > cache_len - window)
        else:
            valid = t_pos <= cache_len
        mask = jnp.broadcast_to(valid[None, None, :], (B, S, T))
        y = _attend_block(q, k_full, v_full, mask, scale)
    elif write_cache:
        # prefill: attend over self (chunked) and return the cache
        y = _chunked_self_attention(q, k, v, positions, causal, window, scale, rules)
        new_cache = (k, v)
    else:
        y = _chunked_self_attention(q, k, v, positions, causal, window, scale, rules)

    y = y.reshape(B, S, Hp, Dh)
    if q_pad:
        y = y[:, :, :H, :]
    out = jnp.einsum("bshk,hkd->bsd", y, p["wo"])
    return rules.act_btd(out), new_cache


def _ring_write(cache, x, pos):
    """Write x (S=1 decode step) at ring position pos."""
    return jax.lax.dynamic_update_slice(cache, x.astype(cache.dtype),
                                        (0, pos, 0, 0))


def _chunked_self_attention(q, k, v, positions, causal, window, scale, rules):
    """Block-causal (optionally banded/SWA) attention, query-chunked.

    q: [B,S,KVd,G,Dh]; k,v: [B,S,KVd,Dh]. Python-unrolled chunk loop.
    """
    B, S, KVd, G, Dh = q.shape
    nq = max(1, S // Q_CHUNK)
    cq = S // nq
    if rules.attn.kind == "seq" and rules.model is not None:
        # sequence-sharded attention: constrain the seq dim over `model`
        q = rules.wsc(q, rules.batch, rules.model, None, None, None)
    outs = []
    for i in range(nq):
        q_i = jax.lax.slice_in_dim(q, i * cq, (i + 1) * cq, axis=1)
        q_pos = positions[:, i * cq:(i + 1) * cq]
        T = k.shape[1]
        if causal:
            kv_hi = min((i + 1) * cq, T)
            # lowest kv position any query in this chunk can see, chunk-aligned
            kv_lo = max(0, ((i * cq - window + 1) // cq) * cq) if window > 0 else 0
        else:
            kv_lo, kv_hi = 0, T          # cross-attention: full kv length
        k_i = jax.lax.slice_in_dim(k, kv_lo, kv_hi, axis=1)
        v_i = jax.lax.slice_in_dim(v, kv_lo, kv_hi, axis=1)
        if causal:
            t_pos = positions[:, kv_lo:kv_hi]
            mask = t_pos[:, None, :] <= q_pos[:, :, None]
            if window > 0:
                mask &= t_pos[:, None, :] > q_pos[:, :, None] - window
        else:
            mask = jnp.ones((B, cq, kv_hi - kv_lo), bool)
        outs.append(_attend_block(q_i, k_i, v_i, mask, scale))
    return jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]


# --------------------------------------------------------------------- #
# MLP (SwiGLU)
# --------------------------------------------------------------------- #
def init_mlp(key, d, ff, dtype):
    return {
        "w_gate": dense_init(subkey(key, "wg"), (d, ff), dtype),
        "w_up": dense_init(subkey(key, "wu"), (d, ff), dtype),
        "w_down": dense_init(subkey(key, "wd"), (ff, d), dtype, fan_in=ff),
    }


def mlp(p, x, rules):
    h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, p["w_gate"]))
    h = h * jnp.einsum("bsd,df->bsf", x, p["w_up"])
    if rules.model is not None:
        h = rules.wsc(h, rules.batch, None, rules.model)
    out = jnp.einsum("bsf,fd->bsd", h, p["w_down"])
    return rules.act_btd(out)
