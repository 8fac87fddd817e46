"""Plain float32 reference of a latent-attention MoE decoder
(DeepSeek-V2, arXiv:2405.04434), on the program's parameter tree.

Straightforward ``jax.numpy`` at the highest matmul precision, no
kernels, no dispatch, no cache: latent attention as the paper writes it
(keys and values decompressed from the normalised latent, one rope key
shared by the heads), YaRN frequencies and softmax scale as Hugging
Face's ``deepseek_v2`` computes them, and every token routed densely:
its top-k gates over all experts, with only the experts this chip holds
(``first`` .. ``first + held``) contributing, plus the shared experts.

Departures, as the program has them: RoPE pairs the halves of the rope
dims (rotate-half) where Hugging Face interleaves them, a fixed
permutation of the rope columns of seeded random weights; no auxiliary
balance loss.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def mm(spec, *xs):
    return jnp.einsum(spec, *[x.astype(jnp.float32) for x in xs],
                      precision=HIGHEST)


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def yarn_range(dim, base, orig, beta_fast, beta_slow):
    """HF's ``yarn_find_correction_range``."""
    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) \
            / (2 * math.log(base))
    return (max(math.floor(corr(beta_fast)), 0),
            min(math.ceil(corr(beta_slow)), dim - 1))


def yarn_get_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def inv_freq(cfg, dim):
    """HF's ``DeepseekV2YarnRotaryEmbedding`` inverse frequencies."""
    pos = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    extra = 1.0 / (cfg.rope_theta ** pos)
    if cfg.yarn_factor <= 0:
        return extra
    inter = 1.0 / (cfg.yarn_factor * cfg.rope_theta ** pos)
    low, high = yarn_range(dim, cfg.rope_theta, cfg.yarn_original_max_pos,
                           cfg.yarn_beta_fast, cfg.yarn_beta_slow)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask


def softmax_scale(cfg):
    s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn_factor > 0 and cfg.yarn_mscale_all_dim:
        s *= yarn_get_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return s


def rope(x, pos, cfg):
    """x [B, S, H, D]: rotate-half over D with YaRN's cos and sin."""
    f = inv_freq(cfg, x.shape[-1])
    ang = pos[..., None].astype(jnp.float32) * f
    m = 1.0
    if cfg.yarn_factor > 0:
        m = yarn_get_mscale(cfg.yarn_factor, cfg.yarn_mscale) \
            / yarn_get_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
    cos, sin = (jnp.cos(ang) * m)[:, :, None], (jnp.sin(ang) * m)[:, :, None]
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mla(p, x, cfg):
    B, S, _ = x.shape
    H, r = cfg.num_heads, cfg.kv_lora_rank
    nope, rot = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    q = mm("bsd,dhk->bshk", x, p["wq"])
    ckv = mm("bsd,dr->bsr", x, p["wkv_a"])
    c = rms_norm(ckv[..., :r], p["kv_norm"], cfg.norm_eps)
    kv = mm("bsr,rhk->bshk", c, p["wkv_b"])
    k_pe = rope(ckv[..., None, r:], pos, cfg)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, cfg)], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (B, S, H, rot))], -1)
    v = kv[..., nope:]
    s = mm("bqhk,bthk->bhqt", q, k) * softmax_scale(cfg)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = mm("bhqt,bthv->bqhv", jax.nn.softmax(s, -1), v)
    return mm("bqhv,hvd->bqd", o, p["wo"])


def swiglu(p, x):
    a = jax.nn.silu(mm("bsd,df->bsf", x, p["w_gate"])) \
        * mm("bsd,df->bsf", x, p["w_up"])
    return mm("bsf,fd->bsd", a, p["w_down"])


def moe(p, x, cfg, first=None, held=None):
    """Every token's top-k over all experts, densely: the experts
    [first, first + held) contribute their gated SwiGLU (their weights
    are p's, in that order), the shared experts always."""
    first = cfg.experts_first if first is None else first
    held = p["w_gate"].shape[0] if held is None else held
    gates = jax.nn.softmax(mm("bsd,de->bse", x, p["router"]), -1)
    top_g, top_i = jax.lax.top_k(gates, cfg.experts_per_token)
    if cfg.norm_topk_prob:
        top_g = top_g / jnp.sum(top_g, -1, keepdims=True)
    else:
        top_g = top_g * cfg.routed_scaling
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(held):
        w = jnp.sum(jnp.where(top_i == first + e, top_g, 0.0), -1)
        pe = {k: p[k][e] for k in ("w_gate", "w_up", "w_down")}
        y = y + w[..., None] * swiglu(pe, x)
    if "shared" in p:
        y = y + swiglu(p["shared"], x)
    return y


def layer(p, x, cfg):
    x = x.astype(jnp.float32)
    x = x + mla(p["attn"], rms_norm(x, p["ln_attn"], cfg.norm_eps), cfg)
    h = rms_norm(x, p["ln_ffn"], cfg.norm_eps)
    return x + (moe(p["moe"], h, cfg) if "moe" in p else swiglu(p["mlp"], h))


def layers(params):
    """The program's layers in order, each its own parameter dict: the
    leading dense stack, then the MoE periods (``periods`` or the ZO and
    BP-tail halves)."""
    out = []
    for group in ("lead", "periods", "periods_zo", "periods_bp"):
        if group in params:
            stack = params[group]["blk0"]
            n = jax.tree.leaves(stack)[0].shape[0]
            out += [jax.tree.map(lambda a, i=i: a[i], stack)
                    for i in range(n)]
    return out


def hidden(params, tokens, cfg):
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        for p in layers(params):
            x = layer(p, x, cfg)
        return rms_norm(x, params["final_norm"], cfg.norm_eps)


def logits(params, tokens, cfg):
    """[B, S, padded vocab] float32."""
    with jax.default_matmul_precision("highest"):
        return mm("bsd,dv->bsv", hidden(params, tokens, cfg),
                  params["unembed"])


def loss(params, tokens, labels, mask, cfg):
    """Mean masked token cross-entropy over the padded logits, as the
    program normalises it."""
    lg = logits(params, tokens, cfg)
    nll = jax.nn.logsumexp(lg, -1) \
        - jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
