"""Latent-attention MoE decoder (DeepSeek-V2) in float32, one layer at a
time, with its weights made from the seed as the harness makes the
program's.

Latent attention as the paper writes it (arXiv:2405.04434, section
2.1): q = x Wq split per head into nope and rope dims; from x Wkv_a the
latent c (RMS-normalised) and one rope key shared by the heads; c Wkv_b
gives each head's nope key and its value; the rope dims rotate by YaRN's
frequencies as Hugging Face's ``deepseek_v2`` computes them; softmax
scale (nope + rope)^-1/2 times YaRN's mscale squared. Layer 0 is a dense
SwiGLU; the others route every token over all of the router's experts
(softmax, greedy top-k, gates neither renormalised nor scaled when the
file says so), densely: each held expert's gated SwiGLU over every token,
plus the shared experts. Only the experts this chip holds contribute.

Departures, stated in the configuration file: rotate-half pairing of the
rope dims where Hugging Face interleaves them (a fixed permutation of the
rope columns of random weights, the same in the program); no auxiliary
balance loss.

``precision="fp8"`` rounds both operands of every matmul to float8_e4m3fn
(model.py): the lower-precision control.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import weights
from . import model
from .model import mm, rms_norm


@dataclass(frozen=True)
class Dims:
    layers: int
    dense_layers: int
    d: int
    heads: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    ff: int
    expert_ff: int
    experts: int             # the router's outputs
    held: int                # experts 0 .. held - 1 live here
    top_k: int
    shared: int
    norm_topk: bool
    routed_scaling: float
    vocab: int
    padded_vocab: int
    rope_theta: float
    yarn: tuple              # (factor, original, beta_fast, beta_slow,
    #                           mscale, mscale_all_dim)
    norm_eps: float
    dtype: str = "bfloat16"

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        v, y = c["vocab_size"], c["rope_scaling"]
        return cls(c["num_hidden_layers"], c["first_k_dense_replace"],
                   c["hidden_size"], c["num_attention_heads"],
                   c["kv_lora_rank"], c["qk_nope_head_dim"],
                   c["qk_rope_head_dim"], c["v_head_dim"],
                   c["intermediate_size"], c["moe_intermediate_size"],
                   c["published"]["n_routed_experts"], c["n_routed_experts"],
                   c["num_experts_per_tok"], c["n_shared_experts"],
                   bool(c["norm_topk_prob"]),
                   float(c["routed_scaling_factor"]), v, -(-v // 256) * 256,
                   float(c["rope_theta"]),
                   (float(y["factor"]), int(y["original_max_position_embeddings"]),
                    float(y["beta_fast"]), float(y["beta_slow"]),
                    float(y["mscale"]), float(y["mscale_all_dim"])),
                   float(c["rms_norm_eps"]), c.get("torch_dtype", "bfloat16"))

    def leaf_shapes(self, dense: bool) -> dict:
        """{name: (shape, dtype)} of a dense or an MoE layer's leaves, as
        the program names them under ``blk0``."""
        d, H, r = self.d, self.heads, self.kv_rank
        dt = self.dtype
        s = {"ln_attn": ((d,), dt), "ln_ffn": ((d,), dt),
             "attn/wq": ((d, H, self.nope + self.rope), dt),
             "attn/wkv_a": ((d, r + self.rope), dt),
             "attn/kv_norm": ((r,), dt),
             "attn/wkv_b": ((r, H, self.nope + self.v), dt),
             "attn/wo": ((H, self.v, d), dt)}
        if dense:
            s.update({"mlp/w_gate": ((d, self.ff), dt),
                      "mlp/w_up": ((d, self.ff), dt),
                      "mlp/w_down": ((self.ff, d), dt)})
            return s
        E, f, fs = self.held, self.expert_ff, self.shared * self.expert_ff
        s.update({"moe/router": ((d, self.experts), "float32"),
                  "moe/w_gate": ((E, d, f), dt), "moe/w_up": ((E, d, f), dt),
                  "moe/w_down": ((E, f, d), dt)})
        if self.shared:
            s.update({"moe/shared/w_gate": ((d, fs), dt),
                      "moe/shared/w_up": ((d, fs), dt),
                      "moe/shared/w_down": ((fs, d), dt)})
        return s


def fan_in(name: str, shape) -> int:
    """Inputs feeding one output: a routed expert's stack leads with the
    expert."""
    if "moe/w_" in name:
        return shape[1]
    return weights.fan_in(name, shape)


def leaf(seed, name: str, shape, dtype, layer) -> jax.Array:
    """Layer `layer`'s value of leaf ``blk0/<name>`` (layers count from 0
    over the whole model, the dense ones first)."""
    full = "blk0/" + name
    if weights.is_norm(full):
        return jnp.ones(shape, dtype)
    size = math.prod(shape)
    layer = jnp.asarray(layer).astype(jnp.uint32)
    w = weights.uniform(seed, full, shape, offset=layer * jnp.uint32(size))
    w = w * jnp.float32(math.sqrt(3.0 / fan_in(full, shape)))
    return weights.cast(w, dtype)


def layer_weights(dims: Dims, seed, layer, dense: bool = None) -> dict:
    """Layer `layer`'s weights (`layer` may be traced when `dense` is
    given)."""
    if dense is None:
        dense = layer < dims.dense_layers
    return {k: leaf(seed, k, shp, jnp.dtype(dt), layer)
            for k, (shp, dt) in dims.leaf_shapes(dense).items()}


def head_weights(dims: Dims, seed) -> dict:
    return model.head_weights(dims, seed)


def yarn_range(dims: Dims):
    factor, orig, fast, slow, _, _ = dims.yarn
    dim = dims.rope

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) \
            / (2 * math.log(dims.rope_theta))
    return (max(math.floor(corr(fast)), 0),
            min(math.ceil(corr(slow)), dim - 1))


def _mscale(scale, m):
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def inv_freq(dims: Dims):
    factor = dims.yarn[0]
    pos = jnp.arange(0, dims.rope, 2, dtype=jnp.float32) / dims.rope
    extra = 1.0 / (dims.rope_theta ** pos)
    inter = 1.0 / (factor * dims.rope_theta ** pos)
    low, high = yarn_range(dims)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dims.rope // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask


def softmax_scale(dims: Dims) -> float:
    factor, *_, mscale_all = dims.yarn
    return (dims.nope + dims.rope) ** -0.5 * _mscale(factor, mscale_all) ** 2


def rope(x, pos, dims: Dims):
    factor, _, _, _, m, m_all = dims.yarn
    ang = pos[..., None].astype(jnp.float32) * inv_freq(dims)
    s = _mscale(factor, m) / _mscale(factor, m_all)
    cos, sin = (jnp.cos(ang) * s)[:, :, None], (jnp.sin(ang) * s)[:, :, None]
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mla(f, x, dims: Dims, precision):
    B, S, _ = x.shape
    r, nope = dims.kv_rank, dims.nope
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    q = mm("bsd,dhk->bshk", x, f["attn/wq"], precision)
    ckv = mm("bsd,dr->bsr", x, f["attn/wkv_a"], precision)
    c = rms_norm(ckv[..., :r], f["attn/kv_norm"], dims.norm_eps)
    kv = mm("bsr,rhk->bshk", c, f["attn/wkv_b"], precision)
    k_pe = rope(ckv[..., None, r:], pos, dims)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, dims)], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (B, S, dims.heads,
                                                 dims.rope))], -1)
    s = mm("bqhk,bthk->bhqt", q, k, precision) * softmax_scale(dims)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = mm("bhqt,bthv->bqhv", jax.nn.softmax(s, -1), kv[..., nope:],
           precision)
    return mm("bqhv,hvd->bqd", o, f["attn/wo"], precision)


def swiglu(x, wg, wu, wd, precision):
    a = jax.nn.silu(mm("bsd,df->bsf", x, wg, precision)) \
        * mm("bsd,df->bsf", x, wu, precision)
    return mm("bsf,fd->bsd", a, wd, precision)


def moe(f, x, dims: Dims, precision):
    """Dense per-token routing: the held experts' gated outputs, one
    expert at a time over every token, plus the shared experts."""
    gates = jax.nn.softmax(mm("bsd,de->bse", x, f["moe/router"], precision),
                           -1)
    top_g, top_i = jax.lax.top_k(gates, dims.top_k)
    if dims.norm_topk:
        top_g = top_g / jnp.sum(top_g, -1, keepdims=True)
    else:
        top_g = top_g * dims.routed_scaling

    def one(y, e):
        w = jnp.sum(jnp.where(top_i == e, top_g, 0.0), -1)
        out = swiglu(x, f["moe/w_gate"][e], f["moe/w_up"][e],
                     f["moe/w_down"][e], precision)
        return y + w[..., None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros(x.shape, jnp.float32),
                        jnp.arange(dims.held))
    if dims.shared:
        y = y + swiglu(x, f["moe/shared/w_gate"], f["moe/shared/w_up"],
                       f["moe/shared/w_down"], precision)
    return y


def layer(w, x, dims: Dims, precision="f32"):
    """x [B, S, d] float32 -> [B, S, d]; causal over S. The layer is
    dense when its weights hold ``mlp/*``."""
    f = {k: v.astype(jnp.float32) for k, v in w.items()}
    x = x + mla(f, rms_norm(x, f["ln_attn"], dims.norm_eps), dims, precision)
    h = rms_norm(x, f["ln_ffn"], dims.norm_eps)
    if "mlp/w_gate" in f:
        return x + swiglu(h, f["mlp/w_gate"], f["mlp/w_up"], f["mlp/w_down"],
                          precision)
    return x + moe(f, h, dims, precision)
