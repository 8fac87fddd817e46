"""Dense decoder-only transformer in float32, one layer at a time.

GQA attention with optional qk-norm and RoPE (rotate-half over the whole
head), SwiGLU and RMSNorm, as the configuration files state them. A
layer's weights are made from the seed when the layer runs, so only one
layer is held in float32 at a time.

``precision="fp8"`` rounds both operands of every matmul to
float8_e4m3fn: the lower-precision control that the correctness limits
must reject.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import weights

HIGHEST = jax.lax.Precision.HIGHEST
LAYER_LEAVES = ("ln_attn", "attn/wq", "attn/wk", "attn/wv", "attn/wo",
                "attn/q_norm", "attn/k_norm", "ln_ffn", "mlp/w_gate",
                "mlp/w_up", "mlp/w_down")


@dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    padded_vocab: int
    rope_theta: float
    norm_eps: float
    qk_norm: bool
    dtype: str = "bfloat16"

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        v = c["vocab_size"]
        return cls(c["num_hidden_layers"], c["hidden_size"],
                   c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"], c["intermediate_size"], v,
                   -(-v // 256) * 256, float(c["rope_theta"]),
                   float(c["rms_norm_eps"]), bool(c["qk_norm"]),
                   c.get("torch_dtype", "bfloat16"))

    def leaf_shapes(self) -> dict:
        d, H, K, Dh, ff = (self.d, self.heads, self.kv_heads, self.head_dim,
                           self.ff)
        s = {"ln_attn": (d,), "attn/wq": (d, H, Dh), "attn/wk": (d, K, Dh),
             "attn/wv": (d, K, Dh), "attn/wo": (H, Dh, d), "ln_ffn": (d,),
             "mlp/w_gate": (d, ff), "mlp/w_up": (d, ff),
             "mlp/w_down": (ff, d)}
        if self.qk_norm:
            s["attn/q_norm"] = (Dh,)
            s["attn/k_norm"] = (Dh,)
        return s


def layer_weights(dims: Dims, seed, layer) -> dict:
    """One layer's weights in the dtype the model holds them, from the
    seed."""
    dtype = jnp.dtype(dims.dtype)
    return {k: weights.layer_leaf(seed, "blk0/" + k, shp, dtype, layer=layer)
            for k, shp in dims.leaf_shapes().items()}


def head_weights(dims: Dims, seed) -> dict:
    dtype = jnp.dtype(dims.dtype)
    return {
        "embed": weights.layer_leaf(seed, "embed", (dims.padded_vocab, dims.d),
                                    dtype, vocab=dims.vocab),
        "final_norm": weights.layer_leaf(seed, "final_norm", (dims.d,), dtype),
        "unembed": weights.layer_leaf(seed, "unembed",
                                      (dims.d, dims.padded_vocab), dtype,
                                      vocab=dims.vocab),
    }


def _round(x, precision):
    return fp8_e4m3fn(x) if precision == "fp8" else x


def fp8_e4m3fn(x):
    """Float32 `x` rounded to float8_e4m3fn's values (to nearest, ties to
    even; 3 mantissa bits, subnormals down to 2**-9, saturated at 448),
    by exact float32 arithmetic on powers of two: a convert to float8 and
    back inside one program may be dropped by the TPU's compiler."""
    _, e = jnp.frexp(x)                     # |x| in [2**(e-1), 2**e)
    k = jnp.maximum(e - 1, -6) - 3          # exponent of the quantum
    q = jax.lax.bitcast_convert_type(
        ((k + 127) << 23).astype(jnp.int32), jnp.float32)
    inv = jax.lax.bitcast_convert_type(
        ((127 - k) << 23).astype(jnp.int32), jnp.float32)
    return jnp.clip(jnp.round(x * inv) * q, -448.0, 448.0)


def mm(spec, a, b, precision="f32"):
    return jnp.einsum(spec, _round(a, precision), _round(b, precision),
                      precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (math.log(theta) / half))
    ang = pos[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(w, x, dims: Dims, precision="f32"):
    """x [B, S, d] float32 -> [B, S, d]; causal over S."""
    B, S, _ = x.shape
    f = {k: v.astype(jnp.float32) for k, v in w.items()}
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    h = rms_norm(x, f["ln_attn"], dims.norm_eps)
    q = mm("bsd,dhk->bshk", h, f["attn/wq"], precision)
    k = mm("bsd,dhk->bshk", h, f["attn/wk"], precision)
    v = mm("bsd,dhk->bshk", h, f["attn/wv"], precision)
    if dims.qk_norm:
        q = rms_norm(q, f["attn/q_norm"], dims.norm_eps)
        k = rms_norm(k, f["attn/k_norm"], dims.norm_eps)
    q = rope(q, pos, dims.rope_theta)
    k = rope(k, pos, dims.rope_theta)
    G = dims.heads // dims.kv_heads
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    s = mm("bqhk,bthk->bhqt", q, k, precision) / math.sqrt(dims.head_dim)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = mm("bhqt,bthk->bqhk", p, v, precision)
    x = x + mm("bshk,hkd->bsd", o, f["attn/wo"], precision)
    h = rms_norm(x, f["ln_ffn"], dims.norm_eps)
    a = jax.nn.silu(mm("bsd,df->bsf", h, f["mlp/w_gate"], precision)) \
        * mm("bsd,df->bsf", h, f["mlp/w_up"], precision)
    return x + mm("bsf,fd->bsd", a, f["mlp/w_down"], precision)


def logits(hw, x, dims: Dims, precision="f32"):
    """Logits over the padded vocabulary, [B, S, Vp] float32."""
    h = rms_norm(x, hw["final_norm"], dims.norm_eps)
    return mm("bsd,dv->bsv", h, hw["unembed"].astype(jnp.float32), precision)


def embed(hw, tokens):
    return jnp.take(hw["embed"], tokens, axis=0).astype(jnp.float32)
