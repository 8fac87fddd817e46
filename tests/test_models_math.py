"""Mathematical invariants of the model components."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.models import ssm
from repro.models.layers import rope
from repro.models.moe import moe_ffn, init_moe
from repro.sharding.rules import ShardingRules


# ------------------------------------------------------------------ #
# chunked recurrences vs sequential reference
# ------------------------------------------------------------------ #
def _wkv_sequential(r, k, v, logw, u, init=None):
    B, S, H, D = r.shape
    S_state = (jnp.zeros((B, H, D, D)) if init is None else init)
    outs = []
    for t in range(S):
        rt, kt, vt = r[:, t], k[:, t], v[:, t]
        cur = S_state + (u[None] * kt)[..., None] * vt[:, :, None, :]
        outs.append(jnp.einsum("bhk,bhkv->bhv", rt, cur))
        S_state = jnp.exp(logw[:, t])[..., None] * S_state \
            + kt[..., None] * vt[:, :, None, :]
    return jnp.stack(outs, 1), S_state


@pytest.mark.parametrize("S,init", [(32, False), (64, True)])
def test_wkv_chunked_vs_sequential(S, init):
    rng = np.random.default_rng(0)
    B, H, D = 2, 3, 8
    r, k, v = (jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
               for _ in range(3))
    logw = -jnp.asarray(rng.uniform(0.01, 3.0, (B, S, H, D)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(H, D)), jnp.float32)
    s0 = (jnp.asarray(rng.normal(size=(B, H, D, D)), jnp.float32)
          if init else None)
    out_c, fin_c = ssm._wkv_chunked(r, k, v, logw, u, init=s0)
    out_s, fin_s = _wkv_sequential(r, k, v, logw, u, init=s0)
    np.testing.assert_allclose(out_c, out_s, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(fin_c, fin_s, rtol=2e-4, atol=2e-4)


def _mamba_sequential(xdt, dt, A, Bc, Cc, carry):
    B, S, di = xdt.shape
    h = carry
    ys = []
    for t in range(S):
        la = jnp.maximum(dt[:, t, :, None] * A[None], -ssm.DECAY_CLAMP)
        h = jnp.exp(la) * h + xdt[:, t, :, None] * Bc[:, t, None, :]
        ys.append(jnp.einsum("bdn,bn->bd", h, Cc[:, t]))
    return jnp.stack(ys, 1), h


@pytest.mark.parametrize("S", [32, 64])
def test_mamba_chunked_vs_sequential(S):
    rng = np.random.default_rng(1)
    B, di, N = 2, 16, 4
    xdt = jnp.asarray(rng.normal(size=(B, S, di)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (B, S, di)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 3.0, (di, N)), jnp.float32)
    Bc = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    Cc = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    carry = jnp.asarray(rng.normal(size=(B, di, N)), jnp.float32)
    y_c, f_c = ssm._mamba_chunked(xdt, dt, A, Bc, Cc, init=carry)
    y_s, f_s = _mamba_sequential(xdt, dt, A, Bc, Cc, carry)
    np.testing.assert_allclose(y_c, y_s, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(f_c, f_s, rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------------ #
# attention / rope
# ------------------------------------------------------------------ #
def test_rope_preserves_norm_and_relativity():
    rng = np.random.default_rng(2)
    B, S, H, D = 1, 16, 2, 32
    x = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    y = rope(x, pos, 10000.0)
    np.testing.assert_allclose(jnp.linalg.norm(y, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    # relative property: <rope(q,i), rope(k,j)> depends only on i-j
    q = jnp.asarray(rng.normal(size=(1, 1, 1, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 1, 1, D)), jnp.float32)
    def dot_at(i, j):
        qi = rope(q, jnp.asarray([[i]], jnp.int32), 10000.0)
        kj = rope(k, jnp.asarray([[j]], jnp.int32), 10000.0)
        return float(jnp.sum(qi * kj))
    assert abs(dot_at(3, 1) - dot_at(7, 5)) < 1e-4


def test_swa_equals_full_when_window_covers():
    """Sliding-window attention == full attention when window >= seq."""
    from repro.models.layers import attention, init_attention
    cfg = reduced(ARCHS["mixtral-8x7b"], sliding_window=128)
    cfg_full = dataclasses.replace(cfg, sliding_window=0)
    rules = ShardingRules(None, cfg, None)
    p = init_attention(jax.random.key(0), cfg, jnp.float32)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 64, cfg.d_model)) * 0.1, jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(64, dtype=jnp.int32), (2, 64))
    y_swa, _ = attention(p, x, cfg, rules, pos, causal=True, window=128)
    y_full, _ = attention(p, x, cfg_full, rules, pos, causal=True, window=0)
    np.testing.assert_allclose(y_swa, y_full, rtol=1e-4, atol=1e-5)


def test_swa_locality():
    """With window w, output at position t is independent of tokens < t-w."""
    from repro.models.layers import attention, init_attention
    cfg = reduced(ARCHS["mixtral-8x7b"], sliding_window=8)
    rules = ShardingRules(None, cfg, None)
    p = init_attention(jax.random.key(0), cfg, jnp.float32)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(1, 32, cfg.d_model)) * 0.1, jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(32, dtype=jnp.int32), (1, 32))
    y1, _ = attention(p, x, cfg, rules, pos, causal=True, window=8)
    x2 = x.at[0, 0].set(99.0)           # perturb a token far outside window
    y2, _ = attention(p, x2, cfg, rules, pos, causal=True, window=8)
    np.testing.assert_allclose(y1[0, -1], y2[0, -1], rtol=1e-4, atol=1e-5)


# ------------------------------------------------------------------ #
# MoE
# ------------------------------------------------------------------ #
def test_moe_matches_dense_dispatch():
    """Sort-based dropless dispatch == brute-force per-token expert
    mixing."""
    cfg = reduced(ARCHS["mixtral-8x7b"])
    rules = ShardingRules(None, cfg, None)
    p = init_moe(jax.random.key(0), cfg, jnp.float32)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 16, cfg.d_model)) * 0.3, jnp.float32)
    y, _ = moe_ffn(p, x, cfg, rules)

    # brute force
    logits = jnp.einsum("bsd,de->bse", x, p["router"])
    gates = jax.nn.softmax(logits, -1)
    tg, ti = jax.lax.top_k(gates, cfg.experts_per_token)
    tg = tg / tg.sum(-1, keepdims=True)
    def expert(e, v):
        h = jax.nn.silu(v @ p["w_gate"][e]) * (v @ p["w_up"][e])
        return h @ p["w_down"][e]
    ref = jnp.zeros_like(x)
    for b in range(2):
        for s in range(16):
            acc = jnp.zeros((cfg.d_model,))
            for k in range(cfg.experts_per_token):
                acc += tg[b, s, k] * expert(int(ti[b, s, k]), x[b, s])
            ref = ref.at[b, s].set(acc)
    np.testing.assert_allclose(y, ref, rtol=2e-3, atol=2e-3)


