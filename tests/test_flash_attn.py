"""Flash-attention Pallas kernel vs the model's XLA attention (interpret
mode), on the model's grouped-query layout: q [B, S, KV, G, Dh], k/v
[B, S, KV, Dh]."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attn import blocks, flash_attention
from repro.kernels.ref import flash_attention_ref
from repro.models.layers import _attend_block

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _qkv(B, KV, G, Sq, Sk, D, dtype, seed):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, Sq, KV, G, D)) * 0.3, dtype)
    k = jnp.asarray(rng.normal(size=(B, Sk, KV, D)) * 0.3, dtype)
    v = jnp.asarray(rng.normal(size=(B, Sk, KV, D)), dtype)
    return q, k, v


def _mask(Sq, Sk, causal, window):
    q_pos = jnp.arange(Sq)[:, None]
    k_pos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    return mask[None]


@pytest.mark.parametrize("B,KV,G,Sq,Sk,D,causal,win,dtype", [
    (1, 2, 1, 128, 128, 64, True, 0, jnp.float32),
    (2, 1, 1, 256, 256, 128, True, 0, jnp.float32),
    (1, 1, 1, 128, 256, 64, False, 0, jnp.float32),   # cross-attention shape
    (1, 2, 1, 256, 256, 64, True, 128, jnp.float32),  # sliding window
    (1, 1, 1, 384, 384, 128, True, 0, jnp.float32),
    # the model's GQA layout, query heads per KV head folded in
    (1, 2, 1, 256, 256, 128, True, 0, jnp.float32),
    (1, 2, 4, 256, 256, 128, True, 0, jnp.bfloat16),
    (1, 2, 4, 1024, 1024, 128, True, 0, jnp.bfloat16),
    (1, 2, 2, 512, 512, 128, True, 0, jnp.bfloat16),
])
def test_flash_matches_ref(B, KV, G, Sq, Sk, D, causal, win, dtype):
    """The kernel against ``_attend_block`` (the model's attention) within
    the dtype's tolerance; the off-TPU reference is ``_attend_block``
    bitwise."""
    q, k, v = _qkv(B, KV, G, Sq, Sk, D, dtype, Sq + Sk + D + G)
    o1 = flash_attention(q, k, v, causal=causal, window=win, interpret=True)
    o2 = _attend_block(q, k, v, _mask(Sq, Sk, causal, win), D ** -0.5)
    assert o1.dtype == o2.dtype == dtype
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32),
                               rtol=TOL[dtype], atol=TOL[dtype])
    ref = flash_attention_ref(q, k, v, causal=causal, window=win)
    assert np.array_equal(np.asarray(ref, np.float32),
                          np.asarray(o2, np.float32))


def test_flash_bf16_io():
    q, k, v = _qkv(1, 2, 1, 128, 128, 64, jnp.bfloat16, 1)
    o1 = flash_attention(q, k, v, interpret=True)
    o2 = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32),
                               rtol=2e-2, atol=2e-2)
    assert o1.dtype == jnp.bfloat16


@pytest.mark.parametrize("changed_from", [256, 300, 511])
def test_causal_mask_is_exact(changed_from):
    """A query's output is bitwise the same when keys and values after it
    change, at a tile boundary and inside a diagonal tile."""
    S = 512
    q, k, v = _qkv(1, 2, 2, S, S, 128, jnp.bfloat16, 3)
    _, k2, v2 = _qkv(1, 2, 2, S, S, 128, jnp.bfloat16, 4)
    later = (jnp.arange(S) >= changed_from)[None, :, None, None]
    o1 = flash_attention(q, k, v, interpret=True)
    o2 = flash_attention(q, jnp.where(later, k2, k), jnp.where(later, v2, v),
                         interpret=True)
    assert np.array_equal(np.asarray(o1[:, :changed_from], np.float32),
                          np.asarray(o2[:, :changed_from], np.float32))
    assert not np.array_equal(np.asarray(o1[:, changed_from:], np.float32),
                              np.asarray(o2[:, changed_from:], np.float32))


@pytest.mark.parametrize("S", [512, 1024])
def test_tiles_above_the_diagonal_are_never_read(S):
    """NaN in k and v after the last query of the first q-tile reaches
    none of that tile's outputs: the kernel reads no k/v tile above the
    diagonal. The XLA attention, which masks a full score block, lets the
    NaN through (0 * NaN in PV)."""
    tile = blocks(S)
    q, k, v = _qkv(1, 2, 4, S, S, 128, jnp.bfloat16, 5)
    after = (jnp.arange(S) >= tile)[None, :, None, None]
    kn = jnp.where(after, jnp.nan, k).astype(k.dtype)
    vn = jnp.where(after, jnp.nan, v).astype(v.dtype)
    clean = flash_attention(q, k, v, interpret=True)
    o = np.asarray(flash_attention(q, kn, vn, interpret=True), np.float32)
    assert np.isfinite(o[:, :tile]).all()
    assert np.array_equal(o[:, :tile], np.asarray(clean[:, :tile], np.float32))
    xla = _attend_block(q, kn, vn, _mask(S, S, True, 0), 128 ** -0.5)
    assert np.isnan(np.asarray(xla[:, :tile], np.float32)).any()


def test_blocks_span_at_least_two_key_tiles():
    assert [blocks(s) for s in (128, 256, 384, 512, 1024, 2048, 4096)] == \
        [None, 128, 128, 256, 512, 512, 512]
    assert blocks(1000) is None
