"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import math


import jax
import jax.numpy as jnp

from ..core import prng


def flash_attention_ref(q, k, v, *, causal=True, window=0, scale=None):
    """Dense-softmax oracle for kernels/flash_attn.py, same layout (q
    [B, Sq, KV, G, Dh], k/v [B, Sk, KV, Dh]) and precision: operands in
    their own dtype with float32 accumulation, float32 softmax, the
    probabilities cast to ``v.dtype`` for PV."""
    Sq, Sk, Dh = q.shape[1], k.shape[1], q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)
    s = jnp.einsum("bskgh,btkh->bksgt", q, k,
                   preferred_element_type=jnp.float32) * scale
    q_pos = jnp.arange(Sq)[:, None]
    k_pos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bksgt,btkh->bskgh", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(v.dtype)


def int8_matmul_ref(a: jax.Array, w: jax.Array):
    """a [M,K] int8, w [K,N] int8 -> (out int32 [M,N], maxabs int32 scalar)."""
    out = jax.lax.dot_general(a.astype(jnp.int32), w.astype(jnp.int32),
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return out, jnp.max(jnp.abs(out)).astype(jnp.int32)


def zo_perturb_ref(theta: jax.Array, seed: jax.Array, salt: int,
                   scale: jax.Array):
    """theta + scale * z with z = hash-gaussian over the *global* flat index
    (bitwise-identical to core/prng.normal on the same flat layout)."""
    flat = theta.reshape(-1)
    z = prng.normal(seed, salt, flat.shape)
    out = flat.astype(jnp.float32) + scale.astype(jnp.float32) * z
    return out.reshape(theta.shape).astype(theta.dtype)


def zo_fused_replay_ref(theta: jax.Array, seeds: jax.Array,
                        coeffs: jax.Array, salt: int):
    """Apply S ledger steps of P (seed, coeff) probe records to one leaf.

    Canonical fleet update stream (docs/fleet.md): per step, the probe
    contributions are accumulated in probe order in fp32, subtracted once,
    and cast to the parameter dtype; the next step starts from that cast
    value. This is bitwise the live path (S=1 applied per step), which is
    what makes ledger replay reproduce the canonical parameter stream
    exactly. seeds uint32 [S, P]; coeffs fp32 [S, P] (0 for masked probes).

    Deliberately a plain python loop over eagerly-dispatched ops: compiling
    the loop (fori_loop / jit) lets XLA contract the mul-add chain into
    FMAs, which shifts the stream by ~1 ulp relative to other call sites.
    Keep every caller on this eager entry point (kernels/ops.py off-TPU).
    """
    S, P = seeds.shape
    shape, dtype = theta.shape, theta.dtype
    n = theta.size
    x = theta.reshape(-1).astype(jnp.float32)
    for s in range(S):
        inner = jnp.zeros((n,), jnp.float32)
        for p in range(P):
            z = prng.normal(seeds[s, p], salt, (n,))
            inner = inner + coeffs[s, p] * z
        x = (x - inner).astype(dtype).astype(jnp.float32)
    return x.reshape(shape).astype(dtype)


def zo_fused_replay_int8_ref(theta: jax.Array, seeds: jax.Array,
                             gs: jax.Array, salt: int, r_max: int,
                             p_zero, shift: int):
    """int8-lane twin of zo_fused_replay_ref (docs/fleet.md record v2).

    Per committed step the per-probe integer updates psr(g*z, shift) are
    accumulated in int32 in probe order and clamped ONCE to [-127, 127]
    — the integer analogue of the fp32 accumulate-then-cast, stated by
    the engine (core/engine.py Int8Engine.zo_apply). Masked probes carry
    g = 0, an exact no-op. Integer ops are immune to FMA contraction, so
    this path matches the Pallas kernel and the live traced step bitwise
    on every backend.
    """
    from ..core.int8 import int8_noise, psr_shift
    S, P = seeds.shape
    n = theta.size
    x = theta.reshape(-1).astype(jnp.int32)
    pz = jnp.float32(p_zero)
    for s in range(S):
        acc = jnp.zeros((n,), jnp.int32)
        for p in range(P):
            z = int8_noise(seeds[s, p], salt, (n,), r_max, pz)
            acc = acc + psr_shift(gs[s, p].astype(jnp.int32) * z,
                                  jnp.int32(shift))
        x = jnp.clip(x - acc, -127, 127)
    return x.astype(jnp.int8).reshape(theta.shape)


def int8_perturb_ref(theta: jax.Array, seed: jax.Array, salt: int, k: int,
                     r_max: int, p_zero: jax.Array):
    """Alg. 2 perturbation on an int8 leaf (clamped +/- sparse uniform)."""
    from ..core.int8 import int8_noise
    z = int8_noise(seed, salt, theta.shape, r_max, p_zero)
    return jnp.clip(theta.astype(jnp.int32) + k * z, -127, 127).astype(jnp.int8)


NEG_INF = -1e30


def _monotone_key(x: jax.Array) -> jax.Array:
    """float32 -> uint32 order-preserving key (-0.0 canonicalized to +0.0,
    so key comparisons agree with float comparisons everywhere)."""
    x = x.astype(jnp.float32)
    # a select, not x + 0.0, which the compiler may fold away under jit
    x = jnp.where(x == 0.0, jnp.float32(0.0), x)
    s = jax.lax.bitcast_convert_type(x, jnp.int32)
    u = s.astype(jnp.uint32)
    return jnp.where(s < 0, ~u, u | jnp.uint32(0x80000000))


def topk_topp_mask_ref(logits: jax.Array, k: jax.Array, p: jax.Array):
    """Sort-free top-k/top-p filter: threshold-refine partial selection.

    logits [B, V] f32; k [B] int32 (<=0 disables); p [B] f32 in (0, 1]
    (>=1 disables). Returns logits with filtered entries at NEG_INF —
    the same keep sets as the full-sort reference (serve/sampler.py
    ``_top_k_mask``/``_top_p_mask``) without materializing a sort:

    * top-k: a 4-round byte-radix descent over the monotone float key
      finds the exact k-th largest *value*; keep = (x >= kth), which is
      bit-identical to the sorted threshold (ties keep everything equal,
      possibly more than k — the reference's semantics);
    * top-p: the same radix descent over probability mass finds the
      boundary value T where the nucleus crosses p, plus G = total mass
      strictly above T. Values above T are kept outright; the tied run at
      T is split by original index order (rank r kept iff G + r*p_T < p),
      mirroring the reference's stable descending sort. Only the boundary
      comparison is float-rounding sensitive (G accumulates in histogram
      order, the reference in sorted order) — identical on exactly
      representable mass grids, and never observable unless p lands
      within one ulp of a partial sum.
    """
    B, V = logits.shape
    rows = jnp.arange(B)[:, None]

    # ---- top-k: radix-select the exact k-th largest key -------------- #
    keys = _monotone_key(logits)
    krem = jnp.clip(k, 1, V).astype(jnp.int32)
    cand = jnp.ones((B, V), jnp.int32)
    kth = jnp.zeros((B,), jnp.uint32)
    for shift in (24, 16, 8, 0):
        byte = ((keys >> shift) & jnp.uint32(0xFF)).astype(jnp.int32)
        hist = jnp.zeros((B, 256), jnp.int32).at[rows, byte].add(cand)
        cnt_ge = jnp.cumsum(hist[:, ::-1], axis=1)[:, ::-1]
        above = cnt_ge - hist                  # strictly above bucket j
        cond = (above < krem[:, None]) & (cnt_ge >= krem[:, None])
        j = jnp.argmax(cond, axis=1).astype(jnp.int32)   # unique True
        krem = krem - jnp.take_along_axis(above, j[:, None], 1)[:, 0]
        kth = kth | (j.astype(jnp.uint32) << shift)
        cand = cand * (byte == j[:, None])
    keep = (keys >= kth[:, None]) | (k <= 0)[:, None]
    x = jnp.where(keep, logits, NEG_INF)

    # ---- top-p: refine the nucleus boundary value -------------------- #
    probs = jax.nn.softmax(x, axis=-1)
    keys = _monotone_key(x)
    cand_m = jnp.ones((B, V), jnp.float32)
    above_mass = jnp.zeros((B,), jnp.float32)
    tkey = jnp.zeros((B,), jnp.uint32)
    for shift in (24, 16, 8, 0):
        byte = ((keys >> shift) & jnp.uint32(0xFF)).astype(jnp.int32)
        mh = jnp.zeros((B, 256), jnp.float32).at[rows, byte].add(
            probs * cand_m)
        above = jnp.cumsum(mh[:, ::-1], axis=1)[:, ::-1] - mh \
            + above_mass[:, None]              # mass strictly above bucket
        cond = above < p[:, None]
        j = jnp.argmax(cond, axis=1).astype(jnp.int32)   # lowest such bucket
        above_mass = jnp.take_along_axis(above, j[:, None], 1)[:, 0]
        tkey = tkey | (j.astype(jnp.uint32) << shift)
        cand_m = cand_m * (byte == j[:, None])
    eq = keys == tkey[:, None]
    p_t = jnp.max(jnp.where(eq, probs, 0.0), axis=1)
    r = jnp.cumsum(eq, axis=1) - eq            # tie rank in index order
    keep_p = (keys > tkey[:, None]) \
        | (eq & (above_mass[:, None] + r * p_t[:, None] < p[:, None])) \
        | (p >= 1.0)[:, None]
    return jnp.where(keep_p, x, NEG_INF)


def paged_attn_step_ref(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                        k_pool: jax.Array, v_pool: jax.Array,
                        page_table: jax.Array, seq_lens: jax.Array, *,
                        scale: float, window: int = 0):
    """Write-then-gather-then-attend oracle for kernels/paged_attn.py.

    q [B,KVd,G,Dh]; k_new/v_new [B,KVd,Dh]; pools [N,ps,KVd,Dh];
    page_table [B,P]; seq_lens [B]. Mirrors the fused megastep: the
    token's K/V is scattered into its pool slot first, then the gathered
    [B, P*ps, KVd, Dh] cache is attended with the model's dense
    ``_attend_block`` so the serve path is *bitwise* the dense decode
    math — the parity tests (tests/test_serve_paged.py) rely on this.
    Null table entries (page 0 — unmapped tail or SWA-reclaimed) are
    masked per position, which is a no-op for live rows: every position
    ``t <= seq_len`` inside the window is backed by a real page.
    """
    from ..models.layers import _attend_block
    from ..serve.kv_pages import NULL_PAGE
    B, KVd, G, Dh = q.shape
    ps = k_pool.shape[1]
    pos = seq_lens.astype(jnp.int32)
    pidx = jnp.take_along_axis(page_table.astype(jnp.int32),
                               (pos // ps)[:, None], axis=1)[:, 0]
    k_pool = k_pool.at[pidx, pos % ps].set(k_new.astype(k_pool.dtype))
    v_pool = v_pool.at[pidx, pos % ps].set(v_new.astype(v_pool.dtype))
    k = k_pool[page_table].reshape(B, -1, KVd, Dh)
    v = v_pool[page_table].reshape(B, -1, KVd, Dh)
    t = jnp.arange(k.shape[1], dtype=jnp.int32)
    valid = t[None, :] <= pos[:, None]
    if window > 0:
        valid &= t[None, :] > pos[:, None] - window
    valid &= jnp.repeat(page_table != NULL_PAGE, ps, axis=1)
    out = _attend_block(q[:, None], k, v, valid[:, None, :], scale)
    return out[:, 0], k_pool, v_pool
