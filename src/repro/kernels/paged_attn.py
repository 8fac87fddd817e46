"""Pallas TPU fused paged-attention decode megastep (vLLM-style tables).

One kernel call per decode step does BOTH halves of the token's cache
traffic:

  1. **fused KV write** — the incoming token's K/V row is DMA'd straight
     into its pool slot (``page_table[b, pos // ps], pos % ps``) before any
     page is read, so the pool-wide ``k_pool.at[pidx, slot].set`` scatter
     that used to run in models/layers.py (forcing XLA to copy/alias-check
     the whole pool every token) disappears; the pools are
     ``input_output_aliases``-donated and updated in place;
  2. **megastep attention** — pages stream HBM->VMEM ``pages_per_block``
     at a time through double-width VMEM scratch, and every KV head is
     batched into one ``[KVd*G, Dh]`` accumulator tile per row; the grid
     is (B, ceil(P / F)). The block's K/V rows are read in pool order,
     ``(position, kv head)``, and every query head is scored against all
     of them with the other heads' rows masked out: two plain 2-D
     matmuls per block, with no per-head slice or concatenation (which
     the TPU compiler refuses at KVd*G rows of G < 8), for KVd times the
     MXU work of a per-head kernel.

Layouts:
  q          [B, KVd, G, Dh]     (G = query heads per KV head)
  k/v new    [B, KVd, Dh]        current token's K/V (pool dtype)
  k/v pool   [N_pages, page_size, KVd, Dh]   (ANY/HBM; aliased outputs)
  page_table [B, P] int32        (P = max pages per sequence; 0 = null page)
  seq_lens   [B] int32           (tokens already cached == write position)

The page table and seq_lens ride as scalar-prefetch operands
(``PrefetchScalarGridSpec``) so physical page ids are known before the
body runs. A page block is skipped — no DMA, no FLOPs — when it starts
past ``seq_lens[b]`` or its table entry is the **null page** (entry 0):
that is how SWA reclamation works, the scheduler re-nulls fully
windowed-out entries after freeing their pages and the kernel never
touches them again.

TPU notes: Dh should be 64/128 and KVd*G a multiple of the 8-sublane
tile. CPU tests run ``interpret=True``, where tiling constraints are
relaxed; tests/test_tpu_compile.py compiles the kernel for a v5e.
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
NULL_PAGE = 0


def _kernel(pt_ref, sl_ref, q_ref, knew_ref, vnew_ref, kpool_in, vpool_in,
            head_ok_ref, col_t_ref, row_t_ref, o_ref, kpool_ref, vpool_ref,
            k_vmem, v_vmem, acc_ref, m_ref, l_ref, ksem, vsem, wsem, *,
            scale, window, page_size, f_pages):
    b = pl.program_id(0)
    pb = pl.program_id(1)
    ps = page_size
    pos = sl_ref[b]                          # current absolute position

    @pl.when(pb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        # fused KV write: land the incoming token in its slot before any
        # page read. Inactive rows (seq_len 0, all-null table) write into
        # the reserved null page, which is never attended.
        wpage = pt_ref[b, pos // ps]
        wslot = pos % ps
        kcp = pltpu.make_async_copy(
            knew_ref.at[b], kpool_ref.at[wpage, wslot], wsem.at[0])
        vcp = pltpu.make_async_copy(
            vnew_ref.at[b], vpool_ref.at[wpage, wslot], wsem.at[1])
        kcp.start()
        vcp.start()
        kcp.wait()
        vcp.wait()

    base = pb * f_pages
    phys = [pt_ref[b, base + j] for j in range(f_pages)]
    live = [(jnp.int32((base + j) * ps) <= pos) & (phys[j] != NULL_PAGE)
            for j in range(f_pages)]
    for j in range(f_pages):
        @pl.when(live[j])
        def _copy(j=j):
            pltpu.make_async_copy(
                kpool_ref.at[phys[j]], k_vmem.at[j], ksem.at[j]).start()
            pltpu.make_async_copy(
                vpool_ref.at[phys[j]], v_vmem.at[j], vsem.at[j]).start()
    for j in range(f_pages):
        @pl.when(live[j])
        def _wait(j=j):
            pltpu.make_async_copy(
                kpool_ref.at[phys[j]], k_vmem.at[j], ksem.at[j]).wait()
            pltpu.make_async_copy(
                vpool_ref.at[phys[j]], v_vmem.at[j], vsem.at[j]).wait()

    @pl.when(jnp.int32(base * ps) <= pos)    # block holds a live position
    def _attend():
        R, Dh = q_ref.shape[1], q_ref.shape[2]
        # the block's K/V rows in pool order, (position, kv head) — every
        # query head is scored against every row and the rows of other
        # heads are masked out, so no per-head slice or concat is needed
        k = k_vmem[...].astype(jnp.float32).reshape(-1, Dh)   # [C, Dh]
        v = v_vmem[...].astype(jnp.float32).reshape(-1, Dh)
        col_t = col_t_ref[...]                  # [1, C] position in block
        row_t = row_t_ref[...]                  # [C, 1]

        def page_live(t):
            ok = jnp.zeros(t.shape, jnp.bool_)
            for j in range(f_pages):
                ok = ok | ((t >= j * ps) & (t < (j + 1) * ps) & live[j])
            return ok

        # dead pages inside a live block hold stale scratch; their softmax
        # weight is exactly 0, but 0 * garbage(NaN) would still poison the
        # weighted-value dot — select them to 0 before contracting.
        v = jnp.where(page_live(row_t), v, 0.0)
        s = jax.lax.dot_general(q_ref[0].astype(jnp.float32), k,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        t = jnp.int32(base * ps) + col_t
        mask = (t <= pos) & page_live(col_t) & (head_ok_ref[...] != 0)
        if window > 0:
            mask &= t > pos - window
        s = jnp.where(mask, s, NEG_INF)                      # [R, C]

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p_ = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p_, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p_, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(pb == pl.num_programs(1) - 1)
    def _done():
        o = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = o.astype(o_ref.dtype)


def default_pages_per_block(page_size: int, table_width: int) -> int:
    """Pages streamed per grid step: aim for a >=128-position KV tile."""
    return max(1, min(table_width, -(-128 // page_size)))


@functools.partial(
    jax.jit,
    static_argnames=("scale", "window", "pages_per_block", "interpret"))
def paged_attention_step(q, k_new, v_new, k_pool, v_pool, page_table,
                         seq_lens, *, scale: float | None = None,
                         window: int = 0, pages_per_block: int = 0,
                         interpret: bool = False):
    """Fused decode megastep: write the token's K/V, attend through pages.

    q [B,KVd,G,Dh], k_new/v_new [B,KVd,Dh] (pool dtype) ->
    (o [B,KVd,G,Dh], k_pool, v_pool) with the pools updated in place
    (input_output_aliases; callers should treat the inputs as donated).
    """
    B, KVd, G, Dh = q.shape
    _, page_size, _, _ = k_pool.shape
    P = page_table.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)
    F = pages_per_block or default_pages_per_block(page_size, P)
    F = min(F, P)
    PB = -(-P // F)
    if PB * F != P:       # pad the table with null pages (always skipped)
        page_table = jnp.pad(page_table, ((0, 0), (0, PB * F - P)),
                             constant_values=NULL_PAGE)
    R, C = KVd * G, F * page_size * KVd
    # static row/column layout of one block: query row r serves kv head
    # r // G; block row c holds position c // KVd of kv head c % KVd
    col = np.arange(C)
    head_ok = (np.arange(R)[:, None] // G == col[None, :] % KVd)
    col_t = (col // KVd).astype(np.int32)
    kern = functools.partial(_kernel, scale=scale, window=window,
                             page_size=page_size, f_pages=F)
    fixed = lambda b, p, pt, sl: (0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, PB),
        in_specs=[
            pl.BlockSpec((1, R, Dh), lambda b, p, pt, sl: (b, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.ANY),      # k_new
            pl.BlockSpec(memory_space=pltpu.ANY),      # v_new
            pl.BlockSpec(memory_space=pltpu.ANY),      # k_pool
            pl.BlockSpec(memory_space=pltpu.ANY),      # v_pool
            pl.BlockSpec((R, C), fixed),               # head_ok
            pl.BlockSpec((1, C), fixed),               # col_t
            pl.BlockSpec((C, 1), fixed),               # row_t
        ],
        out_specs=[
            pl.BlockSpec((1, R, Dh), lambda b, p, pt, sl: (b, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.ANY),
            pl.BlockSpec(memory_space=pltpu.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((F, page_size, KVd, Dh), k_pool.dtype),
            pltpu.VMEM((F, page_size, KVd, Dh), v_pool.dtype),
            pltpu.VMEM((R, Dh), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.SemaphoreType.DMA((F,)),
            pltpu.SemaphoreType.DMA((F,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    o, k_pool, v_pool = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, R, Dh), q.dtype),
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ],
        input_output_aliases={5: 1, 6: 2},
        interpret=interpret,
    )(page_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
      q.reshape(B, R, Dh), k_new.astype(k_pool.dtype),
      v_new.astype(v_pool.dtype), k_pool, v_pool,
      jnp.asarray(head_ok, jnp.int32), jnp.asarray(col_t[None, :]),
      jnp.asarray(col_t[:, None]))
    return o.reshape(B, KVd, G, Dh), k_pool, v_pool
