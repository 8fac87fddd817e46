"""LM stacks: decoder-only (dense/MoE/SSM/hybrid), enc-dec (Whisper), VLM.

Layout: params = {embed, periods (stacked, leading dim = num_periods),
final_norm, unembed [, lead, pos_embed, encoder]}. The layer stack runs as
a ``lax.scan`` over periods; a period is one repetition of
``cfg.block_pattern`` (1 layer for uniform archs, 8 for Jamba). Leading
dense layers (``cfg.first_dense_layers``, DeepSeek's layer 0) are a stack
of their own, ``lead``, scanned before the periods with the dense view of
the config (``lead_config``). Caches ride the scan as xs/ys.
docs/design.md §7 explains the cost-extrapolation contract: the scan body
is identical at any depth, so the dry-run can compile depth-2/depth-4
variants to recover exact per-layer costs.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .. import obs
from ..configs.base import ATTN, MAMBA, RWKV, ModelConfig
from .layers import (attention, dense_init, flash_engages, init_attention,
                     init_mla, init_mlp, mla_attention, mlp, rms_norm,
                     subkey)
from .moe import init_moe, moe_ffn
from .ssm import (init_mamba_block, init_mamba_state, init_rwkv_block,
                  init_rwkv_state, mamba_block, rwkv_block)

CE_CHUNKS = 4            # sequence chunks for the cross-entropy epilogue
MLA_SERVING = ("latent attention (MLA) is trained but not served: prefill "
               "and decode need a latent paged KV cache (ROADMAP R10)")


# --------------------------------------------------------------------- #
# init
# --------------------------------------------------------------------- #
def _ffn_is_moe(cfg: ModelConfig, pos_in_period: int) -> bool:
    return cfg.is_moe and (pos_in_period % cfg.moe_every == cfg.moe_offset)


def lead_config(cfg: ModelConfig) -> ModelConfig:
    """The leading dense layers' view of the config: same attention, a
    dense SwiGLU of width ``d_ff``."""
    return dataclasses.replace(
        cfg, num_layers=cfg.first_dense_layers, first_dense_layers=0,
        num_experts=0, experts_per_token=0, num_shared_experts=0,
        block_pattern=(ATTN,))


def init_block(key, cfg: ModelConfig, kind: str, pos: int, dtype,
               cross_attn: bool = False):
    d = cfg.d_model
    if kind == RWKV:
        return {"rwkv": init_rwkv_block(subkey(key, "rwkv"), cfg, dtype)}
    p: Dict[str, Any] = {}
    if kind == ATTN:
        p["ln_attn"] = jnp.ones((d,), dtype)
        p["attn"] = (init_mla if cfg.is_mla else init_attention)(
            subkey(key, "attn"), cfg, dtype)
        if cross_attn:
            p["ln_cross"] = jnp.ones((d,), dtype)
            p["cross"] = init_attention(subkey(key, "cross"), cfg, dtype)
    else:  # MAMBA
        p["mamba"] = init_mamba_block(subkey(key, "mamba"), cfg, dtype)
    p["ln_ffn"] = jnp.ones((d,), dtype)
    if _ffn_is_moe(cfg, pos):
        p["moe"] = init_moe(subkey(key, "moe"), cfg, dtype)
    else:
        p["mlp"] = init_mlp(subkey(key, "mlp"), d, cfg.d_ff, dtype)
    return p


def init_period(key, cfg: ModelConfig, dtype, cross_attn=False):
    return {f"blk{i}": init_block(subkey(key, i), cfg, kind, i, dtype, cross_attn)
            for i, kind in enumerate(cfg.pattern)}


def init_lm(key, cfg: ModelConfig, max_seq: int, dtype=None):
    """Full parameter tree. Usable under jax.eval_shape for the dry-run."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    d, Vp = cfg.d_model, cfg.padded_vocab
    periods = jax.vmap(
        lambda k: init_period(k, cfg, dtype, cross_attn=cfg.encoder_layers > 0)
    )(jax.random.split(subkey(key, "periods"), cfg.num_periods))
    params = {
        "embed": dense_init(subkey(key, "embed"), (Vp, d), dtype),
        "periods": periods,
        "final_norm": jnp.ones((d,), dtype),
        "unembed": dense_init(subkey(key, "unembed"), (d, Vp), dtype),
    }
    if cfg.first_dense_layers:
        lcfg = lead_config(cfg)
        params["lead"] = jax.vmap(lambda k: init_period(k, lcfg, dtype))(
            jax.random.split(subkey(key, "lead"), cfg.first_dense_layers))
    if cfg.rope_theta <= 0:                      # learned absolute positions
        params["pos_embed"] = dense_init(subkey(key, "pos"), (max_seq, d), dtype)
    if cfg.encoder_layers:
        enc_cfg = dataclasses.replace(cfg, block_pattern=(ATTN,),
                                      num_experts=0, sliding_window=0)
        params["encoder"] = {
            "pos_embed": dense_init(subkey(key, "encpos"),
                                    (cfg.encoder_seq, d), dtype),
            "periods": jax.vmap(
                lambda k: init_period(k, enc_cfg, dtype)
            )(jax.random.split(subkey(key, "enc"), cfg.encoder_layers)),
            "final_norm": jnp.ones((d,), dtype),
        }
    return params


# --------------------------------------------------------------------- #
# blocks
# --------------------------------------------------------------------- #
def apply_block(p, x, cfg: ModelConfig, kind: str, pos: int, rules, *,
                positions, mode: str, cache=None, cache_len=None,
                enc_out=None, cross_cache=None, causal: bool = True,
                paged=None, full_kv: bool = False, flash: bool = False):
    """Returns (x, new_cache_entry, moe_stats).

    paged: (page_table, seq_lens) — decode against the paged KV pool
    (serve subsystem); full_kv: prefill returns the un-rolled full-length
    KV even for SWA archs (the paged pool stores absolute positions and
    applies the window as a mask instead of a ring buffer); flash: the
    self-attention may run in the flash kernel (``attention``). moe_stats:
    (held rows, most rows on one expert) of an MoE FFN, else None.
    """
    if kind == RWKV:
        state = cache if mode == "decode" else None
        x, st = rwkv_block(p["rwkv"], x, cfg, rules, state)
        return x, (st if mode in ("decode", "prefill") else None), None

    new_cache: Dict[str, Any] = {}
    if kind == ATTN:
        h = rms_norm(x, p["ln_attn"], cfg.norm_eps)
        window = cfg.sliding_window
        if cfg.is_mla:
            if mode != "train":
                raise NotImplementedError(MLA_SERVING)
            y = mla_attention(p["attn"], h, cfg, rules, positions)
        elif mode == "decode":
            y, kv = attention(p["attn"], h, cfg, rules, positions,
                              causal=True, window=window,
                              cache=(cache["k"], cache["v"]),
                              cache_len=cache_len, paged=paged)
            new_cache.update(k=kv[0], v=kv[1])
        else:
            y, kv = attention(p["attn"], h, cfg, rules, positions,
                              causal=causal, window=window,
                              write_cache=(mode == "prefill"), flash=flash)
            if mode == "prefill":
                k, v = kv
                if window and k.shape[1] > window and not full_kv:
                    p0 = k.shape[1] - window         # ring-align SWA cache
                    k = jnp.roll(k[:, -window:], p0 % window, axis=1)
                    v = jnp.roll(v[:, -window:], p0 % window, axis=1)
                new_cache.update(k=k, v=v)
        x = x + y
        if "ln_cross" in p:                          # decoder cross-attention
            h = rms_norm(x, p["ln_cross"], cfg.norm_eps)
            if mode == "decode":
                kv_o = (cross_cache["ck"], cross_cache["cv"])
                new_cache.update(ck=kv_o[0], cv=kv_o[1])
            else:
                kv_o = _cross_kv(p["cross"], enc_out, cfg, rules)
                if mode == "prefill":
                    new_cache.update(ck=kv_o[0], cv=kv_o[1])
            y, _ = attention(p["cross"], h, cfg, rules, positions,
                             causal=False, kv_override=kv_o)
            x = x + y
    else:                                            # MAMBA
        state = cache if mode == "decode" else None
        x, st = mamba_block(p["mamba"], x, cfg, rules, state)
        if mode in ("decode", "prefill"):
            new_cache.update(st)

    h = rms_norm(x, p["ln_ffn"], cfg.norm_eps)
    stats = None
    if "moe" in p:
        y, stats = moe_ffn(p["moe"], h, cfg, rules)
    else:
        y = mlp(p["mlp"], h, rules)
    x = rules.act_btd(x + y)
    return x, (new_cache if mode in ("decode", "prefill") else None), stats


def _cross_kv(p, enc_out, cfg: ModelConfig, rules):
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"])
    dup = rules.attn.kv_dup if rules.attn.kind == "tp" else 1
    if dup > 1:
        k = jnp.repeat(k, dup, axis=2)
        v = jnp.repeat(v, dup, axis=2)
    return k, v


# --------------------------------------------------------------------- #
# period stack (scan)
# --------------------------------------------------------------------- #
def run_periods(periods, x, cfg: ModelConfig, rules, *, positions, mode,
                caches=None, cache_len=None, enc_out=None, remat=True,
                pattern=None, unroll=False, paged=None, full_kv=False):
    """Scan the period stack. caches: stacked pytree (leading dim = periods).

    ``unroll=True`` replaces the lax.scan with a python loop over period
    slices — used by the dry-run depth variants so ``cost_analysis`` counts
    every layer (scan bodies are costed once; docs/design.md §7).
    ``paged``/``full_kv`` ride through to apply_block (serve subsystem);
    the page table is shared by every layer, so it is closed over rather
    than scanned.
    """
    pattern = pattern or cfg.pattern

    def body(carry, xs):
        h = carry
        pparams, pcache = xs
        new_caches = []
        for i, kind in enumerate(pattern):
            ci = None if pcache is None else pcache[i]
            h, nc, _ = apply_block(
                pparams[f"blk{i}"], h, cfg, kind, i, rules,
                positions=positions, mode=mode, cache=ci,
                cache_len=cache_len, enc_out=enc_out, cross_cache=ci,
                paged=paged, full_kv=full_kv)
            new_caches.append(nc)
        out_c = tuple(new_caches) if mode in ("decode", "prefill") else None
        return h, out_c

    if remat and mode == "train":
        body = jax.checkpoint(body)

    if unroll:
        n = jax.tree.leaves(periods)[0].shape[0]
        outs = []
        for p_idx in range(n):
            xs_i = (jax.tree.map(lambda a: a[p_idx], periods),
                    None if caches is None
                    else jax.tree.map(lambda a: a[p_idx], caches))
            x, out_c = body(x, xs_i)
            outs.append(out_c)
        if mode in ("decode", "prefill"):
            new_caches = jax.tree.map(lambda *ls: jnp.stack(ls), *outs)
        else:
            new_caches = None
        return x, new_caches

    xs = (periods, caches)
    x, new_caches = jax.lax.scan(body, x, xs)
    return x, new_caches


def run_periods_paired(periods, x_pair, cfg: ModelConfig, rules, *,
                       positions, seed, eps, salts, sizes, remat=True,
                       unroll=False, enc_pair=(None, None)):
    """Antithetic forward of the elastic step: advance the theta+eps*z and
    theta-eps*z probes through the layer stack *together*. Each layer's
    slice is perturbed where it is consumed, one noise generation for both
    signs, so no full-size perturbed copy of the stack exists, and under
    FSDP each layer's weight all-gather is paid once for both passes.

    Exactness: the per-slice noise equals the stacked-leaf noise by the
    flat-offset property of core/prng.py, so each perturbed slice is
    bitwise that of ``zo.perturb`` on the stacked leaf. Train mode only.

    Nothing is differentiated through this forward (its output is
    stop-gradiented), so its causal self-attention over positions
    0..S-1 runs in the Pallas flash kernel where ``layers.flash_engages``
    allows; the attention layer-passes that do are counted at trace time
    under ``attn.flash_passes`` (docs/observability.md).

    Returns (x_pair, stats): stats is None without MoE FFNs, else the
    rows routed to held experts summed over the layers and both probes
    and the most rows on one held expert in one layer (int32).
    """
    from ..core import zo as zo_mod
    pattern = cfg.pattern

    def one(h, pparams, enc_out):
        stats = []
        for i, kind in enumerate(pattern):
            h, _, st = apply_block(pparams[f"blk{i}"], h, cfg, kind, i,
                                   rules, positions=positions, mode="train",
                                   enc_out=enc_out, flash=True)
            if st is not None:
                stats.append(st)
        return h, stats

    def body(carry, xs):
        hp, hm = carry
        pparams, p_idx = xs
        if rules.strategy == "fsdp" and rules.mesh is not None:
            # gather each layer's weights ONCE (replicated), then derive the
            # +/- perturbed copies locally — this is the whole point of the
            # paired forward: without it GSPMD gathers both perturbed copies.
            pparams = jax.tree.map(
                lambda a: rules.wsc(a, *((None,) * a.ndim)), pparams)
        pp, pm = zo_mod.perturb_slice_pair(pparams, salts, sizes, p_idx,
                                           seed, eps)
        # the perturbed pair is made once and then read by the matmuls:
        # fused into a matmul as an operand, the noise would be generated
        # again for every weight tile
        pp, pm = jax.lax.optimization_barrier((pp, pm))
        hp, sp = one(hp, pp, enc_pair[0])
        hm, sm = one(hm, pm, enc_pair[1])
        st = sp + sm
        if not st:
            return (hp, hm), None
        return (hp, hm), (sum(a for a, _ in st),
                          functools.reduce(jnp.maximum, [b for _, b in st]))

    if remat:
        body = jax.checkpoint(body)
    n = jax.tree.leaves(periods)[0].shape[0]
    if flash_engages(cfg, rules, x_pair[0].shape[1]):
        obs.get().counter("attn.flash_passes").inc(
            2 * n * sum(kind == ATTN for kind in pattern))
    idx = jnp.arange(n, dtype=jnp.int32)
    if unroll:
        ys = []
        for i in range(n):
            x_pair, y = body(x_pair, (jax.tree.map(lambda a: a[i], periods),
                                      jnp.int32(i)))
            ys.append(y)
        ys = None if ys[0] is None else jax.tree.map(
            lambda *a: jnp.stack(a), *ys)
    else:
        x_pair, ys = jax.lax.scan(body, x_pair, (periods, idx))
    if ys is None:
        return x_pair, None
    return x_pair, (jnp.sum(ys[0]), jnp.max(ys[1]))


# --------------------------------------------------------------------- #
# embedding / head
# --------------------------------------------------------------------- #
def embed(params, tokens, cfg: ModelConfig, rules, positions,
          img_embeds=None):
    pos_rows = (jnp.take(params["pos_embed"], positions, axis=0)
                if "pos_embed" in params else None)
    return embed_rows(jnp.take(params["embed"], tokens, axis=0), rules,
                      img_embeds, pos_rows)


def embed_rows(x, rules, img_embeds=None, pos_rows=None):
    """The embedding from its gathered rows: token rows ``x`` [B, S, d],
    image embeddings put first, learned position rows added."""
    if img_embeds is not None:
        x = jnp.concatenate([img_embeds.astype(x.dtype), x], axis=1)
    if pos_rows is not None:
        x = x + pos_rows
    return rules.act_btd(x)


def run_encoder(params, frames, cfg: ModelConfig, rules, unroll=False):
    enc = params["encoder"]
    x = frames + enc["pos_embed"][None, :frames.shape[1]]
    x = rules.act_btd(x.astype(frames.dtype))
    pos = jnp.broadcast_to(jnp.arange(frames.shape[1], dtype=jnp.int32),
                           frames.shape[:2])
    enc_cfg = dataclasses.replace(cfg, block_pattern=(ATTN,), num_experts=0,
                                  sliding_window=0, rope_theta=0.0)

    def body(h, pparams):
        h, _, _ = apply_block(pparams["blk0"], h, enc_cfg, ATTN, 0, rules,
                              positions=pos, mode="encode", causal=False)
        return h, None

    if unroll:
        n = jax.tree.leaves(enc["periods"])[0].shape[0]
        for i in range(n):
            x, _ = body(x, jax.tree.map(lambda a: a[i], enc["periods"]))
    else:
        x, _ = jax.lax.scan(body, x, enc["periods"])
    return rms_norm(x, enc["final_norm"], cfg.norm_eps)


def head_logits(params, x, cfg: ModelConfig, rules):
    h = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", h, params["unembed"])
    return rules.logits(logits)


def _chunk_nll(w, hc, yc, mc, Vp, rules):
    """(summed masked NLL, summed mask) of one sequence chunk."""
    logits = jnp.einsum("bsd,dv->bsv", hc, w)
    logits = rules.logits(logits).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(yc, Vp, dtype=logits.dtype)
    ll = jnp.sum(logits * onehot, axis=-1)
    return jnp.sum((logz - ll) * mc), jnp.sum(mc)


def lm_loss(params, x, labels, mask, cfg: ModelConfig, rules):
    """Chunked CE over the (vocab-sharded) logits. Returns scalar fp32."""
    B, S, _ = x.shape
    n = CE_CHUNKS if S % CE_CHUNKS == 0 and S >= CE_CHUNKS else 1
    c = S // n
    h = rms_norm(x, params["final_norm"], cfg.norm_eps)
    tot = jnp.float32(0)
    cnt = jnp.float32(0)
    for i in range(n):
        t, k = _chunk_nll(
            params["unembed"],
            jax.lax.slice_in_dim(h, i * c, (i + 1) * c, axis=1),
            jax.lax.slice_in_dim(labels, i * c, (i + 1) * c, axis=1),
            jax.lax.slice_in_dim(mask, i * c, (i + 1) * c, axis=1),
            cfg.padded_vocab, rules)
        tot = tot + t
        cnt = cnt + k
    return tot / jnp.maximum(cnt, 1.0)


def lm_loss_scanned(params, x, labels, mask, cfg: ModelConfig, rules):
    """``lm_loss`` with the chunks as a scan whose body keeps only its
    logits matmul for the backward (``dots_saveable``).

    The head's gradient then accumulates chunk by chunk in one buffer,
    where under ``lm_loss`` the compiler holds a full-size partial
    gradient per chunk and sums them as it likes. The paired elastic
    step, one program that differentiates both probes' heads, takes this
    one: at qwen3-4b's untied head those partials were most of the step's
    temporaries. The carry rounds the sum to the head's dtype once a
    chunk, so the gradient is not bitwise ``lm_loss``'s; the other
    training paths keep ``lm_loss`` and the streams their tests pin.
    """
    B, S, _ = x.shape
    n = CE_CHUNKS if S % CE_CHUNKS == 0 and S >= CE_CHUNKS else 1
    c = S // n
    h = rms_norm(x, params["final_norm"], cfg.norm_eps)
    w = params["unembed"]

    def chunk(carry, xs):
        t, k = _chunk_nll(w, *xs, cfg.padded_vocab, rules)
        return (carry[0] + t, carry[1] + k), None

    def chunks(a):                      # [B, S, ...] -> [n, B, c, ...]
        return jnp.swapaxes(a.reshape((B, n, c) + a.shape[2:]), 0, 1)

    chunk = jax.checkpoint(chunk,
                           policy=jax.checkpoint_policies.dots_saveable)
    (tot, cnt), _ = jax.lax.scan(chunk, (jnp.float32(0), jnp.float32(0)),
                                 (chunks(h), chunks(labels), chunks(mask)))
    return tot / jnp.maximum(cnt, 1.0)


# --------------------------------------------------------------------- #
# cache construction
# --------------------------------------------------------------------- #
def make_caches(cfg: ModelConfig, B: int, seq_len: int, rules, dtype=None):
    """Zero caches, stacked [periods, ...], matching run_periods xs layout."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    dup = rules.attn.kv_dup if rules.attn.kind == "tp" else 1
    KVd = cfg.num_kv_heads * dup
    T = min(seq_len, cfg.sliding_window) if cfg.sliding_window else seq_len
    per_period = []
    for i, kind in enumerate(cfg.pattern):
        if kind == ATTN:
            entry = {"k": jnp.zeros((B, T, KVd, cfg.head_dim), dtype),
                     "v": jnp.zeros((B, T, KVd, cfg.head_dim), dtype)}
            if cfg.encoder_layers:
                entry["ck"] = jnp.zeros((B, cfg.encoder_seq, KVd, cfg.head_dim), dtype)
                entry["cv"] = jnp.zeros((B, cfg.encoder_seq, KVd, cfg.head_dim), dtype)
        elif kind == MAMBA:
            entry = init_mamba_state(cfg, B, dtype)
        else:
            entry = init_rwkv_state(cfg, B, dtype)
        per_period.append(entry)
    stacked = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (cfg.num_periods,) + a.shape).copy(),
        tuple(per_period))
    return stacked


def make_paged_caches(cfg: ModelConfig, slots: int, num_pages: int,
                      page_size: int, rules, dtype=None):
    """Paged serve caches, same pytree structure as ``make_caches``.

    Attention KV lives in a global page pool [periods, num_pages, page_size,
    KVd, Dh] shared by all sequences (page 0 is the reserved null page);
    recurrent (mamba/rwkv) state and cross-attention KV are O(1)-per-token
    or fixed-size, so they stay dense per slot: [periods, slots, ...].
    """
    dtype = dtype or jnp.dtype(cfg.dtype)
    dup = rules.attn.kv_dup if rules.attn.kind == "tp" else 1
    KVd = cfg.num_kv_heads * dup
    per_period = []
    for i, kind in enumerate(cfg.pattern):
        if kind == ATTN:
            entry = {
                "k": jnp.zeros((num_pages, page_size, KVd, cfg.head_dim),
                               dtype),
                "v": jnp.zeros((num_pages, page_size, KVd, cfg.head_dim),
                               dtype)}
            if cfg.encoder_layers:
                entry["ck"] = jnp.zeros((slots, cfg.encoder_seq, KVd,
                                         cfg.head_dim), dtype)
                entry["cv"] = jnp.zeros((slots, cfg.encoder_seq, KVd,
                                         cfg.head_dim), dtype)
        elif kind == MAMBA:
            entry = init_mamba_state(cfg, slots, dtype)
        else:
            entry = init_rwkv_state(cfg, slots, dtype)
        per_period.append(entry)
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (cfg.num_periods,) + a.shape).copy(),
        tuple(per_period))
