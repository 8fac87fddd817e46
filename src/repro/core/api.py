"""Public API: build (init, train_step, prefill_step, decode_step,
input_specs, shardings) for any (arch, shape, lane, mesh).

This is the layer the launcher, dry-run, benchmarks and examples consume.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.base import LaneConfig, ModelConfig, ShapeConfig
from ..models import transformer as tf
from ..models.transformer import (embed, head_logits, lm_loss,
                                  lm_loss_scanned, make_caches, run_encoder,
                                  run_periods)
from ..sharding.rules import ShardingRules
from . import elastic, zo as zo_mod
from .elastic import TrainState


def tail_periods(cfg: ModelConfig, lane: LaneConfig) -> int:
    """BP-tail size in periods (>=1, < num_periods)."""
    plen = len(cfg.pattern)
    k = max(1, -(-lane.bp_tail_layers // plen))          # ceil
    return min(k, cfg.num_periods - 1)


@dataclass
class BuiltModel:
    cfg: ModelConfig
    shape: ShapeConfig
    lane: LaneConfig
    rules: ShardingRules
    init: Callable
    loss_fn: Callable
    train_step: Callable
    prefill_step: Callable
    decode_step: Callable
    # serve subsystem entry points (src/repro/serve/): sampled serving needs
    # raw logits, and the paged variants address the KV pool via page tables.
    # Optional: builds that predate the serve path may leave them unset.
    prefill_logits: Optional[Callable] = None
    decode_step_paged: Optional[Callable] = None

    # ---- host-side helpers -------------------------------------------- #
    def input_specs(self) -> Dict[str, jax.ShapeDtypeStruct]:
        return build_input_specs(self.cfg, self.shape, self.lane, self.rules)

    def abstract_params(self):
        return jax.eval_shape(lambda: self.init(jax.random.key(0)))

    def abstract_state(self):
        params = self.abstract_params()
        return TrainState(params,
                          jax.ShapeDtypeStruct((), jnp.int32),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))

    def abstract_caches(self):
        return jax.eval_shape(
            lambda: split_caches(
                make_caches(self.cfg, self.shape.global_batch,
                            self.shape.seq_len, self.rules),
                self.cfg, self.lane))


def split_caches(caches, cfg: ModelConfig, lane: LaneConfig):
    k = tail_periods(cfg, lane)
    pz = cfg.num_periods - k
    zo_c = jax.tree.map(lambda a: a[:pz], caches)
    bp_c = jax.tree.map(lambda a: a[pz:], caches)
    return {"zo": zo_c, "bp": bp_c}


def build(cfg: ModelConfig, shape: ShapeConfig, lane: LaneConfig,
          rules: ShardingRules, remat: bool = True,
          scan_unroll: bool = False) -> BuiltModel:
    if cfg.is_mla and shape.kind in ("prefill", "decode"):
        raise NotImplementedError(tf.MLA_SERVING)
    K = tail_periods(cfg, lane)
    PZ = cfg.num_periods - K
    lead_cfg = tf.lead_config(cfg)
    n_img = cfg.num_image_tokens
    dtype = jnp.dtype(cfg.dtype)
    # ElasticZO: the ZO head is never differentiated — cut the grad chain so
    # the head's scan saves no residuals (the paper's memory claim; Eq. 4).
    stop_zo_grad = lane.lane != "full_bp"

    # ---------------- init -------------------------------------------- #
    def init(key):
        params = tf.init_lm(key, cfg, max_seq=shape.seq_len, dtype=dtype)
        periods = params.pop("periods")
        params["periods_zo"] = jax.tree.map(lambda a: a[:PZ], periods)
        params["periods_bp"] = jax.tree.map(lambda a: a[PZ:], periods)
        return params

    # ---------------- forward ------------------------------------------ #
    # The stack runs in two halves: the ZO head (embedding and
    # ``periods_zo``) and the BP tail (``periods_bp``). The training loss
    # names each half's device time (``zo_forward``, ``bp_tail``; see
    # core/zo.py); prefill and decode run the same halves unnamed.
    def zo_half(params, tokens, positions, mode, *, img_embeds=None,
                frames=None, caches=None, cache_len=None, paged=None,
                full_kv=False):
        """Embedding and the ZO periods -> (x, their caches, enc_out)."""
        enc_out = None
        if cfg.encoder_layers and mode != "decode":
            enc_out = run_encoder(params, frames, cfg, rules,
                                  unroll=scan_unroll)
        x = embed(params, tokens, cfg, rules, positions, img_embeds)
        if "lead" in params:                 # leading dense layers
            x, _ = run_periods(params["lead"], x, lead_cfg, rules,
                               positions=positions, mode=mode, remat=remat,
                               unroll=scan_unroll)
        x, ncz = run_periods(params["periods_zo"], x, cfg, rules,
                             positions=positions, mode=mode,
                             caches=None if caches is None else caches["zo"],
                             cache_len=cache_len, enc_out=enc_out,
                             remat=remat, unroll=scan_unroll, paged=paged,
                             full_kv=full_kv)
        if stop_zo_grad and mode == "train":
            x = jax.lax.stop_gradient(x)
            if enc_out is not None:
                enc_out = jax.lax.stop_gradient(enc_out)
        return x, ncz, enc_out

    def tail_half(params, x, positions, mode, *, enc_out=None, caches=None,
                  cache_len=None, paged=None, full_kv=False):
        """The BP-tail periods -> (x, their caches)."""
        return run_periods(params["periods_bp"], x, cfg, rules,
                           positions=positions, mode=mode,
                           caches=None if caches is None else caches["bp"],
                           cache_len=cache_len, enc_out=enc_out,
                           remat=remat, unroll=scan_unroll, paged=paged,
                           full_kv=full_kv)

    def backbone(params, tokens, positions, mode, *, img_embeds=None,
                 frames=None, caches=None, cache_len=None, paged=None,
                 full_kv=False):
        x, ncz, enc_out = zo_half(params, tokens, positions, mode,
                                  img_embeds=img_embeds, frames=frames,
                                  caches=caches, cache_len=cache_len,
                                  paged=paged, full_kv=full_kv)
        x, ncb = tail_half(params, x, positions, mode, enc_out=enc_out,
                           caches=caches, cache_len=cache_len, paged=paged,
                           full_kv=full_kv)
        new_caches = ({"zo": ncz, "bp": ncb}
                      if mode in ("decode", "prefill") else None)
        return x, new_caches

    # ---------------- train -------------------------------------------- #
    def loss_fn(params, batch):
        tokens = batch["tokens"]
        B, S_tok = tokens.shape
        S_tot = S_tok + n_img
        positions = jnp.broadcast_to(
            jnp.arange(S_tot, dtype=jnp.int32), (B, S_tot))
        with jax.named_scope(zo_mod.FORWARD):
            x, _, enc_out = zo_half(params, tokens, positions, "train",
                                    img_embeds=batch.get("img"),
                                    frames=batch.get("frames"))
        with jax.named_scope(zo_mod.TAIL):
            x, _ = tail_half(params, x, positions, "train", enc_out=enc_out)
            if n_img:
                x = x[:, n_img:]
            return lm_loss(params, x, batch["labels"], batch["mask"], cfg,
                           rules)

    # The elastic step perturbs where the weights are consumed: each layer's
    # slice inside the scan, the embedding's gathered rows, one noise
    # generation for both probe signs and no full-size perturbed copy. The
    # ``clean`` tail mode's third forward needs the whole unperturbed-point
    # pass and keeps the engine's materialised path (core/zo.py).
    paired_loss_fn = None
    if lane.lane == "elastic_zo" and lane.bp_grad_mode == "avg_perturbed":
        from ..models.transformer import embed_rows, run_periods_paired
        from . import prng

        def paired_loss(bp_part, zo_part, batch, key):
            tokens = batch["tokens"]
            B, S_tok = tokens.shape
            S_tot = S_tok + n_img
            positions = jnp.broadcast_to(
                jnp.arange(S_tot, dtype=jnp.int32), (B, S_tot))
            seed = prng.seed_from_key(key)
            eps = lane.zo_eps

            tok_p, tok_m = zo_mod.perturb_rows_pair(zo_part, "embed",
                                                    tokens, key, eps)
            pos_p, pos_m = (zo_mod.perturb_rows_pair(
                zo_part, "pos_embed", positions, key, eps)
                if "pos_embed" in zo_part else (None, None))
            # what is left (whisper's encoder) is perturbed whole
            rest = {k: v for k, v in zo_part.items()
                    if k not in ("periods_zo", "lead", "embed", "pos_embed")}
            rest_p, rest_m = zo_mod.perturb_pair(rest, key, eps)
            with jax.named_scope(zo_mod.FORWARD):
                enc_pair = (None, None)
                if cfg.encoder_layers:
                    enc_pair = (run_encoder(rest_p, batch["frames"], cfg,
                                            rules, unroll=scan_unroll),
                                run_encoder(rest_m, batch["frames"], cfg,
                                            rules, unroll=scan_unroll))
                x_pair = (embed_rows(tok_p, rules, batch.get("img"), pos_p),
                          embed_rows(tok_m, rules, batch.get("img"), pos_m))
                for group, gcfg in (("lead", lead_cfg), ("periods_zo", cfg)):
                    if group not in zo_part:
                        continue
                    salts, sizes = zo_mod.slice_noise_spec(
                        zo_part[group], f"['{group}']")
                    x_pair, stats = run_periods_paired(
                        zo_part[group], x_pair, gcfg, rules,
                        positions=positions, seed=seed, eps=eps, salts=salts,
                        sizes=sizes, remat=remat, unroll=scan_unroll,
                        enc_pair=enc_pair)
                counts = {} if stats is None else {
                    "moe.held_rows": stats[0],
                    "moe.max_expert_rows": stats[1]}
                xp, xm = x_pair
                xp = jax.lax.stop_gradient(xp)
                xm = jax.lax.stop_gradient(xm)
            losses = []
            with jax.named_scope(zo_mod.TAIL):
                for x, enc_out in zip((xp, xm), enc_pair):
                    x, _ = run_periods(
                        bp_part["periods_bp"], x, cfg, rules,
                        positions=positions, mode="train",
                        enc_out=None if enc_out is None
                        else jax.lax.stop_gradient(enc_out),
                        remat=remat, unroll=scan_unroll)
                    if n_img:
                        x = x[:, n_img:]
                    losses.append(lm_loss_scanned(
                        bp_part, x, batch["labels"], batch["mask"], cfg,
                        rules))
            return losses[0], losses[1], counts

        paired_loss_fn = paired_loss

    train_step = elastic.make_elastic_step(loss_fn, lane,
                                           paired_loss_fn=paired_loss_fn)

    # ---------------- serve -------------------------------------------- #
    def prefill_step(params, batch):
        tokens = batch["tokens"]
        B, S_tok = tokens.shape
        S_tot = S_tok + n_img
        positions = jnp.broadcast_to(
            jnp.arange(S_tot, dtype=jnp.int32), (B, S_tot))
        x, caches = backbone(params, tokens, positions, "prefill",
                             img_embeds=batch.get("img"),
                             frames=batch.get("frames"))
        logits = head_logits(params, x[:, -1:], cfg, rules)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, caches

    def decode_step(params, tokens, caches, cache_len):
        B = tokens.shape[0]
        positions = jnp.broadcast_to(cache_len.astype(jnp.int32), (B, 1))
        x, new_caches = backbone(params, tokens, positions, "decode",
                                 caches=caches, cache_len=cache_len)
        logits = head_logits(params, x, cfg, rules)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, new_caches

    def prefill_logits(params, batch, last_pos):
        """Prefill returning raw next-token logits gathered at per-row
        ``last_pos`` (absolute index incl. image tokens — supports
        right-padded/bucketed prompts), plus full-length un-rolled caches
        for paged admission. Returns (logits [B, Vp] f32, caches)."""
        tokens = batch["tokens"]
        B, S_tok = tokens.shape
        S_tot = S_tok + n_img
        positions = jnp.broadcast_to(
            jnp.arange(S_tot, dtype=jnp.int32), (B, S_tot))
        x, caches = backbone(params, tokens, positions, "prefill",
                             img_embeds=batch.get("img"),
                             frames=batch.get("frames"), full_kv=True)
        idx = jnp.broadcast_to(last_pos.astype(jnp.int32)[:, None, None],
                               (B, 1, x.shape[-1]))
        xl = jnp.take_along_axis(x, idx, axis=1)
        logits = head_logits(params, xl, cfg, rules)
        return logits[:, 0].astype(jnp.float32), caches

    def decode_step_paged(params, tokens, caches, page_table, seq_lens):
        """One continuous-batching decode step against the paged KV pool.

        tokens [B, 1]; page_table [B, P] int32 (physical page per logical
        block, 0 = null); seq_lens [B] int32 (tokens already cached per
        row — also the write position of this step's token). Rows with
        seq_len 0 and an all-null table are inactive padding slots.
        Returns (logits [B, Vp] f32, new_caches).
        """
        positions = seq_lens.astype(jnp.int32)[:, None]
        x, new_caches = backbone(params, tokens, positions, "decode",
                                 caches=caches,
                                 paged=(page_table, seq_lens))
        logits = head_logits(params, x, cfg, rules)
        return logits[:, 0].astype(jnp.float32), new_caches

    return BuiltModel(cfg, shape, lane, rules, init, loss_fn,
                      train_step, prefill_step, decode_step,
                      prefill_logits, decode_step_paged)


# ------------------------------------------------------------------ #
# input specs (ShapeDtypeStructs; no allocation)
# ------------------------------------------------------------------ #
def build_input_specs(cfg: ModelConfig, shape: ShapeConfig, lane: LaneConfig,
                      rules: ShardingRules) -> Dict[str, jax.ShapeDtypeStruct]:
    B, S = shape.global_batch, shape.seq_len
    n_img = cfg.num_image_tokens
    dtype = jnp.dtype(cfg.dtype)
    S_tok = S - n_img if shape.kind in ("train", "prefill") else S
    specs: Dict[str, jax.ShapeDtypeStruct] = {}
    if shape.kind == "train":
        specs["tokens"] = jax.ShapeDtypeStruct((B, S_tok), jnp.int32)
        specs["labels"] = jax.ShapeDtypeStruct((B, S_tok), jnp.int32)
        specs["mask"] = jax.ShapeDtypeStruct((B, S_tok), jnp.float32)
        specs["probe_mask"] = jax.ShapeDtypeStruct(
            (lane.zo_num_probes,), jnp.float32)
    elif shape.kind == "prefill":
        specs["tokens"] = jax.ShapeDtypeStruct((B, S_tok), jnp.int32)
    else:  # decode
        specs["tokens"] = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        specs["cache_len"] = jax.ShapeDtypeStruct((), jnp.int32)
    if cfg.encoder_layers and shape.kind in ("train", "prefill"):
        specs["frames"] = jax.ShapeDtypeStruct(
            (B, cfg.encoder_seq, cfg.d_model), dtype)
    if n_img and shape.kind in ("train", "prefill"):
        specs["img"] = jax.ShapeDtypeStruct((B, n_img, cfg.d_model), dtype)
    return specs


def batch_shardings(specs, rules: ShardingRules):
    """NamedShardings for the input-spec dict (None mesh -> None)."""
    if rules.mesh is None:
        return jax.tree.map(lambda _: None, specs)
    out = {}
    for k, v in specs.items():
        if k in ("probe_mask", "cache_len"):
            out[k] = NamedSharding(rules.mesh, P())
        elif v.ndim == 3:
            out[k] = NamedSharding(rules.mesh, P(rules.batch, None, None))
        else:
            out[k] = NamedSharding(rules.mesh, P(rules.batch, None))
        # batch dim must divide the data axes; replicate tiny batches
        bsize = 1
        for a in (rules.batch or ()):
            bsize *= rules.mesh.shape[a]
        if v.shape and v.shape[0] % max(bsize, 1) != 0:
            out[k] = NamedSharding(rules.mesh, P(*((None,) * v.ndim)))
    return out
