"""Mixture-of-Experts FFN: dropless dispatch over this chip's share of the
experts, optional shared experts.

The layer is told which experts it holds (``cfg.experts_first`` and
``cfg.experts_held`` of the router's ``cfg.num_experts``; all of them by
default). It routes every token over all experts and computes only its
own experts' part of the result, as one chip of an expert-parallel
deployment does without the exchange:

  1. router logits in float32, softmax, greedy top-k over all experts;
     the k gates renormalised (``norm_topk_prob``) or scaled by
     ``routed_scaling``
  2. the token-slots routed to a held expert are sorted by expert (a
     stable sort; the other slots sort after them) and their token rows
     gathered
  3. each held expert's SwiGLU as grouped matmuls over its run of rows
     (``jax.lax.ragged_dot``): every routed slot is computed, none is
     dropped, whatever the skew
  4. combine: each row's output times its gate, added back to its token
  5. the shared experts (one SwiGLU of width shared x expert width) on
     every token, added

The routing and the combine run under the ``moe_route`` scope, the
grouped matmuls under ``moe_experts`` (core/zo.py). ``moe_ffn`` also
returns the rows routed to held experts and the most on one expert, the
step's ``moe.held_rows`` and ``moe.max_expert_rows``.

Sharding (rules.moe): under the "tp" plan the expert width is sharded
over ``model``; otherwise GSPMD places the grouped matmuls.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..core.zo import MOE_EXPERTS, MOE_ROUTE
from .layers import dense_init, init_mlp, mlp, subkey


def init_moe(key, cfg: ModelConfig, dtype):
    d, ff, E = cfg.d_model, cfg.expert_d_ff, cfg.held_experts
    p = {
        "router": dense_init(subkey(key, "router"), (d, cfg.num_experts),
                             jnp.float32),
        "w_gate": dense_init(subkey(key, "wg"), (E, d, ff), dtype),
        "w_up": dense_init(subkey(key, "wu"), (E, d, ff), dtype),
        "w_down": dense_init(subkey(key, "wd"), (E, ff, d), dtype, fan_in=ff),
    }
    if cfg.num_shared_experts:
        p["shared"] = init_mlp(subkey(key, "shared"), d,
                               cfg.num_shared_experts * ff, dtype)
    return p


def route(router, x, cfg: ModelConfig):
    """Top-k (gates, expert ids) of every token of x [T, D], over all of
    the router's experts."""
    # float32 at full precision, as the published gate computes it: the
    # TPU's default would round the operands to bf16 and flip top-k picks
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router,
                        precision=jax.lax.Precision.HIGHEST)
    gates = jax.nn.softmax(logits, axis=-1)
    top_g, top_i = jax.lax.top_k(gates, cfg.experts_per_token)
    if cfg.norm_topk_prob:
        top_g = top_g / jnp.sum(top_g, axis=-1, keepdims=True)
    elif cfg.routed_scaling != 1.0:
        top_g = top_g * cfg.routed_scaling
    return top_g, top_i


def moe_ffn(p, x, cfg: ModelConfig, rules):
    """x: [B, S, D] -> ([B, S, D], (held rows, most rows on one held
    expert), both int32)."""
    B, S, D = x.shape
    E, K = cfg.held_experts, cfg.experts_per_token
    n = B * S * K
    xt = x.reshape(B * S, D)
    with jax.named_scope(MOE_ROUTE):
        top_g, top_i = route(p["router"], xt, cfg)
        local = top_i.reshape(n) - cfg.experts_first
        slot_e = jnp.where((local >= 0) & (local < E), local, E)
        order = jnp.argsort(slot_e, stable=True)
        sizes = jnp.bincount(slot_e, length=E + 1)[:E].astype(jnp.int32)
        held = jnp.sum(sizes)
        valid = (jnp.arange(n) < held)[:, None]
        rows = order // K
        # rows past the held ones are zero in and masked out: the grouped
        # matmuls leave them unwritten
        xs = jnp.where(valid, jnp.take(xt, rows, axis=0), 0)

    with jax.named_scope(MOE_EXPERTS):
        h = jax.nn.silu(jax.lax.ragged_dot(xs, p["w_gate"], sizes)) \
            * jax.lax.ragged_dot(xs, p["w_up"], sizes)
        if rules.moe == "tp" and rules.model is not None:
            h = rules.wsc(h, None, rules.model)
        out = jax.lax.ragged_dot(h, p["w_down"], sizes)

    with jax.named_scope(MOE_ROUTE):
        g = jnp.take(top_g.reshape(n), order)[:, None]
        out = jnp.where(valid, out.astype(jnp.float32) * g, 0)
        y = jnp.zeros((B * S, D), jnp.float32).at[rows].add(out)
        y = y.astype(x.dtype).reshape(B, S, D)
    if "shared" in p:
        y = y + mlp(p["shared"], x, rules)
    return rules.act_btd(y), (held, jnp.max(sizes))
