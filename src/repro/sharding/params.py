"""Map the LM parameter tree to NamedShardings via path-based rules.

Weights are TP-sharded over `model` on the dimension the rules pick and
FSDP-sharded over `data` on a complementary dimension; stacked period
leaves get an extra unsharded leading (layer) axis. See docs/design.md §5.
"""
from __future__ import annotations


import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..sharding.rules import ShardingRules


def _spec_for(path_names, leaf_name, rules: ShardingRules):
    r = rules
    n = leaf_name
    if n in ("embed",):
        return r.spec_embed()
    if n == "unembed":
        return r.spec_unembed()
    if n == "pos_embed":
        return (None, r.fsdp)
    if n in ("final_norm",):
        return (None,)
    # attention
    if n in ("wq", "wk", "wv"):
        return r.spec_attn_qkv()
    if n == "wo" and "attn" in path_names or n == "wo" and "cross" in path_names:
        return r.spec_attn_o()
    if n in ("q_norm", "k_norm", "kv_norm"):
        return (None,)
    if n == "wkv_a":                      # latent attention [D, r + rope]
        return (r.fsdp, None)
    if n == "wkv_b":                      # [r, H, nope + v]
        return r.spec_attn_qkv()
    # dense mlp (and an MoE layer's shared experts)
    dense = "moe" not in path_names or "shared" in path_names
    if n in ("w_gate", "w_up") and dense:
        return r.spec_mlp_in()
    if n == "w_down" and dense:
        return r.spec_mlp_out()
    # moe
    if n == "router":
        return r.spec_router()
    if n in ("w_gate", "w_up"):
        return r.spec_moe_in()
    if n == "w_down":
        return r.spec_moe_out()
    # rwkv
    if n in ("w_r", "w_k", "w_v", "w_g"):
        return (r.fsdp, r.wmodel)
    if n == "w_o":
        return (r.wmodel, r.fsdp)
    if n in ("maa_w1", "decay_w1"):
        return (r.fsdp, None)
    if n == "maa_w2":
        return (None, None, r.fsdp)
    if n == "decay_w2":
        return (None, r.wmodel)
    if n == "maa_base":
        return (None, None)
    if n in ("maa_x", "decay_base", "cm_mu_k", "cm_mu_r",
             "ln1", "ln2", "ln_attn", "ln_ffn", "ln_cross",
             "conv_b_dummy"):
        return (None,)
    if n in ("bonus", "gn_scale"):
        return (r.wmodel, None)
    if n == "cm_k":
        return (r.fsdp, r.wmodel)
    if n == "cm_v":
        return (r.wmodel, r.fsdp)
    if n == "cm_r":
        return (r.fsdp, None)
    # mamba
    if n == "in_proj":
        return (r.fsdp, r.wmodel)
    if n == "conv_w":
        return (None, r.wmodel)
    if n in ("conv_b", "dt_bias", "D_skip"):
        return (r.wmodel,)
    if n == "x_proj":
        return (r.wmodel, None)
    if n == "dt_proj":
        return (None, r.wmodel)
    if n == "A_log":
        return (r.wmodel, None)
    if n == "out_proj":
        return (r.wmodel, r.fsdp)
    if n in ("dt_norm", "B_norm", "C_norm", "norm"):
        return (None,)
    return None     # fall back to replicated-with-rank


def _path_names(path):
    out = []
    for e in path:
        if hasattr(e, "key"):
            out.append(str(e.key))
        elif hasattr(e, "name"):
            out.append(str(e.name))
    return out


def param_shardings(abstract_params, rules: ShardingRules):
    """Pytree of NamedShardings matching `abstract_params`."""
    if rules.mesh is None:
        return jax.tree.map(lambda _: None, abstract_params)

    def f(path, leaf):
        names = _path_names(path)
        spec = _spec_for(names, names[-1], rules)
        if spec is None:
            spec = (None,) * leaf.ndim
        stacked = any(p in ("periods_zo", "periods_bp", "periods", "lead")
                      for p in names)
        if stacked:
            spec = (None,) + tuple(spec)
        if len(spec) != leaf.ndim:
            spec = tuple(spec) + (None,) * (leaf.ndim - len(spec))
            spec = spec[:leaf.ndim]
        # drop shardings that do not divide the dim evenly
        fixed = []
        for dim, ax in zip(leaf.shape, spec):
            if ax is None:
                fixed.append(None)
                continue
            size = 1
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                size *= rules.mesh.shape[a]
            fixed.append(ax if dim % size == 0 else None)
        return NamedSharding(rules.mesh, P(*fixed))

    return jax.tree_util.tree_map_with_path(f, abstract_params)


def cache_shardings(abstract_caches, rules: ShardingRules):
    """Shardings for the (zo, bp) cache pytree by leaf rank/kind."""
    if rules.mesh is None:
        return jax.tree.map(lambda _: None, abstract_caches)

    def f(path, leaf):
        names = _path_names(path)
        n = names[-1] if names else ""
        if n in ("k", "v", "ck", "cv"):
            spec = rules.spec_kv_cache()
        elif n == "ssm":
            spec = rules.spec_ssm_cache()
        elif n == "wkv":
            spec = rules.spec_rwkv_cache()
        elif n == "conv":
            spec = rules.spec_conv_cache()
        elif n in ("tm_shift", "cm_shift"):
            spec = (None, rules.batch, None, None)
        else:
            spec = (None,) * leaf.ndim
        spec = tuple(spec)[:leaf.ndim] + (None,) * max(0, leaf.ndim - len(spec))
        fixed = []
        for dim, ax in zip(leaf.shape, spec):
            if ax is None:
                fixed.append(None)
                continue
            size = 1
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                size *= rules.mesh.shape[a]
            fixed.append(ax if dim % size == 0 else None)
        return NamedSharding(rules.mesh, P(*fixed))

    return jax.tree_util.tree_map_with_path(f, abstract_caches)
