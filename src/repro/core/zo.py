"""Zeroth-order (SPSA) machinery with the MeZO seed-replay trick.

The perturbation ``z ~ N(0, I)`` is never materialized as a stored buffer:
it is regenerated from a per-step key every time it is needed (perturb +,
perturb -, update), exactly like Alg. 1's ``PerturbParameters`` /
``ZOUpdateParameters`` replaying a seed. Under XLA the RNG + add fuses into
a single elementwise pass over the parameters, so the ZO part of a step is
a pure read-modify-write stream of theta (1R + 1W of HBM traffic) — see
kernels/zo_perturb.py for the explicit Pallas version of the same op.

The projected gradient ``g = (l+ - l-)/(2 eps)`` is a *scalar*; in the
data-parallel setting it is the only thing the ZO part of the model ever
all-reduces (docs/design.md §2).

The step's device time is split into four phases by ``jax.named_scope``
names that ride into the compiled HLO's ``op_name`` metadata (they change
nothing else), defined once below: ``PERTURB`` (here: ``perturb``,
``perturb_pair``, ``perturb_slice_pair``, ``perturb_rows_pair``),
``FORWARD`` and ``TAIL`` (core/api.py's training loss), ``UPDATE`` (here:
``zo_update``; core/engine.py's ``zo_apply``). ``bench/phases.py`` reads
each phase's time back from a device trace. Three more scopes nest inside
the phases and name the parts of a latent-attention MoE layer: ``MLA``
(models/layers.py: the latent projections and attention), ``MOE_ROUTE``
(models/moe.py: router, top-k, sort, gather, scatter and combine) and
``MOE_EXPERTS`` (the held experts' grouped matmuls).

Two ways to perturb. ``perturb`` builds a whole perturbed copy of a tree:
the materialised path of the lanes whose ZO part has no layer scan
(LeNet, ``full_zo``), the ``clean`` tail mode and the fleet worker's
probe. The LM elastic step perturbs where the weights are consumed:
``perturb_slice_pair`` inside the layer scan and ``perturb_rows_pair`` on
the gathered embedding rows, each generating z once for both probe signs.
Every element of a full-size copy is counted at trace time under the
``zo.perturb.materialized_elements`` counter (docs/observability.md).
"""
from __future__ import annotations

import zlib
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from .. import obs
from . import prng

# the step's phase scopes (jax.named_scope names)
PERTURB = "zo_perturb"
FORWARD = "zo_forward"
TAIL = "bp_tail"
UPDATE = "zo_update"
# scopes inside the phases (a latent-attention MoE layer's parts)
MLA = "mla"
MOE_ROUTE = "moe_route"
MOE_EXPERTS = "moe_experts"


def path_salt(path, prefix: str = "") -> int:
    return zlib.crc32((prefix + jax.tree_util.keystr(path)).encode()) \
        & 0x3FFFFFFF


def leaf_noise(key, path, leaf) -> jax.Array:
    """The z for one parameter leaf (fp32, cast at the use site).

    Counter-based hash noise (core/prng.py): shardable elementwise ops, so
    GSPMD never materializes a replicated full-size z, and the value is
    independent of the mesh (elastic-restart safe).
    """
    return prng.normal(prng.seed_from_key(key), path_salt(path), leaf.shape)


def _pair(leaf32, z, eps, dtype):
    """(θ+εz, θ−εz), each rounded to `dtype` exactly as ``perturb`` rounds
    it with scale ±eps."""
    return ((leaf32 + eps * z).astype(dtype),
            (leaf32 + (-eps) * z).astype(dtype))


def _unzip(tree, pairs):
    return (jax.tree.map(lambda _, pr: pr[0], tree, pairs),
            jax.tree.map(lambda _, pr: pr[1], tree, pairs))


def _count_materialized(tree, copies: int) -> None:
    n = sum(int(leaf.size) for leaf in jax.tree.leaves(tree))
    obs.get().counter("zo.perturb.materialized_elements").inc(copies * n)


def slice_noise_spec(stacked, prefix: str):
    """(salts, sizes) of a layer stack whose leaves lead with the layer
    dim, for ``perturb_slice_pair``: each leaf's salt is that of its
    path under `prefix` in the whole tree (the one ``perturb`` and the
    update give it), each size the leaf's elements per layer."""
    n = jax.tree.leaves(stacked)[0].shape[0]
    salts = jax.tree_util.tree_map_with_path(
        lambda p, _: path_salt(p, prefix), stacked)
    return salts, jax.tree.map(lambda a: a.size // n, stacked)


@jax.named_scope(PERTURB)
def perturb_slice_pair(pparams, salts, sizes, p_idx, seed, eps: float):
    """Both probes' perturbed copies of one scanned layer-slice, from one
    generation of its noise: z_slice = z_stacked[p_idx] via the flat-index
    offset, so each copy equals the matching slice of ``perturb(stacked,
    key, ±eps)`` bitwise.

    pparams: this period's param slice; salts/sizes: static pytrees (crc32
    of the *stacked* leaf path, per-period flat size); p_idx: traced scan
    index; seed: uint32 scalar (prng.seed_from_key of the probe key).
    Returns (plus, minus), each shaped like pparams.
    """
    def f(leaf, salt, size):
        off = p_idx.astype(jnp.uint32) * jnp.uint32(size)
        z = prng.normal(seed, salt, leaf.shape, offset=off)
        return _pair(leaf.astype(jnp.float32), z, eps, leaf.dtype)
    return _unzip(pparams, jax.tree.map(f, pparams, salts, sizes))


@jax.named_scope(PERTURB)
def perturb_rows_pair(params, name: str, rows, key, eps: float):
    """Both probes' perturbed copies of the gathered rows
    ``params[name][rows]`` of a 2-D top-level leaf (an embedding), with
    the noise of flat index ``row * width + j``: bitwise
    ``perturb(params, key, ±eps)[name][rows]``, with no full-size copy and
    no z for the rows not gathered."""
    table = params[name]
    width = table.shape[-1]
    flat = rows.astype(jnp.uint32)[..., None] * jnp.uint32(width) \
        + jax.lax.iota(jnp.uint32, width)
    z = prng.normal_at(prng.seed_from_key(key),
                       path_salt((jax.tree_util.DictKey(name),)), flat)
    x = jnp.take(table, rows, axis=0).astype(jnp.float32)
    return _pair(x, z, eps, table.dtype)


@jax.named_scope(PERTURB)
def perturb_pair(params, key, eps: float):
    """Both probes' whole perturbed copies of a tree from one generation
    of each leaf's noise: (``perturb(params, key, eps)``, ``perturb(params,
    key, -eps)``) bitwise."""
    _count_materialized(params, 2)

    def f(path, leaf):
        return _pair(leaf.astype(jnp.float32), leaf_noise(key, path, leaf),
                     eps, leaf.dtype)
    return _unzip(params, jax.tree_util.tree_map_with_path(f, params))


@jax.named_scope(PERTURB)
def perturb(params, key, scale: float | jax.Array):
    """theta + scale * z, z regenerated from `key` (leafwise)."""
    _count_materialized(params, 1)

    def f(path, leaf):
        z = leaf_noise(key, path, leaf)
        return (leaf.astype(jnp.float32) + scale * z).astype(leaf.dtype)
    return jax.tree_util.tree_map_with_path(f, params)


@jax.named_scope(UPDATE)
def zo_update(params, key, step_size):
    """theta - step_size * z  (z replayed from `key`). step_size may be a
    traced scalar (eta * g)."""
    def f(path, leaf):
        z = leaf_noise(key, path, leaf)
        return (leaf.astype(jnp.float32) - step_size * z).astype(leaf.dtype)
    return jax.tree_util.tree_map_with_path(f, params)


def projected_gradient(l_plus, l_minus, eps, clip: Optional[float] = None):
    g = (l_plus - l_minus) / (2.0 * eps)
    if clip is not None and clip > 0:
        g = jnp.clip(g, -clip, clip)
    return g


def spsa_gradient_estimate(loss_fn: Callable[[Any], jax.Array], params, key,
                           eps: float, clip: Optional[float] = None):
    """Reference two-point SPSA estimator (used by tests / Full-ZO lane).

    Returns (g, l_plus, l_minus); the caller applies ``zo_update`` with the
    same key.
    """
    l_plus = loss_fn(perturb(params, key, eps))
    l_minus = loss_fn(perturb(params, key, -eps))
    g = projected_gradient(l_plus, l_minus, eps, clip)
    return g, l_plus, l_minus
