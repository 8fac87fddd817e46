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
``perturb_slice``), ``FORWARD`` and ``TAIL`` (core/api.py's training
loss), ``UPDATE`` (here: ``zo_update``; core/engine.py's ``zo_apply``).
``bench/phases.py`` reads each phase's time back from a device trace.
"""
from __future__ import annotations

import zlib
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from . import prng

# the step's phase scopes (jax.named_scope names)
PERTURB = "zo_perturb"
FORWARD = "zo_forward"
TAIL = "bp_tail"
UPDATE = "zo_update"


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


@jax.named_scope(PERTURB)
def perturb_slice(pparams, salts, sizes, p_idx, seed, scale):
    """Perturb one scanned layer-slice so it matches the stacked leaf's
    noise exactly: z_slice = z_stacked[p_idx] via the flat-index offset.

    pparams: this period's param slice; salts/sizes: static pytrees (crc32
    of the *stacked* leaf path, per-period flat size); p_idx: traced scan
    index; seed: uint32 scalar (prng.seed_from_key of the probe key).
    """
    def f(leaf, salt, size):
        off = p_idx.astype(jnp.uint32) * jnp.uint32(size)
        z = prng.normal(seed, salt, leaf.shape, offset=off)
        return (leaf.astype(jnp.float32) + scale * z).astype(leaf.dtype)
    return jax.tree.map(f, pparams, salts, sizes)


@jax.named_scope(PERTURB)
def perturb(params, key, scale: float | jax.Array):
    """theta + scale * z, z regenerated from `key` (leafwise)."""
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
