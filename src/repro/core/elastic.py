"""ElasticZO (Alg. 1): ZO for the first C layers, BP for the last L-C.

Parameter partition is structural: the LM parameter tree stores the layer
stack as two period-stacks, ``periods_zo`` (first P-K periods) and
``periods_bp`` (last K periods). Lanes assign top-level groups:

  elastic_zo : ZO = {embed, pos_embed, encoder, lead, periods_zo}
               BP = {periods_bp, final_norm, unembed}
  full_zo    : ZO = everything            (paper baseline, C = L)
  full_bp    : BP = everything            (paper baseline, C = 0)

The BP-tail gradient is taken at the *perturbed* points and averaged
(Alg. 1 keeps activations from the l+ and l- passes instead of running a
third forward; ``bp_grad_mode="clean"`` selects the third-pass variant).
Because only tail leaves are differentiated, XLA drops all head residuals
— the paper's memory claim, realized through DCE instead of manual buffer
management.

Multi-probe (n>1) antithetic SPSA with a runtime ``probe_mask`` implements
straggler mitigation: a dropped probe is masked out and the update is
renormalized by the surviving count — no recompile, no waiting
(docs/design.md §8).

This module is the fp32 *lane definition*: the partition and the
``TrainState``. The step itself — probe schedule, coeff transform, the
accumulate-then-cast ZO update, the tail SGD — is built by the
lane-polymorphic update engine (core/engine.py, docs/design.md §10),
which the fleet's ledger replay derives from as well.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional

import jax

from ..configs.base import LaneConfig
from .engine import Fp32Engine

ZO_GROUPS = ("embed", "pos_embed", "encoder", "lead", "periods_zo")
BP_GROUPS = ("periods_bp", "final_norm", "unembed")


class TrainState(NamedTuple):
    params: Any
    step: jax.Array            # i32 scalar
    seed: jax.Array            # uint32[2] base PRNG key data


def partition(params: Dict[str, Any], lane: LaneConfig):
    """Split the top-level param dict into (zo_part, bp_part)."""
    if lane.lane == "full_bp":
        return {}, dict(params)
    if lane.lane == "full_zo":
        return dict(params), {}
    zo_part = {k: v for k, v in params.items() if k in ZO_GROUPS}
    bp_part = {k: v for k, v in params.items() if k in BP_GROUPS}
    leftover = set(params) - set(zo_part) - set(bp_part)
    if leftover:
        raise ValueError(f"unpartitioned param groups: {sorted(leftover)}")
    return zo_part, bp_part


def merge(zo_part, bp_part):
    return {**zo_part, **bp_part}


def make_elastic_step(loss_fn: Callable[[Any, Any], jax.Array],
                      lane: LaneConfig,
                      partition_fn: Optional[Callable] = None,
                      paired_loss_fn: Optional[Callable] = None):
    """Build the ElasticZO train step (engine-built, fp32 numerics).

    loss_fn(params, batch) -> scalar fp32 (global mean under GSPMD).
    partition_fn(params) -> (zo_part, bp_part); defaults to the LM
    top-level-group partition. Returned step:
    (state, batch, probe_mask) -> (state, metrics).
    probe_mask: fp32[n_probes]; all-ones for a healthy fleet.
    """
    return Fp32Engine(lane, partition_fn,
                      paired_loss_fn=paired_loss_fn).make_step(loss_fn)
