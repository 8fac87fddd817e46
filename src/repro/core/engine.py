"""The lane-polymorphic update engine (docs/design.md §10).

The canonical ElasticZO train step is ONE decomposition, stated here and
only here:

    partition -> probe(seeds, +/-eps) -> loss-diff -> coeff transform
              -> ZO update -> BP-tail update

with a numerics plugin per lane:

  * ``Fp32Engine`` (lanes full_zo / elastic_zo / full_bp, Alg. 1):
    g = clip(delta / 2eps); coeff = eta(t) * g * mask / valid; the ZO
    update accumulates the probe contributions **in probe order in
    fp32, subtracts once, and casts once per step**
    (accumulate-then-cast); the BP tail averages the perturbed-point
    gradients and applies one fp32-accumulate/cast SGD step.

  * ``Int8Engine`` (lane elastic_zo_int8, Alg. 2): g = sgn(L+ - L-) in
    {-1, 0, +1} (integer logits via core/int_loss.py, or the sign of
    the fp32 loss diff); the ZO update accumulates the per-probe
    pseudo-stochastically-rounded integer updates psr(g*z, shift) in
    int32 **in probe order and clamps once per step** to [-127, 127];
    the BP tail is the NITI FC backward, combined as a saturating int8
    sum.

Every phase exists in two dtype domains with identical semantics:

  * *traced* — inside the jitted train step built by ``make_step``
    (``core/elastic.py`` and ``core/elastic_int8.py`` are thin lane
    wrappers over this);
  * *ledger* — host-driven application of committed fleet records
    (``fleet/replay.py`` decodes wire bytes and calls ``host_coeffs`` /
    ``apply_zo_records`` / ``apply_tail_records``). Scalar
    hyperparameter math on this path runs in strict numpy float32 so
    every fleet participant derives identical coefficients; the bulk
    ZO apply dispatches to kernels/zo_fused_replay.py (TPU) or its
    eager oracle in kernels/ref.py, both of which pin the same
    accumulate-then-cast (fp32) / accumulate-then-clamp (int8) order.

Probes are keyed ``fold_in(fold_in(base_key, step), probe_id)`` with
*global* probe ids in both domains — the fleet's probe-parallel layout
is the single-process step with probe blocks assigned to workers.

Inside the traced step every lane names its device time with the same
four ``jax.named_scope`` phases of core/zo.py (``zo_perturb``,
``zo_forward``, ``bp_tail``, ``zo_update``). The scopes are HLO
metadata only; the step's numerics and buffers do not depend on them.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..configs.base import LaneConfig
from . import prng, zo

# ------------------------------------------------------------------ #
# shared scalar schedule — one formula, two dtype domains
# ------------------------------------------------------------------ #


def decay_traced(lane: LaneConfig, step: jax.Array) -> jax.Array:
    if lane.lr_decay_every <= 0 or lane.lr_decay_factor == 1.0:
        return jnp.float32(1.0)
    k = jnp.floor(step.astype(jnp.float32) / lane.lr_decay_every)
    return jnp.power(jnp.float32(lane.lr_decay_factor), k)


def decay_host(lane: LaneConfig, step: int) -> np.float32:
    """Strict-fp32 host twin of ``decay_traced`` (same rounding)."""
    if lane.lr_decay_every <= 0 or lane.lr_decay_factor == 1.0:
        return np.float32(1.0)
    k = np.float32(np.floor(np.float32(step) / np.float32(lane.lr_decay_every)))
    return np.power(np.float32(lane.lr_decay_factor), k)


def tail_learning_rate(lane: LaneConfig) -> float:
    # `is None` test: an explicit tail LR of 0.0 means "freeze the tail"
    return lane.learning_rate if lane.tail_learning_rate is None \
        else lane.tail_learning_rate


class UpdateEngine:
    """Base: lane binding + the partition phase. Subclasses are the
    numerics plugins; ``engine_for`` picks one from the lane config."""

    numerics: str = "?"

    def __init__(self, lane: LaneConfig,
                 partition_fn: Optional[Callable] = None):
        self.lane = lane
        if partition_fn is None:
            from . import elastic
            partition_fn = lambda p: elastic.partition(p, lane)  # noqa: E731
        self.partition = partition_fn


# ------------------------------------------------------------------ #
# fp32 lanes (Alg. 1)
# ------------------------------------------------------------------ #
class Fp32Engine(UpdateEngine):
    numerics = "fp32"

    def __init__(self, lane: LaneConfig,
                 partition_fn: Optional[Callable] = None,
                 paired_loss_fn: Optional[Callable] = None):
        super().__init__(lane, partition_fn)
        self.paired_loss_fn = paired_loss_fn

    # ---- coeff transform (ledger domain, strict fp32) ----------------- #
    def host_coeffs(self, step: int, deltas: np.ndarray,
                    mask: np.ndarray) -> Tuple[np.ndarray, np.float32]:
        """(coeffs fp32[n], valid): coeff_i = eta(t)*clip(d_i/2eps)*m_i/valid.

        The update applies ``theta <- cast(theta_f32 - sum_i coeff_i *
        z(seed_i))`` — the same descent direction as the traced step.
        """
        lane = self.lane
        deltas = np.asarray(deltas, np.float32)
        mask = np.asarray(mask, np.float32)
        g = deltas / np.float32(2.0 * lane.zo_eps)
        if lane.zo_clip is not None and lane.zo_clip > 0:
            g = np.clip(g, np.float32(-lane.zo_clip), np.float32(lane.zo_clip))
        g = g * mask
        valid = np.float32(max(float(mask.sum()), 1.0))
        eta = np.float32(lane.learning_rate) * decay_host(lane, step)
        return (eta * g) / valid, valid

    # ---- ZO update (traced domain) ------------------------------------ #
    @staticmethod
    @jax.named_scope(zo.UPDATE)
    def zo_apply(zo_part, terms: Sequence[Tuple[jax.Array, jax.Array]]):
        """theta <- cast(theta_f32 - sum_p coeff_p * z_p), probe order.

        terms: [(probe key, coeff scalar)] — coeff is the traced twin of
        ``host_coeffs`` (eta*g*mask/valid). The accumulate-then-cast
        order here is normative; kernels/zo_fused_replay.py and
        kernels/ref.zo_fused_replay_ref state the identical order for
        the ledger domain.
        """
        def f(path, leaf):
            acc = None
            for key, coeff in terms:
                t = coeff * zo.leaf_noise(key, path, leaf)
                acc = t if acc is None else acc + t
            if acc is None:
                return leaf
            return (leaf.astype(jnp.float32) - acc).astype(leaf.dtype)
        return jax.tree_util.tree_map_with_path(f, zo_part)

    # ---- ZO update (ledger domain) ------------------------------------ #
    @staticmethod
    def apply_zo_records(zo_part, seeds: np.ndarray, coeffs: np.ndarray):
        """Apply S committed steps x n probes to every ZO leaf in one
        fused pass (seeds u64/u32 [S, n], coeffs fp32 [S, n])."""
        from ..kernels import ops

        def f(path, leaf):
            return ops.zo_fused_replay(leaf, seeds.astype(np.uint32), coeffs,
                                       zo.path_salt(path))
        return jax.tree_util.tree_map_with_path(f, zo_part)

    # ---- BP-tail update (shared expression) --------------------------- #
    @staticmethod
    @jax.named_scope(zo.TAIL)
    def tail_apply(bp_part, grad_avg, eta):
        """p <- cast(p_f32 - eta * g_f32); eta traced or host fp32."""
        return jax.tree.map(
            lambda p, g: (p.astype(jnp.float32)
                          - eta * g.astype(jnp.float32)).astype(p.dtype),
            bp_part, grad_avg)

    def apply_tail_records(self, bp_part, step: int,
                           worker_grads: List[Any], valid: np.float32):
        """Ledger-domain tail: sum the accepted workers' dequantized
        grad trees (worker-id order), average by `valid`, apply."""
        if not jax.tree_util.tree_leaves(bp_part) or not worker_grads:
            return bp_part
        acc = None
        for part in worker_grads:
            acc = part if acc is None else jax.tree.map(jnp.add, acc, part)
        avg = jax.tree.map(lambda a: a / jnp.float32(valid), acc)
        eta = np.float32(tail_learning_rate(self.lane)) \
            * decay_host(self.lane, step)
        return self.tail_apply(bp_part, avg, jnp.float32(eta))

    # ---- the train step (traced domain) ------------------------------- #
    def make_step(self, loss_fn: Callable[[Any, Any], jax.Array]):
        """(state, batch, probe_mask fp32[n]) -> (state, metrics).

        metrics: ``loss``, ``zo_g`` (mean |g| over the probes) and
        ``zo_dl`` fp32[n], each probe's signed L+ - L- before clipping
        and masking (empty in ``full_bp``, which takes no probe); the
        in-scan path adds the paired loss's counts over the probes
        (``moe.held_rows``, ``moe.max_expert_rows`` for an MoE stack,
        ``merge_counts``)."""
        from .elastic import TrainState, merge
        lane = self.lane
        n = lane.zo_num_probes
        base_eta_tail = tail_learning_rate(lane)
        paired_loss_fn = self.paired_loss_fn

        def step(state: TrainState, batch, probe_mask: jax.Array):
            assert probe_mask.shape == (n,), \
                (f"probe_mask has shape {probe_mask.shape} but lane "
                 f"{lane.lane!r} runs {n} probes — derive LoopConfig."
                 "n_probes from the lane (LoopConfig.for_lane)")
            decay = decay_traced(lane, state.step)
            eta_zo = lane.learning_rate * decay
            eta_tail = base_eta_tail * decay
            params = state.params
            zo_part, bp_part = self.partition(params)
            base = jax.random.wrap_key_data(state.seed)
            key = jax.random.fold_in(base, state.step)

            if lane.lane == "full_bp":
                loss, grads = jax.value_and_grad(
                    lambda bp: loss_fn(bp, batch))(bp_part)
                new_params = self.tail_apply(bp_part, grads, eta_tail)
                metrics = {"loss": loss, "zo_g": jnp.float32(0),
                           "zo_dl": jnp.zeros((0,), jnp.float32)}
                return (TrainState(new_params, state.step + 1, state.seed),
                        metrics)

            def tail_loss(bp, zo_pert):
                return loss_fn(merge(zo_pert, bp), batch)

            has_tail = bool(bp_part) and lane.lane == "elastic_zo"
            zo_terms = []           # (probe key, coeff) in probe order
            tail_grad = None
            loss_acc = jnp.float32(0)
            g_acc = jnp.float32(0)
            dls = []
            valid = jnp.maximum(jnp.sum(probe_mask), 1.0)

            zo_src = zo_part
            counts = {}             # device-side counts of the paired loss
            for i in range(n):
                pk = jax.random.fold_in(key, i)
                if paired_loss_fn is not None and has_tail:
                    # both probes perturbed where the weights are
                    # consumed (core/api.py): z once per layer slice for
                    # both signs, no full-size perturbed copy; the grad of
                    # the mean IS the averaged tail grad.
                    def f(bp, _zo=zo_src, _pk=pk):
                        lp_, lm_, c_ = paired_loss_fn(bp, _zo, batch, _pk)
                        return 0.5 * (lp_ + lm_), (lp_, lm_, c_)
                    (_, (lp, lm, c)), g_tail_i = jax.value_and_grad(
                        f, has_aux=True)(bp_part)
                    counts = merge_counts(counts, c)
                else:
                    zp = zo.perturb(zo_src, pk, lane.zo_eps)
                    if has_tail:
                        lp, gp = jax.value_and_grad(tail_loss)(bp_part, zp)
                        # sequence the minus pass after the plus pass so
                        # their activation peaks don't overlap
                        zo_src, lp = jax.lax.optimization_barrier((zo_src, lp))
                        zm = zo.perturb(zo_src, pk, -lane.zo_eps)
                        lm, gm = jax.value_and_grad(tail_loss)(bp_part, zm)
                        if lane.bp_grad_mode == "clean":
                            _, g_tail_i = jax.value_and_grad(tail_loss)(
                                bp_part, zo_part)
                        else:
                            g_tail_i = jax.tree.map(
                                lambda a, b: (a + b) * 0.5, gp, gm)
                    else:
                        lp = loss_fn(merge(zp, bp_part), batch)
                        zo_src, lp = jax.lax.optimization_barrier((zo_src, lp))
                        zm = zo.perturb(zo_src, pk, -lane.zo_eps)
                        lm = loss_fn(merge(zm, bp_part), batch)
                if has_tail:
                    with jax.named_scope(zo.TAIL):
                        g_tail_i = jax.tree.map(
                            lambda x, m=probe_mask[i]:
                                m * x.astype(jnp.float32), g_tail_i)
                        tail_grad = g_tail_i if tail_grad is None else \
                            jax.tree.map(jnp.add, tail_grad, g_tail_i)
                dls.append(lp - lm)
                g = zo.projected_gradient(lp, lm, lane.zo_eps, lane.zo_clip)
                g = g * probe_mask[i]
                zo_terms.append((pk, eta_zo * g / valid))
                loss_acc = loss_acc + 0.5 * (lp + lm) * probe_mask[i]
                g_acc = g_acc + jnp.abs(g)

            new_zo = self.zo_apply(zo_part, zo_terms)
            if has_tail:
                with jax.named_scope(zo.TAIL):
                    tail_grad = jax.tree.map(lambda gt: gt / valid,
                                             tail_grad)
                new_bp = self.tail_apply(bp_part, tail_grad, eta_tail)
            else:
                new_bp = bp_part

            new_params = merge(new_zo, new_bp)
            metrics = {"loss": loss_acc / valid, "zo_g": g_acc / n,
                       "zo_dl": jnp.stack(dls), **counts}
            return TrainState(new_params, state.step + 1, state.seed), metrics

        return step


def merge_counts(acc: dict, new: dict) -> dict:
    """Probe counts merged: a ``max_`` count keeps the largest, the
    others add."""
    out = dict(acc)
    for k, v in new.items():
        if k not in out:
            out[k] = v
        elif k.rsplit(".", 1)[-1].startswith("max_"):
            out[k] = jnp.maximum(out[k], v)
        else:
            out[k] = out[k] + v
    return out


# ------------------------------------------------------------------ #
# int8 lane (Alg. 2)
# ------------------------------------------------------------------ #
class Int8Engine(UpdateEngine):
    numerics = "int8"

    def __init__(self, lane: LaneConfig,
                 partition_fn: Optional[Callable] = None,
                 tail_fcs: Optional[List[Tuple[str, str]]] = None,
                 loss_mode: Optional[str] = None,
                 p_zero: Optional[float] = None):
        super().__init__(lane, partition_fn)
        self.tail_fcs = tail_fcs or []
        self.loss_mode = lane.int8_loss_mode if loss_mode is None \
            else loss_mode
        self.r_max = lane.int8_r_max
        self.p_zero = lane.int8_p_zero if p_zero is None else p_zero
        # static twin of int8.bitwidth(r_max) - b_zo (Alg. 2 shift)
        self.zo_shift = max(int(self.r_max).bit_length() - lane.int8_b_zo, 0)

    # ---- coeff transform (ledger domain) ------------------------------ #
    def host_coeffs(self, step: int, gs: np.ndarray,
                    mask: np.ndarray) -> Tuple[np.ndarray, np.float32]:
        """(coeffs int32[n], valid). The int8 coeff IS the masked ternary
        sign — sgn coeffs are applied per probe, never renormalized
        (masked probes have g=0, an exact no-op of the integer update)."""
        gs = np.asarray(gs, np.int32)
        mask = np.asarray(mask, np.float32)
        valid = np.float32(max(float(mask.sum()), 1.0))
        return gs * mask.astype(np.int32), valid

    # ---- ZO update (traced domain) ------------------------------------ #
    @jax.named_scope(zo.UPDATE)
    def zo_apply(self, zo_part, terms: Sequence[Tuple[jax.Array, jax.Array]]):
        """theta <- clamp(theta - sum_p psr(g_p * z_p, shift), -127, 127).

        terms: [(probe uint32 seed, ternary g int32)] in probe order;
        int32 accumulation, ONE clamp per step — the integer twin of the
        fp32 accumulate-then-cast.
        """
        from .int8 import QTensor, int8_noise, psr_shift
        shift = jnp.int32(self.zo_shift)

        def f(path, leaf):
            if not isinstance(leaf, QTensor):
                return leaf
            salt = zo.path_salt(path)
            acc = None
            for seed, g in terms:
                z = int8_noise(seed, salt, leaf.data.shape, self.r_max,
                               jnp.float32(self.p_zero))
                t = psr_shift(g * z, shift)
                acc = t if acc is None else acc + t
            if acc is None:
                return leaf
            d = jnp.clip(leaf.data.astype(jnp.int32) - acc, -127, 127)
            return QTensor(d.astype(jnp.int8), leaf.exp)
        return jax.tree_util.tree_map_with_path(
            f, zo_part, is_leaf=lambda x: isinstance(x, QTensor))

    # ---- ZO update (ledger domain) ------------------------------------ #
    def apply_zo_records(self, zo_part, seeds: np.ndarray, gs: np.ndarray):
        """S committed steps x n probes on every int8 QTensor leaf
        (seeds u64/u32 [S, n], gs int32 [S, n]; masked probes g=0)."""
        from ..kernels import ops
        from .int8 import QTensor

        def f(path, leaf):
            if not isinstance(leaf, QTensor):
                return leaf
            data = ops.zo_fused_replay_int8(
                leaf.data, seeds.astype(np.uint32), gs.astype(np.int32),
                zo.path_salt(path), self.r_max, np.float32(self.p_zero),
                self.zo_shift)
            return QTensor(data, leaf.exp)
        return jax.tree_util.tree_map_with_path(
            f, zo_part, is_leaf=lambda x: isinstance(x, QTensor))

    # ---- probe phase (one statement; live step AND fleet probe_fn) ---- #
    def probe_pair(self, forward: Callable, zo_part, bp_part, batch,
                   seed: jax.Array):
        """One probe's Alg. 2 evaluation: functional +/- perturbation
        pair (the paper's in-place +1/-2/+1 replay minus its
        double-clamp asymmetry, docs/design.md §9), two integer
        forwards, ternary loss-diff. Returns (g int32, logits_p,
        acts_p). Shared verbatim by ``make_step`` and
        worker.make_int8_probe_fn so the two domains cannot drift.
        """
        from .int8 import perturb_int8
        from .int_loss import float_loss, int_loss_sign
        pzero = jnp.float32(self.p_zero)
        # the model's forward holds its tail layers too: zo_forward
        # covers the whole integer forward of both probes
        with jax.named_scope(zo.PERTURB):
            zo_p = perturb_int8(zo_part, seed, +1, self.r_max, pzero)
        with jax.named_scope(zo.FORWARD):
            logits_p, acts_p = forward({**zo_p, **bp_part}, batch["x"])
        with jax.named_scope(zo.PERTURB):
            zo_m = perturb_int8(zo_part, seed, -1, self.r_max, pzero)
        with jax.named_scope(zo.FORWARD):
            logits_m, _ = forward({**zo_m, **bp_part}, batch["x"])
        if self.loss_mode == "int":
            g = int_loss_sign(logits_p, logits_m, batch["y"])
        else:
            lf_p = float_loss(logits_p, batch["y"])
            lf_m = float_loss(logits_m, batch["y"])
            g = jnp.sign(lf_p - lf_m).astype(jnp.int32)
        return g, logits_p, acts_p

    # ---- BP tail ------------------------------------------------------- #
    @jax.named_scope(zo.TAIL)
    def tail_updates(self, bp_part, acts, logits, labels):
        """One probe's NITI backward: {layer: upd int32} (not applied).

        The propagated error chain uses the *pre-update* weights, so
        computing all updates first and applying once is exactly the
        sequential Alg. 2 application.
        """
        from .int8 import QTensor, fc_backward_int8, output_error_int8
        upds: Dict[str, jax.Array] = {}
        if not self.tail_fcs:
            return upds
        e = output_error_int8(logits, labels)
        for name, act_key in reversed(self.tail_fcs):
            w = bp_part[name]["w"]
            a_in: QTensor = acts[act_key]
            new_w, e = fc_backward_int8(w, a_in, e, self.lane.int8_b_bp)
            upds[name] = w.data.astype(jnp.int32) - new_w.data.astype(jnp.int32)
            # relu mask for the propagated error (pre-activation of the
            # previous layer is >0 exactly where its output is >0)
            e = e * (a_in.data.astype(jnp.int32) > 0)
        return upds

    @staticmethod
    @jax.named_scope(zo.TAIL)
    def combine_tail(upds_list: Sequence[Dict[str, jax.Array]]):
        """Saturating-int8 combine of per-probe updates (wire-exact: the
        ledger carries this as the record's int8 tail payload)."""
        acc: Dict[str, jax.Array] = {}
        for upds in upds_list:
            for name, u in upds.items():
                acc[name] = u if name not in acc else acc[name] + u
        return {n: jnp.clip(u, -127, 127).astype(jnp.int8)
                for n, u in acc.items()}

    @staticmethod
    @jax.named_scope(zo.TAIL)
    def tail_apply(bp_part, combined: Dict[str, Any]):
        """w <- clamp(w - sum(upd), -127, 127); exponents unchanged."""
        from .int8 import QTensor
        new_bp = dict(bp_part)
        for name, u in combined.items():
            w = bp_part[name]["w"]
            d = jnp.clip(w.data.astype(jnp.int32) - u.astype(jnp.int32),
                         -127, 127)
            new_bp[name] = {"w": QTensor(d.astype(jnp.int8), w.exp)}
        return new_bp

    def apply_tail_records(self, bp_part, step: int,
                           worker_upds: List[Any], valid=None):
        """Ledger-domain tail: int32 sum of the accepted workers' int8
        payload trees (exact, order-free), one saturating apply.

        worker_upds are bp-shaped ``{layer: {"w": upd}}`` trees (the
        record's payload unflattened against the schema treedef).
        """
        if not jax.tree_util.tree_leaves(bp_part) or not worker_upds:
            return bp_part
        acc = None
        for part in worker_upds:
            part = jax.tree.map(lambda u: u.astype(jnp.int32), part)
            acc = part if acc is None else jax.tree.map(jnp.add, acc, part)
        return self.tail_apply(bp_part, {n: sub["w"] for n, sub in
                                         acc.items()})

    # ---- the train step (traced domain) ------------------------------- #
    def make_step(self, forward: Callable):
        """forward(params, x) -> (logits QTensor, acts). Returned step:
        (state, batch, probe_mask fp32[n]) -> (state, metrics)."""
        from .elastic import TrainState
        from .int_loss import float_loss
        lane = self.lane
        n = lane.zo_num_probes

        def step(state: TrainState, batch, probe_mask):
            assert probe_mask.shape == (n,), \
                (f"probe_mask has shape {probe_mask.shape} but lane "
                 f"{lane.lane!r} runs {n} probes")
            params = state.params
            zo_part, bp_part = self.partition(params)
            base = jax.random.wrap_key_data(state.seed)
            key = jax.random.fold_in(base, state.step)

            zo_terms = []
            tail_upds = []
            loss_acc = jnp.float32(0)
            g_acc = jnp.float32(0)
            acc_acc = jnp.float32(0)
            valid = jnp.maximum(jnp.sum(probe_mask), 1.0)
            for i in range(n):
                seed = prng.seed_from_key(jax.random.fold_in(key, i))
                g, logits_p, acts_p = self.probe_pair(
                    forward, zo_part, bp_part, batch, seed)
                g = g * probe_mask[i].astype(jnp.int32)
                zo_terms.append((seed, g))
                upds = self.tail_updates(bp_part, acts_p, logits_p,
                                         batch["y"])
                mi = probe_mask[i].astype(jnp.int32)
                tail_upds.append({k: mi * u for k, u in upds.items()})
                loss_acc = loss_acc + float_loss(logits_p, batch["y"]) \
                    * probe_mask[i]
                g_acc = g_acc + g.astype(jnp.float32)
                acc_acc = acc_acc + probe_mask[i] * jnp.mean(
                    (jnp.argmax(logits_p.data, -1) == batch["y"])
                    .astype(jnp.float32))

            new_zo = self.zo_apply(zo_part, zo_terms)
            new_bp = self.tail_apply(bp_part, self.combine_tail(tail_upds)) \
                if self.tail_fcs else dict(bp_part)
            metrics = {
                "loss": loss_acc / valid,
                "g": g_acc / valid,
                "acc": acc_acc / valid,
            }
            return (TrainState({**new_zo, **new_bp}, state.step + 1,
                               state.seed), metrics)

        return step


def engine_for(lane: LaneConfig, partition_fn: Optional[Callable] = None,
               **kwargs) -> UpdateEngine:
    """The one lane -> numerics-plugin mapping."""
    if lane.lane == "elastic_zo_int8":
        return Int8Engine(lane, partition_fn, **kwargs)
    return Fp32Engine(lane, partition_fn, **kwargs)


# ------------------------------------------------------------------ #
# step memory analysis (diagnostic path, opt-in)
# ------------------------------------------------------------------ #
def step_memory_analysis(step_fn: Callable, state, batch,
                         probe_mask) -> Optional[Dict[str, int]]:
    """Measured XLA footprint of ONE train step, without executing it.

    ``jax.live_arrays()`` cannot see inside a jitted program, so this is the measured twin of
    the paper's analytic model (Eqs. 2-4 / 13-15): the step is lowered
    and compiled exactly as the production path runs it (same donation)
    and XLA's buffer assignment reports argument/output/temp/alias bytes
    (obs/memory.compiled_footprint). benchmarks/paper_tables.py puts
    these next to the Eq. values per lane; the difference is the
    reconciliation residual in BENCH_paper.json's ``memory`` section.
    """
    from ..obs.memory import compiled_footprint
    mask = jnp.asarray(np.asarray(probe_mask, np.float32))
    return compiled_footprint(step_fn, state, batch, mask,
                              donate_argnums=(0,))
