"""Paper-faithful experiment harnesses (Tables 1-2, Figs. 2-7 analogs).

Datasets are the deterministic synthetic stand-ins (docs/design.md §9); the
claims being reproduced are the *orderings and gaps between lanes*
(Full BP > ZO-Feat-Cls1 > ZO-Feat-Cls2 > Full ZO), the memory accounting
(Eqs. 2-4, 13-15 evaluated exactly), the INT8 speed/memory ratios, and the
~95% integer sign agreement.
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import LaneConfig
from repro.configs.paper_models import LeNet5Config, PointNetConfig
from repro.core.elastic import TrainState, make_elastic_step
from repro.core.elastic_int8 import make_int8_elastic_step, int8_eval
from repro.core.int8 import QTensor, quant_from_float
from repro.core.int_loss import float_loss, int_loss_sign
from repro.data.synthetic import glyphs, point_clouds
from repro.models import lenet, pointnet


# ------------------------------------------------------------------ #
# Table 1 analog: accuracy by lane
# ------------------------------------------------------------------ #
def _eval_lenet(params, xs, ys):
    logits, _ = lenet.lenet5_forward(params, xs)
    return float(jnp.mean((jnp.argmax(logits, -1) == ys).astype(jnp.float32)))


def lenet_lane_configs(steps=600, lr=0.05, zo_lr=5e-3, eps=1e-2, probes=4
                       ) -> List[Tuple[str, LaneConfig, int]]:
    """The four paper lanes as (name, LaneConfig, partition point C) —
    shared by the accuracy harness and the measured-memory harness so
    the two can never drift apart."""
    dk = dict(lr_decay_factor=0.8, lr_decay_every=max(steps // 10, 1))
    return [
        ("full_zo", LaneConfig(lane="full_zo", learning_rate=zo_lr,
                               zo_eps=eps, zo_num_probes=probes, **dk), 5),
        ("zo_feat_cls2", LaneConfig(lane="elastic_zo", learning_rate=zo_lr,
                                    tail_learning_rate=lr, zo_eps=eps,
                                    zo_num_probes=probes, **dk), 3),
        ("zo_feat_cls1", LaneConfig(lane="elastic_zo", learning_rate=zo_lr,
                                    tail_learning_rate=lr, zo_eps=eps,
                                    zo_num_probes=probes, **dk), 4),
        ("full_bp", LaneConfig(lane="full_bp", learning_rate=lr, **dk), 0),
    ]


# INT8/INT8* lanes (Alg. 2): (name, partition point C, tail FCs)
INT8_LANES = [
    ("full_zo", 5, []),
    ("zo_feat_cls2", 3, [("fc2", "fc2_in"), ("fc3", "fc3_in")]),
    ("zo_feat_cls1", 4, [("fc3", "fc3_in")]),
]


def int8_lane_config() -> LaneConfig:
    return LaneConfig(int8_r_max=3, int8_p_zero=0.33, int8_b_zo=1,
                      int8_b_bp=5)


def lenet_lanes(steps=600, batch=32, train_n=2048, test_n=512, seed=0,
                lr=0.05, zo_lr=5e-3, eps=1e-2, rotate=0.0, init_params=None,
                probes=4):
    """Returns {lane: (test_acc, loss_curve)} for the four paper lanes."""
    xs_tr, ys_tr = glyphs(train_n, seed=seed, rotate_deg=rotate)
    xs_te, ys_te = glyphs(test_n, seed=seed + 1, start=10_000,
                          rotate_deg=rotate)
    xs_te, ys_te = jnp.asarray(xs_te), jnp.asarray(ys_te)
    results = {}
    cfgs = lenet_lane_configs(steps=steps, lr=lr, zo_lr=zo_lr, eps=eps,
                              probes=probes)
    for name, lane, c in cfgs:
        params = init_params or lenet.init_lenet5(jax.random.key(7))
        part = (lambda p, c=c: lenet.partition_at(p, c)) \
            if lane.lane == "elastic_zo" else None
        step = jax.jit(make_elastic_step(lenet.lenet5_loss, lane,
                                         partition_fn=part))
        state = TrainState(params, jnp.int32(0),
                           jax.random.key_data(jax.random.key(11)))
        pm = jnp.ones((lane.zo_num_probes,), jnp.float32)
        curve = []
        for s in range(steps):
            i0 = (s * batch) % train_n
            bx = jnp.asarray(xs_tr[i0:i0 + batch])
            by = jnp.asarray(ys_tr[i0:i0 + batch])
            state, m = step(state, {"x": bx, "y": by}, pm)
            if s % max(steps // 20, 1) == 0:
                curve.append(float(m["loss"]))
        acc = _eval_lenet(state.params, xs_te, ys_te)
        results[name] = (acc, curve)
    return results


def lenet_int8_lanes(steps=600, batch=64, train_n=2048, test_n=512, seed=0,
                     loss_mode="int"):
    """INT8/INT8* lanes (Alg. 2)."""
    xs_tr, ys_tr = glyphs(train_n, seed=seed)
    xs_te, ys_te = glyphs(test_n, seed=seed + 1, start=10_000)
    qx_te = quant_from_float(jnp.asarray(xs_te))
    results = {}
    for name, c, tail in INT8_LANES:
        lane = int8_lane_config()
        step = jax.jit(make_int8_elastic_step(
            lenet.lenet5_forward_int8,
            partition_fn=lambda p, c=c: lenet.partition_at(p, c),
            tail_fcs=tail, lane=lane, loss_mode=loss_mode))
        params = lenet.init_lenet5_int8(jax.random.key(7))
        state = TrainState(params, jnp.int32(0),
                           jax.random.key_data(jax.random.key(13)))
        for s in range(steps):
            i0 = (s * batch) % train_n
            bx = quant_from_float(jnp.asarray(xs_tr[i0:i0 + batch]))
            by = jnp.asarray(ys_tr[i0:i0 + batch])
            state, m = step(state, {"x": bx, "y": by},
                            jnp.ones((1,), jnp.float32))
        acc = float(int8_eval(lenet.lenet5_forward_int8, state.params,
                              qx_te, ys_te))
        results[name] = (acc, [])
    return results


def pointnet_lanes(steps=400, batch=32, train_n=1024, test_n=256,
                   num_points=256, classes=8):
    cfg = PointNetConfig(num_classes=classes, num_points=num_points)
    xs_tr, ys_tr = point_clouds(train_n, num_points, seed=3,
                                num_classes=classes)
    xs_te, ys_te = point_clouds(test_n, num_points, seed=4, start=50_000,
                                num_classes=classes)
    xs_te, ys_te = jnp.asarray(xs_te), jnp.asarray(ys_te)
    results = {}
    dk = dict(lr_decay_factor=0.8, lr_decay_every=max(steps // 10, 1))
    for name, lanecfg, c in [
        ("full_zo", LaneConfig(lane="full_zo", learning_rate=5e-3,
                               zo_eps=1e-2, zo_num_probes=4, **dk), 8),
        ("zo_feat_cls2", LaneConfig(lane="elastic_zo", learning_rate=5e-3,
                                    tail_learning_rate=0.05, zo_eps=1e-2,
                                    zo_num_probes=4, **dk), 6),
        ("zo_feat_cls1", LaneConfig(lane="elastic_zo", learning_rate=5e-3,
                                    tail_learning_rate=0.05, zo_eps=1e-2,
                                    zo_num_probes=4, **dk), 7),
        ("full_bp", LaneConfig(lane="full_bp", learning_rate=0.05, **dk), 0),
    ]:
        params = pointnet.init_pointnet(jax.random.key(5), cfg)
        part = (lambda p, c=c: pointnet.partition_at(p, c)) \
            if lanecfg.lane == "elastic_zo" else None
        step = jax.jit(make_elastic_step(pointnet.pointnet_loss, lanecfg,
                                         partition_fn=part))
        state = TrainState(params, jnp.int32(0),
                           jax.random.key_data(jax.random.key(17)))
        pm = jnp.ones((lanecfg.zo_num_probes,), jnp.float32)
        for s in range(steps):
            i0 = (s * batch) % train_n
            state, m = step(state, {"x": jnp.asarray(xs_tr[i0:i0 + batch]),
                                    "y": jnp.asarray(ys_tr[i0:i0 + batch])}, pm)
        logits, _ = pointnet.pointnet_forward(state.params, xs_te)
        acc = float(jnp.mean((jnp.argmax(logits, -1) == ys_te)
                             .astype(jnp.float32)))
        results[name] = (acc, [])
    return results


# ------------------------------------------------------------------ #
# Figs. 4-6 analog: memory accounting, Eqs. 2-4 / 13-15 evaluated exactly
# ------------------------------------------------------------------ #
def lenet_memory_table(batch: int) -> Dict[str, Dict[str, float]]:
    """Exact evaluation of the paper's memory model for LeNet-5."""
    cfg = LeNet5Config()
    c1, c2 = cfg.conv_channels
    # activation sizes per layer (fp32 elements, batch included)
    acts = {
        "conv1": batch * 28 * 28 * c1, "pool1": batch * 14 * 14 * c1,
        "conv2": batch * 14 * 14 * c2, "pool2": batch * 7 * 7 * c2,
        "fc1": batch * 120, "fc2": batch * 84, "fc3": batch * 10,
    }
    thetas = {
        "conv1": 5 * 5 * 1 * c1 + c1, "conv2": 5 * 5 * c1 * c2 + c2,
        "fc1": 784 * 120 + 120, "fc2": 120 * 84 + 84, "fc3": 84 * 10 + 10,
    }
    trainable = list(thetas)
    A = sum(acts.values())
    TH = sum(thetas.values())

    def mem_fp32(c):                       # Eq. 2-4, bytes (fp32 = 4B)
        tail = trainable[c:]
        g = sum(thetas[l] for l in tail)   # gradients of tail params
        e = sum(acts[l] for l in tail)     # errors of tail layers
        return 4 * (TH + A + g + e)

    def mem_int8(c, reuse_scratch: bool):
        """Eq. 13-15. ``reuse_scratch=False`` is the paper's no-lifetime
        accounting (every int32 accumulator held simultaneously);
        ``True`` models the real implementation where the int32 scratch is
        rounded to int8 immediately and one buffer is reused across layers
        (this is what reproduces the paper's measured 1.46-1.60x)."""
        tail = trainable[c:]
        g8 = sum(thetas[l] for l in tail)
        e8 = sum(acts[l] for l in tail)
        if reuse_scratch:
            a32 = max(acts[l] for l in trainable)
            g32 = max((thetas[l] for l in tail), default=0)
            e32 = max((acts[l] for l in tail), default=0)
        else:
            a32 = sum(acts[l] for l in trainable)
            g32 = sum(thetas[l] for l in tail)
            e32 = sum(acts[l] for l in tail)
        return (TH + A + g8 + e8) + 4 * (a32 + g32 + e32)

    rows = {}
    for name, c in [("full_bp", 0), ("zo_feat_cls1", 4), ("zo_feat_cls2", 3),
                    ("full_zo", 5)]:
        rows[name] = {"fp32_bytes": mem_fp32(c),
                      "int8_bytes": mem_int8(c, False),
                      "int8_reused_bytes": mem_int8(c, True)}
    return rows


def pointnet_memory_table(batch: int, num_points=1024):
    cfg = PointNetConfig()
    dims = (3,) + cfg.feat_dims
    acts = {f"feat{i}": batch * num_points * dims[i + 1] for i in range(5)}
    acts["pool"] = batch * 1024
    hd = (1024,) + cfg.head_dims + (cfg.num_classes,)
    for i, n in enumerate(("head0", "head1", "cls")):
        acts[n] = batch * hd[i + 1]
    thetas = {f"feat{i}": dims[i] * dims[i + 1] + dims[i + 1] for i in range(5)}
    for i, n in enumerate(("head0", "head1", "cls")):
        thetas[n] = hd[i] * hd[i + 1] + hd[i + 1]
    trainable = list(thetas)
    A, TH = sum(acts.values()), sum(thetas.values())

    def mem(c):
        tail = trainable[c:]
        g = sum(thetas[l] for l in tail)
        e = sum(acts[l] for l in tail)
        return 4 * (TH + A + g + e)

    return {"full_bp": {"fp32_bytes": mem(0)},
            "zo_feat_cls1": {"fp32_bytes": mem(7)},
            "zo_feat_cls2": {"fp32_bytes": mem(6)},
            "full_zo": {"fp32_bytes": mem(8)},
            "theta_bytes": 4 * TH, "act_bytes": 4 * A}


# ------------------------------------------------------------------ #
# measured memory: XLA buffer assignment per lane, next to Eqs. 2-4/13-15
# ------------------------------------------------------------------ #
def lenet_measured_memory(batch: int = 32) -> Dict[str, Dict[str, int]]:
    """MEASURED per-lane step footprint for the four fp32 paper lanes, at
    one probe per step (Alg. 1, and what Eqs. 2-4 count).

    Lowers and compiles (never runs) the exact production train step —
    same ``make_elastic_step`` program, same state donation as the train
    loop — and reads XLA's buffer-assignment stats
    (core/engine.step_memory_analysis). Returns
    {lane: {argument_bytes, output_bytes, temp_bytes, alias_bytes,
    peak_bytes, ...}}; benchmarks/run.py places ``peak_bytes`` next to
    ``lenet_memory_table``'s Eq. 2-4 value and reports the residual.
    """
    from repro.core.engine import step_memory_analysis
    xs, ys = glyphs(batch, seed=0)
    batch_d = {"x": jnp.asarray(xs), "y": jnp.asarray(ys)}
    rows = {}
    for name, lane, c in lenet_lane_configs(probes=1):
        params = lenet.init_lenet5(jax.random.key(7))
        part = (lambda p, c=c: lenet.partition_at(p, c)) \
            if lane.lane == "elastic_zo" else None
        step = make_elastic_step(lenet.lenet5_loss, lane, partition_fn=part)
        state = TrainState(params, jnp.int32(0),
                           jax.random.key_data(jax.random.key(11)))
        rows[name] = step_memory_analysis(
            step, state, batch_d, np.ones((lane.zo_num_probes,), np.float32))
    return rows


def lenet_int8_measured_memory(batch: int = 32) -> Dict[str, Dict[str, int]]:
    """MEASURED per-lane step footprint for the INT8/INT8* lanes (Alg. 2).

    Same instrument as ``lenet_measured_memory`` over the int8 step.
    Reconciliation caveat: this build *simulates* int8 in XLA (int8
    storage but int32/float32 compute upcasts throughout), so the
    measured peak lands well ABOVE Eq. 13-15 — and above the fp32 lane —
    unlike the paper's hand-managed MCU buffers. The residual reported
    in BENCH_paper.json quantifies exactly that simulation overhead;
    the analytic table remains the paper-faithful number.
    """
    from repro.core.engine import step_memory_analysis
    xs, ys = glyphs(batch, seed=0)
    batch_d = {"x": quant_from_float(jnp.asarray(xs)), "y": jnp.asarray(ys)}
    rows = {}
    for name, c, tail in INT8_LANES:
        step = make_int8_elastic_step(
            lenet.lenet5_forward_int8,
            partition_fn=lambda p, c=c: lenet.partition_at(p, c),
            tail_fcs=tail, lane=int8_lane_config(), loss_mode="int")
        params = lenet.init_lenet5_int8(jax.random.key(7))
        state = TrainState(params, jnp.int32(0),
                           jax.random.key_data(jax.random.key(13)))
        rows[name] = step_memory_analysis(step, state, batch_d,
                                          np.ones((1,), np.float32))
    return rows


# ------------------------------------------------------------------ #
# Fig. 7 analog: step-time breakdown (wall clock on this host)
# ------------------------------------------------------------------ #
def steptime_breakdown(batch=64, iters=20):
    xs, ys = glyphs(batch, seed=0)
    out = {}
    # fp32 phases
    params = lenet.init_lenet5(jax.random.key(0))
    from repro.core import zo
    key = jax.random.key(1)
    fwd = jax.jit(lambda p, x: lenet.lenet5_forward(p, x)[0])
    pert = jax.jit(lambda p: zo.perturb(p, key, 1e-3))
    upd = jax.jit(lambda p: zo.zo_update(p, key, 1e-4))
    bx = jnp.asarray(xs)

    def t(f, *a):
        f(*a)                              # compile+warm
        t0 = time.perf_counter()
        for _ in range(iters):
            jax.block_until_ready(f(*a))
        return (time.perf_counter() - t0) / iters * 1e6

    out["fp32_forward_us"] = t(fwd, params, bx) * 2   # two passes per step
    out["fp32_perturb_us"] = t(pert, params) * 2
    out["fp32_update_us"] = t(upd, params)
    tail_loss = jax.jit(jax.grad(
        lambda bp, x, y: lenet.lenet5_loss({**params, **bp}, {"x": x, "y": y})))
    bp_part = {n: params[n] for n in ("fc3",)}
    out["fp32_bp_tail_us"] = t(tail_loss, bp_part, bx, jnp.asarray(ys))

    # int8 phases
    qparams = lenet.init_lenet5_int8(jax.random.key(0))
    qx = quant_from_float(bx)
    from repro.core.int8 import perturb_int8
    from repro.core import prng
    seed = prng.seed_from_key(key)
    qfwd = jax.jit(lambda p, x: lenet.lenet5_forward_int8(p, x)[0].data)
    qpert = jax.jit(lambda p: perturb_int8(p, seed, 1, 3, jnp.float32(0.33)))
    out["int8_forward_us"] = t(qfwd, qparams, qx) * 2
    out["int8_perturb_us"] = t(qpert, qparams) * 2
    return out


# ------------------------------------------------------------------ #
# §4.3 claim: integer sign agreement rate
# ------------------------------------------------------------------ #
def sign_agreement(trials=500, classes=10, seed=0):
    rng = np.random.default_rng(seed)
    agree, total = 0, 0
    for _ in range(trials):
        B = int(rng.choice([1, 4, 16, 32]))
        ea = int(rng.integers(-6, -2))
        eb = ea + int(rng.integers(-1, 2))
        a = QTensor(jnp.asarray(rng.integers(-110, 110, (B, classes)), jnp.int8),
                    jnp.int32(ea))
        b = QTensor(jnp.asarray(
            np.clip(np.asarray(a.data) + rng.integers(-25, 25, (B, classes)),
                    -127, 127), jnp.int8), jnp.int32(eb))
        y = jnp.asarray(rng.integers(0, classes, (B,)), jnp.int32)
        s_int = int(int_loss_sign(a, b, y))
        d = float(float_loss(a, y) - float_loss(b, y))
        if d == 0.0:
            continue
        total += 1
        agree += (s_int == np.sign(d))
    return agree / total, total
