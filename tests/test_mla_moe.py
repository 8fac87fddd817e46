"""DeepSeek-V2-Lite's block (latent attention with YaRN, a leading dense
layer, dropless MoE over a held share of the experts with shared experts)
against the plain float32 reference (repro/reference/mla_moe.py), on
seeded random weights at a small size."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, LaneConfig, ShapeConfig, reduced
from repro.core import api, elastic, zo
from repro.core.elastic import TrainState
from repro.models import layers
from repro.models import transformer as tf
from repro.models.moe import init_moe, moe_ffn
from repro.reference import mla_moe as ref
from repro.sharding.rules import ShardingRules

DS = ARCHS["deepseek-v2-lite"]


def small(**kw):
    """d_model 64, one dense layer and two MoE layers, 8 routed experts
    (top 3) of which this chip holds 0-3, two shared, float32 weights."""
    base = dict(num_experts=8, experts_per_token=3, experts_held=4,
                num_layers=3, dtype="float32")
    base.update(kw)
    return reduced(DS, **base)


def _rules(cfg, B=2, S=16):
    return ShardingRules(None, cfg, ShapeConfig("t", S, B, "train"))


def _x(shape, seed=0, scale=1.0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape)
                       * scale, jnp.float32)


# ------------------------------------------------------------------ #
# published constants
# ------------------------------------------------------------------ #
def test_yarn_ramp_bounds_and_softmax_scale():
    rot = DS.qk_rope_head_dim
    assert rot == 64
    assert layers.yarn_correction_range(DS, rot) == (10, 23)
    assert ref.yarn_range(rot, DS.rope_theta, DS.yarn_original_max_pos,
                          DS.yarn_beta_fast, DS.yarn_beta_slow) == (10, 23)
    assert layers.mla_softmax_scale(DS) == pytest.approx(0.114721, abs=5e-7)
    assert ref.softmax_scale(DS) == pytest.approx(0.114721, abs=5e-7)
    assert layers.rope_mscale(DS) == 1.0
    np.testing.assert_allclose(layers.rope_inv_freq(DS, rot),
                               np.asarray(ref.inv_freq(DS, rot)), rtol=1e-6)
    f = layers.rope_inv_freq(DS, rot)
    # extrapolated below the ramp, divided by the factor above it
    assert f[0] == 1.0 and f[9] == pytest.approx(10000 ** (-18 / 64))
    assert f[31] == pytest.approx(10000 ** (-62 / 64) / 40, rel=1e-6)


def test_published_parameter_count_of_one_chip_share():
    cfg = dataclasses.replace(DS, experts_held=16)
    m = api.build(cfg, ShapeConfig("t", 1024, 4, "train"), LaneConfig(),
                  _rules(cfg, 4, 1024))
    n = sum(a.size for a in jax.tree.leaves(m.abstract_params()))
    assert n == 4_910_345_728
    assert cfg.num_periods == 26


# ------------------------------------------------------------------ #
# forward against the reference
# ------------------------------------------------------------------ #
def _program_logits(params, tokens, cfg):
    rules = _rules(cfg, *tokens.shape)
    pos = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    x = tf.embed(params, tokens, cfg, rules, pos)
    x, _ = tf.run_periods(params["lead"], x, tf.lead_config(cfg), rules,
                          positions=pos, mode="train")
    for g in ("periods_zo", "periods_bp"):
        x, _ = tf.run_periods(params[g], x, cfg, rules, positions=pos,
                              mode="train")
    return tf.head_logits(params, x, cfg, rules)


def _built(cfg, B=2, S=16, **lane):
    shape = ShapeConfig("t", seq_len=S, global_batch=B, kind="train")
    lane = LaneConfig(lane="elastic_zo", bp_tail_layers=1, **lane)
    return api.build(cfg, shape, lane, ShardingRules(None, cfg, shape)), lane


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 0.04)])
def test_logits_match_reference(dtype, tol):
    """The logits' error norm against the reference's norm. float32
    weights: the program's float32 rounding alone (1e-5; reads 9e-7).
    bfloat16: activations rounded at every op (2^-8) and a token whose
    top-k flips on that rounding (4%; reads 2.2%); a wrong mechanism
    moves the logits by its own size."""
    cfg = small(dtype=dtype)
    m, _ = _built(cfg)
    params = m.init(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 256)
    got = jax.jit(_program_logits, static_argnums=2)(params, tokens, cfg)
    want = ref.logits(params, tokens, cfg)
    err = float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                / jnp.linalg.norm(want))
    assert err <= tol, err
    loss = jax.jit(m.loss_fn)(params, {"tokens": tokens, "labels": tokens,
                                       "mask": jnp.ones((2, 16))})
    want_loss = ref.loss(params, tokens, tokens, jnp.ones((2, 16)), cfg)
    assert float(loss) == pytest.approx(float(want_loss), rel=tol)


def test_elastic_step_matches_reference():
    """One elastic step (float32 weights): the loss, the ZO update along
    the step's noise and the BP tail's averaged gradient step against the
    reference loss at the two perturbed points."""
    cfg = small()
    m, lane = _built(cfg)
    params = m.init(jax.random.key(0))
    rng = np.random.default_rng(3)
    batch = {"tokens": jnp.asarray(rng.integers(0, 256, (2, 16)), jnp.int32),
             "labels": jnp.asarray(rng.integers(0, 256, (2, 16)), jnp.int32),
             "mask": jnp.ones((2, 16), jnp.float32)}
    seed = jax.random.key_data(jax.random.key(5))
    state = TrainState(params, jnp.int32(0), seed)
    new, met = jax.jit(m.train_step)(state, batch, jnp.ones((1,)))

    zo_part, bp_part = elastic.partition(params, lane)
    pk = jax.random.fold_in(jax.random.fold_in(
        jax.random.wrap_key_data(seed), 0), 0)

    def f(bp, zp):
        return ref.loss(elastic.merge(zp, bp), batch["tokens"],
                        batch["labels"], batch["mask"], cfg)
    zp = zo.perturb(zo_part, pk, lane.zo_eps)
    zm = zo.perturb(zo_part, pk, -lane.zo_eps)
    lp, gp = jax.value_and_grad(f)(bp_part, zp)
    lm, gm = jax.value_and_grad(f)(bp_part, zm)
    assert float(met["loss"]) == pytest.approx(float(lp + lm) / 2, rel=1e-5)
    g = float(zo.projected_gradient(lp, lm, lane.zo_eps, lane.zo_clip))
    assert float(met["zo_g"]) == pytest.approx(abs(g), rel=2e-3)

    want_zo = zo.zo_update(zo_part, pk, lane.learning_rate * g)
    got_zo, got_bp = elastic.partition(new.params, lane)
    for (path, w0), w1, w in zip(jax.tree_util.tree_leaves_with_path(zo_part),
                                 jax.tree.leaves(got_zo),
                                 jax.tree.leaves(want_zo)):
        step = np.linalg.norm(np.asarray(w - w0))
        gap = np.linalg.norm(np.asarray(w1 - w))
        assert gap <= 2e-3 * step + 1e-7, (jax.tree_util.keystr(path), gap)
    for (path, w0), w1, a, b in zip(
            jax.tree_util.tree_leaves_with_path(bp_part),
            jax.tree.leaves(got_bp), jax.tree.leaves(gp),
            jax.tree.leaves(gm)):
        want = w0 - lane.learning_rate * 0.5 * (a + b)
        step = np.linalg.norm(np.asarray(want - w0))
        gap = np.linalg.norm(np.asarray(w1 - want))
        assert gap <= 1e-3 * step + 1e-7, (jax.tree_util.keystr(path), gap)
    # the counts: every slot of the held experts over the ZO MoE layer
    assert 0 < int(met["moe.held_rows"]) <= 2 * 32 * cfg.experts_per_token
    assert 0 < int(met["moe.max_expert_rows"]) <= 32


# ------------------------------------------------------------------ #
# the expert share and dropless dispatch
# ------------------------------------------------------------------ #
def test_four_shares_add_up_to_the_uncut_layer():
    """Four chips hold experts 0-1, 2-3, 4-5 and 6-7: their outputs, with
    the shared experts that each computes counted once, add up to the
    reference's whole layer."""
    full = small(experts_held=0)
    p = init_moe(jax.random.key(2), full, jnp.float32)
    x = _x((2, 16, 64), seed=4)
    want = ref.moe(p, x, full, first=0, held=8)
    shared = ref.swiglu(p["shared"], x)
    total = -3 * shared
    for i in range(4):
        cfg = dataclasses.replace(full, experts_first=2 * i, experts_held=2)
        share = dict(p, **{k: p[k][2 * i:2 * i + 2]
                           for k in ("w_gate", "w_up", "w_down")})
        y, _ = moe_ffn(share, x, cfg, _rules(cfg))
        total = total + y
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("held", [8, 4])
def test_forced_skew_drops_no_slot(held):
    """Every token routed to experts 0 and 1 first (a capacity of 1.25 x
    the mean would have dropped most of them): every slot is computed,
    the counts say so, and the output is the reference's."""
    cfg = small(experts_per_token=2, experts_held=held)
    p = init_moe(jax.random.key(3), cfg, jnp.float32)
    router = np.zeros((64, 8), np.float32)
    router[:, 0], router[:, 1] = 1.0, 0.5
    p["router"] = jnp.asarray(router)
    x = jnp.abs(_x((2, 16, 64), seed=6)) + 0.5
    y, (rows, most) = jax.jit(lambda p, x: moe_ffn(p, x, cfg, _rules(cfg)))(
        p, x)
    assert int(rows) == 2 * 16 * 2 and int(most) == 2 * 16
    np.testing.assert_allclose(y, ref.moe(p, x, cfg), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("arch", ["mixtral-8x7b", "phi3.5-moe-42b-a6.6b"])
def test_every_moe_config_takes_the_dropless_path(arch):
    """The renormalised top-k configs run the same dispatch: under skew
    no slot is dropped and the output is the dense per-token mixture."""
    cfg = reduced(ARCHS[arch], dtype="float32")
    p = init_moe(jax.random.key(0), cfg, jnp.float32)
    router = np.zeros((64, cfg.num_experts), np.float32)
    router[:, 0], router[:, 1] = 1.0, 0.5
    p["router"] = jnp.asarray(router)
    x = jnp.abs(_x((2, 16, 64), seed=7)) + 0.5
    y, (rows, most) = moe_ffn(p, x, cfg, _rules(cfg))
    assert int(rows) == 2 * 16 * cfg.experts_per_token and int(most) == 32
    np.testing.assert_allclose(y, ref.moe(p, x, cfg), rtol=2e-5, atol=2e-5)


def test_serving_an_mla_model_is_not_implemented():
    cfg = reduced(DS)
    for kind in ("prefill", "decode"):
        shape = ShapeConfig("s", 16, 2, kind)
        with pytest.raises(NotImplementedError, match="latent paged"):
            api.build(cfg, shape, LaneConfig(),
                      ShardingRules(None, cfg, shape))
