"""The float32 reference against the program, at a tiny size on the CPU.

In float32 the program and the reference compute the same mathematics,
so they agree to float32 rounding: logits and loss of the forward, and
the loss and per-leaf parameter changes of an elastic-ZO step
(which checks the reference's replay of the ZO noise).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import tiny
from bench import common, weights
from bench.drive import train
from bench.reference import model
from bench.reference.model import Dims
from bench.traffic import lm_tokens


def _program(c, S, B):
    from repro.configs.base import LaneConfig, ShapeConfig
    from repro.core import api
    from repro.sharding.rules import ShardingRules
    cfg = common.model_config(c)
    shape = ShapeConfig("t", seq_len=S, global_batch=B, kind="prefill")
    lane = LaneConfig()
    m = api.build(cfg, shape, lane, ShardingRules(None, cfg, shape))
    first_bp = cfg.num_layers - api.tail_periods(cfg, lane)
    return cfg, m, first_bp


@pytest.mark.parametrize("qk_norm", [True, False])
def test_forward_logits_and_loss_match_program(qk_norm):
    c = dict(tiny.config(qk_norm), torch_dtype="float32")
    B, S, seed = 2, 16, 1234
    cfg, m, first_bp = _program(c, S, B)
    params = weights.make_params(m.abstract_params(), seed, first_bp,
                                 cfg.vocab_size)
    b = lm_tokens.batch({"batch": B, "seq": S}, 7, 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        got, _ = m.prefill_logits(params, {"tokens": jnp.asarray(b["tokens"])},
                                  jnp.full((B,), S - 1, jnp.int32))
        loss = m.loss_fn(params, {k: jnp.asarray(v) for k, v in b.items()})
    dims = Dims.from_config(c)
    hw = model.head_weights(dims, jnp.uint32(seed))
    x = model.embed(hw, jnp.asarray(b["tokens"]))
    for i in range(dims.layers):
        x = model.layer(model.layer_weights(dims, jnp.uint32(seed), i), x, dims)
    lg = model.logits(hw, x, dims)
    np.testing.assert_allclose(np.asarray(got), np.asarray(lg[:, -1]),
                               rtol=2e-5, atol=2e-5)
    logz = jax.nn.logsumexp(lg, -1)
    ll = jnp.take_along_axis(lg, jnp.asarray(b["labels"])[..., None], -1)[..., 0]
    np.testing.assert_allclose(float(loss), float(jnp.mean(logz - ll)),
                               rtol=1e-5)


def test_elastic_zo_steps_match_program_in_float32():
    ctx = tiny.train_ctx()
    ctx["config"]["torch_dtype"] = "float32"
    out = train.run(ctx)
    gaps = {c.name: c.value for c in out["checks"]}
    assert gaps["loss_rel_gap"] < 1e-5
    assert gaps["tail_grad_gap"] < 1e-4
    assert gaps["zo_step_gap"] < 1e-4
    assert gaps["zo_g_gap"] < 1e-4


def test_noise_replay_matches_the_program_generator():
    from repro.core import prng
    from bench.reference import noise
    seed = jnp.uint32(0xDEADBEEF)
    salt = noise.leaf_salt("['periods_zo']['blk0']['attn']['wq']")
    whole = prng.normal(seed, salt, (3, 8, 4))
    np.testing.assert_array_equal(np.asarray(noise.normal(seed, salt, (3, 8, 4))),
                                  np.asarray(whole))
    np.testing.assert_array_equal(
        np.asarray(noise.normal(seed, salt, (8, 4), offset=jnp.uint32(32))),
        np.asarray(whole[1]))


@pytest.mark.parametrize("dtype", ["bfloat16", "float8_e4m3fn"])
def test_explicit_rounding_equals_the_convert(dtype):
    """The harness rounds by ops a compiler must honour (the TPU's may
    keep float32 across a convert and back); on the CPU they give what
    the convert gives, over float8's finite range (|x| < 464)."""
    rng = np.random.default_rng(7)
    x = rng.standard_normal(200_000).astype(np.float32) \
        * np.float32(10.0) ** rng.uniform(-7, 2.6, 200_000).astype(np.float32)
    x = np.clip(x, -463.0, 463.0)
    x = jnp.asarray(np.concatenate([x, np.float32(
        [0.0, -0.0, 2 ** -10, 3 * 2 ** -10, 1.5 * 2 ** -9, 2.5 * 2 ** -9,
         440.0, 448.0, -447.0, 463.0])]))
    want = x.astype(dtype).astype(jnp.float32)
    if dtype == "bfloat16":
        got = jax.jit(lambda v: weights.cast(v, jnp.bfloat16)
                      .astype(jnp.float32))(x)
    else:
        got = jax.jit(model.fp8_e4m3fn)(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
