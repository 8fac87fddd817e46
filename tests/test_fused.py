"""The LM elastic step, which perturbs inside the layer scan (one noise
generation for both probes), == the engine's materialised two-pass step
(whole perturbed copies, ``make_elastic_step`` with no paired loss)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, LaneConfig, ShapeConfig, reduced
from repro.core import api, elastic, prng
from repro.core.elastic import TrainState
from repro.sharding.rules import ShardingRules


@pytest.mark.parametrize("arch", ["llama3-8b", "mixtral-8x7b", "qwen3-4b"])
def test_fused_equals_unfused(arch):
    cfg = reduced(ARCHS[arch])
    shape = ShapeConfig("s", seq_len=64, global_batch=2, kind="train")
    batch = {
        "tokens": jax.random.randint(jax.random.key(1), (2, 64), 0,
                                     cfg.vocab_size, jnp.int32),
        "labels": jax.random.randint(jax.random.key(2), (2, 64), 0,
                                     cfg.vocab_size, jnp.int32),
        "mask": jnp.ones((2, 64), jnp.float32),
    }
    lane = LaneConfig(lane="elastic_zo", bp_tail_layers=1,
                      learning_rate=1e-2, zo_eps=1e-3)
    m = api.build(cfg, shape, lane, ShardingRules(None, cfg, shape))
    params = m.init(jax.random.key(0))
    state = TrainState(params, jnp.int32(0),
                       jax.random.key_data(jax.random.key(7)))
    steps = {"in_scan": m.train_step,
             "materialised": elastic.make_elastic_step(m.loss_fn, lane)}
    outs = {}
    for name, step in steps.items():
        st2, metrics = jax.jit(step)(state, batch,
                                     jnp.ones((1,), jnp.float32))
        outs[name] = (float(metrics["loss"]), float(metrics["zo_dl"][0]),
                      st2.params)
    (l_a, dl_a, p_a), (l_b, dl_b, p_b) = outs["in_scan"], \
        outs["materialised"]
    assert abs(l_a - l_b) < 1e-3
    # the probes' loss difference: same sign, within a tenth
    assert dl_b != 0 and np.sign(dl_a) == np.sign(dl_b)
    assert abs(dl_a - dl_b) <= 0.1 * abs(dl_b), (dl_a, dl_b)
    for a, b in zip(jax.tree.leaves(p_a), jax.tree.leaves(p_b)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-2, atol=5e-4)


def test_offset_noise_matches_stacked_slice():
    """The flat-offset property the in-scan path relies on: noise of a
    stacked leaf's slice l == offset generation at l*slice_size."""
    seed = jnp.uint32(99)
    full = prng.normal(seed, 13, (6, 4, 8))
    for l in range(6):
        sl = prng.normal(seed, 13, (4, 8), offset=l * 32)
        assert jnp.array_equal(full[l], sl)
