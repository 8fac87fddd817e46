"""Where the flash-attention kernel engages: the paired ZO forward of the
elastic step only, for dense causal self-attention over at least two key
tiles. Off the TPU the model keeps its XLA attention; these tests force
the TPU dispatch of kernels/ops.py and run the kernel in interpret mode.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import LaneConfig, ShapeConfig, get_arch, reduced
from repro.core import api, elastic
from repro.core.elastic import TrainState
from repro.kernels import flash_attn, ops
from repro.models import transformer
from repro.sharding.rules import ShardingRules

COUNTER = "attn.flash_passes"
DENSE = reduced(get_arch("qwen3-4b"), num_layers=3, head_dim=128,
                dtype="float32")


def _force_tpu_dispatch(monkeypatch):
    """The dispatch as it is on a TPU, the kernel interpreted."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(
        ops, "_pallas_flash_attention",
        lambda *a, interpret=False, **kw: flash_attn.flash_attention(
            *a, interpret=True, **kw))


@pytest.fixture
def on_tpu(monkeypatch):
    _force_tpu_dispatch(monkeypatch)


def _built(cfg, S, B=1):
    # the tail's learning rate large enough that its update, and not the
    # rounding of the parameters it is added to, is what is compared
    shape = ShapeConfig("t", seq_len=S, global_batch=B, kind="train")
    lane = LaneConfig(lane="elastic_zo", bp_tail_layers=1, zo_eps=1e-3,
                      tail_learning_rate=100.0)
    return api.build(cfg, shape, lane, ShardingRules(None, cfg, shape)), lane


def _inputs(m, S, B=1, seed=0):
    params = m.init(jax.random.key(seed))
    state = TrainState(params, jnp.int32(0),
                       jax.random.key_data(jax.random.key(1)))
    tokens = jax.random.randint(jax.random.key(2), (B, S), 0,
                                m.cfg.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1),
             "mask": jnp.ones((B, S), jnp.float32)}
    return state, batch


def _passes(cfg, S):
    m, _ = _built(cfg, S)
    state, batch = _inputs(m, S)
    rec = obs.install()
    try:
        jax.jit(m.train_step).lower(state, batch, jnp.ones((1,), jnp.float32))
    finally:
        obs.uninstall()
    return rec.counter(COUNTER).value


@pytest.mark.parametrize("cfg,S,expected", [
    (DENSE, 256, 2 * 2),                               # 2 ZO layers x 2 probes
    (DENSE, 128, 0),                                   # one key tile
    (reduced(get_arch("deepseek-v2-lite"), num_layers=3), 256, 0),  # MLA
])
def test_flash_passes_count_the_zo_forward_layer_passes(on_tpu, cfg, S,
                                                        expected):
    assert _passes(cfg, S) == expected


def test_off_tpu_the_model_keeps_its_attention():
    assert _passes(DENSE, 256) == 0


def test_only_the_paired_forward_calls_the_kernel(on_tpu):
    """The ZO layers' paired forward holds the kernel; the BP tail's
    layers, run as the step runs them, do not."""
    S = 256
    m, lane = _built(DENSE, S)
    state, _ = _inputs(m, S)
    zo_part, bp_part = elastic.partition(state.params, lane)
    rules = m.rules
    x = jnp.zeros((1, S, DENSE.d_model), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (1, S))
    from repro.core import zo
    salts, sizes = zo.slice_noise_spec(zo_part["periods_zo"],
                                       "['periods_zo']")
    paired = jax.make_jaxpr(lambda p, x: transformer.run_periods_paired(
        p, (x, x), DENSE, rules, positions=pos, seed=jnp.uint32(3), eps=1e-3,
        salts=salts, sizes=sizes))(zo_part["periods_zo"], x)
    tail = jax.make_jaxpr(lambda p, x: transformer.run_periods(
        p, x, DENSE, rules, positions=pos, mode="train"))(
            bp_part["periods_bp"], x)
    assert "pallas_call" in str(paired)
    assert "pallas_call" not in str(tail)


def test_routing_leaves_the_tail_gradient_unchanged(monkeypatch):
    """One elastic step with the kernel in the ZO forward and one with the
    XLA attention: the BP tail's update (its learning rate times the grad
    of the paired loss) and the losses agree to float32 rounding."""
    S = 256
    m, lane = _built(DENSE, S)
    state, batch = _inputs(m, S)
    mask = jnp.ones((1,), jnp.float32)
    _, bp0 = elastic.partition(state.params, lane)

    def tail_change(step_out):
        st, met = step_out
        _, bp = elastic.partition(st.params, lane)
        return jax.tree.map(lambda a, b: np.asarray(a - b), bp, bp0), met

    xla, met_x = tail_change(jax.jit(m.train_step)(state, batch, mask))
    _force_tpu_dispatch(monkeypatch)
    m2, _ = _built(DENSE, S)
    rec = obs.install()
    try:
        flash, met_f = tail_change(jax.jit(m2.train_step)(state, batch, mask))
    finally:
        obs.uninstall()
    assert rec.counter(COUNTER).value == 2 * 2
    # the kernel ran: the ZO loss difference moved by its rounding
    assert float(met_f["zo_g"]) != float(met_x["zo_g"])
    np.testing.assert_allclose(float(met_f["loss"]), float(met_x["loss"]),
                               rtol=1e-5)
    leaves_x, leaves_f = jax.tree.leaves(xla), jax.tree.leaves(flash)
    assert leaves_x and any(np.abs(a).max() > 0 for a in leaves_x)
    for a, b in zip(leaves_x, leaves_f):
        np.testing.assert_allclose(b, a, rtol=1e-4,
                                   atol=1e-4 * np.abs(a).max())
