"""Per-architecture smoke tests: reduced same-family config, one elastic
train step + prefill + decode on CPU; asserts shapes and finiteness.
(The full configs are exercised compile-only by launch/dryrun.py.)
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, LaneConfig, ShapeConfig, reduced
from repro.core import api
from repro.core.elastic import TrainState
from repro.sharding.rules import ShardingRules


def _batch(cfg, specs, key=0):
    rng = np.random.default_rng(key)
    batch = {}
    for k, v in specs.items():
        if k in ("tokens", "labels"):
            batch[k] = jnp.asarray(rng.integers(0, cfg.vocab_size, v.shape),
                                   jnp.int32)
        elif k == "mask":
            batch[k] = jnp.ones(v.shape, v.dtype)
        elif k in ("frames", "img"):
            batch[k] = jnp.asarray(rng.normal(size=v.shape) * 0.1, v.dtype)
    return batch


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_arch_train_step(arch):
    cfg = reduced(ARCHS[arch])
    shape = ShapeConfig("t", seq_len=64, global_batch=2, kind="train")
    lane = LaneConfig(lane="elastic_zo", bp_tail_layers=1)
    rules = ShardingRules(None, cfg, shape)
    m = api.build(cfg, shape, lane, rules)
    params = m.init(jax.random.key(0))
    batch = _batch(cfg, m.input_specs())
    batch.pop("probe_mask", None)
    state = TrainState(params, jnp.int32(0),
                       jax.random.key_data(jax.random.key(1)))
    state, metrics = jax.jit(m.train_step)(state, batch,
                                           jnp.ones((1,), jnp.float32))
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and 0 < loss < 20, loss
    # params changed and stayed finite
    changed = False
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(state.params)):
        assert bool(jnp.all(jnp.isfinite(b.astype(jnp.float32))))
        changed |= not jnp.array_equal(a, b)
    assert changed


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_arch_prefill_decode(arch):
    cfg = reduced(ARCHS[arch])
    lane = LaneConfig()
    ps = ShapeConfig("p", seq_len=64, global_batch=2, kind="prefill")
    ds = ShapeConfig("d", seq_len=64, global_batch=2, kind="decode")
    if cfg.is_mla:
        # latent attention is trained, not served: no latent paged cache
        for s in (ps, ds):
            with pytest.raises(NotImplementedError, match="latent paged"):
                api.build(cfg, s, lane, ShardingRules(None, cfg, s))
        return
    mp = api.build(cfg, ps, lane, ShardingRules(None, cfg, ps))
    md = api.build(cfg, ds, lane, ShardingRules(None, cfg, ds))
    params = mp.init(jax.random.key(0))
    batch = _batch(cfg, mp.input_specs())
    nt, caches = jax.jit(mp.prefill_step)(params, batch)
    assert nt.shape == (2, 1) and nt.dtype == jnp.int32
    assert int(nt.min()) >= 0
    nt2, caches2 = jax.jit(md.decode_step)(params, nt, caches, jnp.int32(63))
    assert nt2.shape == (2, 1)
    # cache structure is stable across decode steps
    assert (jax.tree_util.tree_structure(caches)
            == jax.tree_util.tree_structure(caches2))


def test_decode_matches_prefill_rwkv():
    """Recurrent arch invariant: decoding token t with the prefill-produced
    state must equal prefilling t+1 tokens (exact O(1) step vs chunked)."""
    cfg = reduced(ARCHS["rwkv6-1.6b"])
    lane = LaneConfig()
    S = 32
    ps = ShapeConfig("p", seq_len=S, global_batch=1, kind="prefill")
    ps2 = ShapeConfig("p2", seq_len=S + 1, global_batch=1, kind="prefill")
    ds = ShapeConfig("d", seq_len=S + 1, global_batch=1, kind="decode")
    mp = api.build(cfg, ps, lane, ShardingRules(None, cfg, ps))
    mp2 = api.build(cfg, ps2, lane, ShardingRules(None, cfg, ps2))
    md = api.build(cfg, ds, lane, ShardingRules(None, cfg, ds))
    params = mp.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, S + 1)), jnp.int32)
    # path A: prefill S, then decode token S
    ntA, caches = jax.jit(mp.prefill_step)(params, {"tokens": toks[:, :S]})
    ntA2, _ = jax.jit(md.decode_step)(params, toks[:, S:S + 1], caches,
                                      jnp.int32(S))
    # path B: prefill S+1 directly
    ntB, _ = jax.jit(mp2.prefill_step)(params, {"tokens": toks})
    assert int(ntA2[0, 0]) == int(ntB[0, 0])
