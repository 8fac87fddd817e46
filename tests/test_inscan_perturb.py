"""The LM elastic step perturbs inside the layer scan: its noise is
bitwise the noise of the materialised path and of the ZO update, and no
full-size perturbed copy of a ZO leaf is made.

A step whose probes used other noise than its update would move the
parameters along a direction its loss difference never measured, and the
benchmark's check (which replays only the update's noise) would not see
it; these tests are that guard.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import LaneConfig, ShapeConfig, get_arch, reduced
from repro.core import api, elastic, prng, zo
from repro.core.elastic import TrainState
from repro.core.engine import step_memory_analysis
from repro.sharding.rules import ShardingRules

EPS = 1e-3
COUNTER = "zo.perturb.materialized_elements"


def _built(cfg, B=2, S=16):
    shape = ShapeConfig("t", seq_len=S, global_batch=B, kind="train")
    lane = LaneConfig(lane="elastic_zo", bp_tail_layers=1, zo_eps=EPS)
    m = api.build(cfg, shape, lane, ShardingRules(None, cfg, shape))
    return m, lane


def _tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and x.shape == y.shape
        assert np.array_equal(np.asarray(x.astype(jnp.float32)),
                              np.asarray(y.astype(jnp.float32)))


@jax.jit
def _in_scan(zo_part, tokens, key):
    """The in-scan path's perturbed slices of each layer stack (the
    leading dense layers' and the ZO periods', stacked back by the scan)
    and embedding rows, made as ``paired_loss`` makes them."""
    seed = prng.seed_from_key(key)
    plus, minus = {}, {}
    for group in ("lead", "periods_zo"):
        if group not in zo_part:
            continue
        stack = zo_part[group]
        salts, sizes = zo.slice_noise_spec(stack, f"['{group}']")
        n = jax.tree.leaves(stack)[0].shape[0]

        def body(c, xs, salts=salts, sizes=sizes):
            sl, i = xs
            return c, zo.perturb_slice_pair(sl, salts, sizes, i, seed, EPS)

        _, (plus[group], minus[group]) = jax.lax.scan(
            body, 0, (stack, jnp.arange(n)))
    rows = zo.perturb_rows_pair(zo_part, "embed", tokens, key, EPS)
    return plus, minus, rows


@jax.jit
def _materialised(zo_part, key):
    return zo.perturb(zo_part, key, EPS), zo.perturb(zo_part, key, -EPS)


@pytest.mark.parametrize("arch", ["qwen3-4b", "mixtral-8x7b",
                                  "deepseek-v2-lite"])
def test_in_scan_noise_is_the_materialised_noise(arch):
    lead = get_arch(arch).first_dense_layers
    cfg = reduced(get_arch(arch), num_layers=4 + lead)
    m, lane = _built(cfg)
    zo_part, _ = elastic.partition(m.init(jax.random.key(0)), lane)
    assert jax.tree.leaves(zo_part["periods_zo"])[0].shape[0] == 3
    assert ("lead" in zo_part) == bool(lead)
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(7), 5), 0)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0,
                                cfg.vocab_size, jnp.int32)
    plus, minus, (rows_p, rows_m) = _in_scan(zo_part, tokens, key)
    ref_p, ref_m = _materialised(zo_part, key)
    for group in plus:
        _tree_equal(plus[group], ref_p[group])
        _tree_equal(minus[group], ref_m[group])
    _tree_equal(rows_p, ref_p["embed"][tokens])
    _tree_equal(rows_m, ref_m["embed"][tokens])
    # and the probes moved the weights: the pair is not the identity
    assert not np.array_equal(np.asarray(rows_p, np.float32),
                              np.asarray(rows_m, np.float32))


def test_normal_at_is_the_indexed_normal():
    seed = jnp.uint32(2 ** 31 + 17)
    full = prng.normal(seed, 11, (50, 24))
    idx = jnp.asarray([[3, 0, 49], [49, 7, 7]], jnp.uint32)
    flat = idx[..., None] * jnp.uint32(24) + jnp.arange(24, dtype=jnp.uint32)
    assert jnp.array_equal(prng.normal_at(seed, 11, flat), full[idx])


def test_whole_leaf_pair_is_perturb_of_both_signs():
    params = {"a": jax.random.normal(jax.random.key(0), (5, 7), jnp.bfloat16),
              "b": {"c": jnp.linspace(-1, 1, 33, dtype=jnp.float32)}}
    key = jax.random.key(3)
    plus, minus = jax.jit(lambda p: zo.perturb_pair(p, key, EPS))(params)
    ref_p, ref_m = _materialised(params, key)
    _tree_equal(plus, ref_p)
    _tree_equal(minus, ref_m)


def test_elastic_step_holds_no_perturbed_copy():
    """The reduced qwen3 elastic step's compiled temporaries stay below
    one copy of its ZO parameters, and tracing it counts no materialised
    element; the materialised step (no paired loss) counts two copies.

    float32 weights: the CPU backend converts whole bf16 weight stacks to
    float32 for its matmuls, which would hide the copy's absence."""
    cfg = reduced(get_arch("qwen3-4b"), d_model=512, d_ff=2048,
                  num_layers=6, vocab_size=256, dtype="float32")
    m, lane = _built(cfg, B=1, S=8)
    params = m.init(jax.random.key(0))
    zo_part, _ = elastic.partition(params, lane)
    zo_elems = sum(int(a.size) for a in jax.tree.leaves(zo_part))
    zo_bytes = sum(int(a.nbytes) for a in jax.tree.leaves(zo_part))
    state = TrainState(params, jnp.int32(0),
                       jax.random.key_data(jax.random.key(1)))
    batch = {"tokens": jnp.zeros((1, 8), jnp.int32),
             "labels": jnp.zeros((1, 8), jnp.int32),
             "mask": jnp.ones((1, 8), jnp.float32)}
    counted = {}
    for name, step in (("in_scan", m.train_step),
                       ("materialised",
                        elastic.make_elastic_step(m.loss_fn, lane))):
        rec = obs.install()
        try:
            fp = step_memory_analysis(step, state, batch, np.ones(1))
        finally:
            obs.uninstall()
        counted[name] = (rec.counter(COUNTER).value, fp["temp_bytes"])
    assert counted["in_scan"][0] == 0
    assert counted["in_scan"][1] < zo_bytes, (counted, zo_bytes)
    assert counted["materialised"][0] == 2 * zo_elems
    assert counted["materialised"][1] > zo_bytes, (counted, zo_bytes)
