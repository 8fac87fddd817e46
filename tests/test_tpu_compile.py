"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret-mode tests check what the kernels compute; the interpreter
relaxes the TPU's tiling, memory and op constraints, so these tests hand
each kernel, at the widths the system runs it, to the TPU compiler for a
described (not attached) v5e. Nothing runs. The kernel modules are called
directly: ``kernels/ops.py`` sees the CPU backend here and would take the
jnp reference.

The topology is described inside a fixture, never at import: only the
process that runs these tests loads the TPU compiler library.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels import paged_attn, topk_mask, zo_fused_replay

QWEN = get_arch("qwen3-4b")
SERVE_SLOTS, PAGE_SIZE, PAGES_PER_SEQ = 4, 16, 35     # 512 + 33 tokens


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compile_v5e(one_chip):
    """compile_v5e(fn, *(shape, dtype)) -> Compiled, with the persistent
    compilation cache off: a described chip's programs cannot be read
    back from it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *specs):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in specs]
        return jax.jit(fn).lower(*args).compile()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _has_kernel(compiled):
    return "tpu_custom_call" in compiled.as_text()


def test_paged_attention_compiles_at_qwen3_4b_serve_shapes(compile_v5e):
    B, KVd, Dh = SERVE_SLOTS, QWEN.num_kv_heads, QWEN.head_dim
    G = QWEN.num_heads // KVd
    N = 1 + B * PAGES_PER_SEQ
    bf = jnp.bfloat16
    pool = ((N, PAGE_SIZE, KVd, Dh), bf)
    c = compile_v5e(
        lambda q, kn, vn, kp, vp, pt, sl: paged_attn.paged_attention_step(
            q, kn, vn, kp, vp, pt, sl, scale=Dh ** -0.5),
        ((B, KVd, G, Dh), bf), ((B, KVd, Dh), bf), ((B, KVd, Dh), bf),
        pool, pool, ((B, PAGES_PER_SEQ), jnp.int32), ((B,), jnp.int32))
    assert _has_kernel(c)


def test_topk_topp_mask_compiles_at_qwen3_4b_vocab(compile_v5e):
    B = SERVE_SLOTS
    c = compile_v5e(topk_mask.topk_topp_mask,
                    ((B, QWEN.padded_vocab), jnp.float32),
                    ((B,), jnp.int32), ((B,), jnp.float32))
    assert _has_kernel(c)


def test_zo_fused_replay_compiles_on_a_qwen3_4b_leaf(compile_v5e):
    c = compile_v5e(
        lambda t, s, k: zo_fused_replay.zo_fused_replay(t, s, k, salt=7),
        ((QWEN.d_model, QWEN.d_ff), jnp.bfloat16),
        ((3, 2), jnp.uint32), ((3, 2), jnp.float32))
    assert _has_kernel(c)


def test_zo_fused_replay_int8_compiles_on_a_lenet_leaf(compile_v5e):
    c = compile_v5e(
        lambda t, s, g: zo_fused_replay.zo_fused_replay_int8(
            t, s, g, salt=7, r_max=3, p_zero=0.33, shift=1),
        ((784, 120), jnp.int8), ((3, 2), jnp.uint32), ((3, 2), jnp.int32))
    assert _has_kernel(c)
