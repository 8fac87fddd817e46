"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret-mode tests check what the kernels compute; the interpreter
relaxes the TPU's tiling, memory and op constraints, so these tests hand
each kernel, at the widths the system runs it, to the TPU compiler for a
described (not attached) v5e. Nothing runs. The kernel modules are called
directly: ``kernels/ops.py`` sees the CPU backend here and would take the
jnp reference.

The topology is described inside a fixture, never at import: only the
process that runs these tests loads the TPU compiler library.

The elastic training step is compiled here too, at qwen3-4b widths with
two layers, to read where the compiler puts the ZO noise.
"""
import dataclasses
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import LaneConfig, ShapeConfig, get_arch
from repro.core import api
from repro.core.elastic import TrainState
from repro.kernels import paged_attn, topk_mask, zo_fused_replay
from repro.sharding.rules import ShardingRules

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


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_HEADER = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\(.*\))?.*\{\s*$")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _fusions(text):
    """{fusion: (opcodes of every computation it calls, its op_name)} of
    a compiled HLO text. Every computation header counts, those whose
    parameter list holds ``/*index=N*/`` included."""
    comps, calls, fusions, cur = {}, {}, {}, None
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            header = _HEADER.match(line)
            if header:
                cur = header.group(1)
                comps.setdefault(cur, set())
                calls.setdefault(cur, set())
            continue
        if cur is None:
            continue
        name, rest = m.groups()
        head = rest.split("metadata=")[0]
        op = re.search(r"\b([a-z][\w\-]*)\(", head)
        comps[cur].add(op.group(1) if op else "")
        calls[cur].update(_CALLS.findall(head))
        if op and op.group(1) == "fusion":
            op_name = _OP_NAME.search(rest)
            fusions[name] = (_CALLS.findall(head),
                             op_name.group(1) if op_name else "")
    memo = {}

    def ops(c):
        if c not in memo:
            memo[c] = set(comps.get(c, ()))
            for d in calls.get(c, ()):
                memo[c] |= ops(d)
        return memo[c]
    return {f: (set().union(set(), *(ops(c) for c in cs)), op_name)
            for f, (cs, op_name) in fusions.items()}


def test_elastic_step_keeps_the_noise_out_of_the_matmuls(one_chip,
                                                         compile_v5e):
    """No fusion of the step holds both the noise hash (u32 xor and
    logical right shift) and a matmul, which would generate z again for
    every weight tile; inside the layer scan each ZO leaf's noise is made
    by one fusion, for both probe signs."""
    cfg = dataclasses.replace(QWEN, num_layers=2)
    B, S = 4, 1024
    shape = ShapeConfig("t", seq_len=S, global_batch=B, kind="train")
    lane = LaneConfig(lane="elastic_zo", bp_tail_layers=1)
    m = api.build(cfg, shape, lane, ShardingRules(None, cfg, shape))

    def spec(shape_, dtype):
        return jax.ShapeDtypeStruct(shape_, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: spec(a.shape, a.dtype),
                          m.abstract_params())
    state = TrainState(params, spec((), jnp.int32), spec((2,), jnp.uint32))
    batch = {"tokens": spec((B, S), jnp.int32),
             "labels": spec((B, S), jnp.int32),
             "mask": spec((B, S), jnp.float32)}
    text = jax.jit(m.train_step, donate_argnums=(0,)).lower(
        state, batch, spec((1,), jnp.float32)).compile().as_text()
    noise = {f: v for f, v in _fusions(text).items()
             if {"xor", "shift-right-logical"} <= v[0]}
    assert noise
    assert not [f for f, (ops, _) in noise.items()
                if ops & {"convolution", "dot"}]
    in_scan = [f for f, (_, op_name) in noise.items()
               if "zo_perturb" in op_name and "while" in op_name]
    assert len(in_scan) == len(jax.tree.leaves(params["periods_zo"]))


def test_mla_moe_step_compiles_with_grouped_matmul_kernels(one_chip,
                                                           compile_v5e):
    """DeepSeek-V2-Lite's elastic step at published widths (one chip's
    16 of 64 experts, the dense layer and two MoE layers): the held
    experts' grouped matmuls become the compiler's ragged-dot kernels,
    three for each probe of the ZO MoE layer, and no fusion holds both
    the noise hash and a matmul; the leading dense layer's noise is made
    inside its own scan, one fusion a leaf."""
    cfg = dataclasses.replace(get_arch("deepseek-v2-lite"), num_layers=3,
                              experts_held=16)
    B, S = 4, 1024
    shape = ShapeConfig("t", seq_len=S, global_batch=B, kind="train")
    lane = LaneConfig(lane="elastic_zo", bp_tail_layers=1)
    m = api.build(cfg, shape, lane, ShardingRules(None, cfg, shape))

    def spec(shape_, dtype):
        return jax.ShapeDtypeStruct(shape_, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: spec(a.shape, a.dtype),
                          m.abstract_params())
    state = TrainState(params, spec((), jnp.int32), spec((2,), jnp.uint32))
    batch = {"tokens": spec((B, S), jnp.int32),
             "labels": spec((B, S), jnp.int32),
             "mask": spec((B, S), jnp.float32)}
    compiled = jax.jit(m.train_step, donate_argnums=(0,)).lower(
        state, batch, spec((1,), jnp.float32)).compile()
    text = compiled.as_text()
    gmm = [n for n in (_INSTR.match(line) for line in text.splitlines())
           if n and n.group(1).startswith("ragged-dot-none")
           and "tpu_custom_call" in n.group(2)]
    assert len(gmm) >= 2 * 3 + 2 * 3      # ZO layer's probes; tail's
    noise = {f: v for f, v in _fusions(text).items()
             if {"xor", "shift-right-logical"} <= v[0]}
    assert not [f for f, (ops, _) in noise.items()
                if ops & {"convolution", "dot"}]
    in_scan = [f for f, (_, op_name) in noise.items()
               if "zo_perturb" in op_name and "while" in op_name]
    assert len(in_scan) == len(jax.tree.leaves(params["periods_zo"])) \
        + len(jax.tree.leaves(params["lead"]))
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 16e9
