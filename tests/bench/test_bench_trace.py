"""Trace reduction: a hand-built trace with known answers, and a small
trace recorded on the CPU."""
from types import SimpleNamespace as NS

import pytest

import jax
import jax.numpy as jnp

from bench import trace

HLO = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0: u32[8]) -> f32[8] {
  %param_0 = u32[8]{0} parameter(0)
  %shift-right-logical.1 = u32[8]{0} shift-right-logical(%param_0, %param_0)
  %xor.1 = u32[8]{0} xor(%param_0, %shift-right-logical.1)
  ROOT %convert.1 = f32[8]{0} convert(%xor.1)
}

%fused_computation.2 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %add.2 = f32[8]{0} add(%param_0, %param_0)
}

ENTRY %main (p: u32[8]) -> f32[8] {
  %p = u32[8]{0} parameter(0)
  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1
  ROOT %fusion.8 = f32[8]{0} fusion(%fusion.7), kind=kLoop, calls=%fused_computation.2
}
"""


def ev(name, start_ns, dur_ns):
    return NS(name=name, start_ns=start_ns, duration_ns=dur_ns)


def fixture():
    ops = [
        ev("%fusion.7 = f32[8]{0} fusion(u32[8]{0} %p), kind=kLoop, "
           "calls=%fused_computation.1", 1_000, 2_000),
        ev("%fusion.8 = f32[8]{0} fusion(f32[8]{0} %fusion.7), kind=kLoop, "
           "calls=%fused_computation.2", 3_000, 1_000),
        ev("%paged_attention_step.3 = (bf16[4,24,128]{2,1,0}) custom-call("
           "s32[4,24]{1,0} %a), custom_call_target=\"tpu_custom_call\"",
           6_000, 1_000),
        ev("%topk_topp_mask.3 = f32[4,1564,128]{2,1,0:T(8,128)} custom-call("
           "s32[4]{0} %k), custom_call_target=\"tpu_custom_call\"",
           7_000, 500),
    ]
    modules = [ev("jit_step(123)", 900, 3_200), ev("jit__megastep(9)", 5_900,
                                                   1_700)]
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=modules), NS(name="XLA Ops", events=ops),
        NS(name="Async XLA Ops", events=[ev("%copy-start", 0, 10_000)])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench/window", 0, 10_000), ev("bench/feed", 4_000, 1_500),
        ev("other", 0, 10_000)])])
    return NS(planes=[host, device])


def test_hand_built_trace():
    r = trace.reduce(fixture(), [HLO])
    assert r["window_s"] == pytest.approx(10e-6)
    assert r["busy_s"] == pytest.approx(4.5e-6)
    assert r["noise_matched"]
    assert r["noise_s"] == pytest.approx(2e-6)
    assert r["kernels"]["paged_attn"] == pytest.approx(1e-6)
    assert r["kernel_calls"]["topk_mask"] == [(pytest.approx(5e-7),
                                               (4, 1564 * 128))]
    idle = dict(r["breakdown"]["idle_gaps"])
    # idle: [0,1000) [4000,6000) [7500,10000); feed covers [4000,5500)
    assert idle["bench/feed"] == pytest.approx(1.5e-6)
    assert idle["(no harness span)"] == pytest.approx(4e-6)
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["fusion.7 f32[8]"] == pytest.approx(2e-6)


def test_noise_fusions_from_hlo():
    assert trace.noise_fusions(HLO) == {"fusion.7": False}
    assert trace.module_name(HLO) == "jit_step"


def test_trace_recorded_on_cpu():
    f = jax.jit(lambda x: jnp.sin(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    t = trace.Tracer()
    t.start()
    with jax.profiler.TraceAnnotation("bench/window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench/step"):
                f(x).block_until_ready()
    r = t.stop(window_s=1.0)
    assert 0 < r["window_s"] < 1.0
    assert r["busy_s"] >= 0.0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
