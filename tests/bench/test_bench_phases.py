"""The elastic-ZO step's phases read from a trace (bench/phases.py):
a hand-built trace and HLO with scoped ``op_name`` metadata, the train
cell's step compiled on the CPU on the unfused and the fused-probe path
and traced there, the serving programs (which carry none of the names),
and an armed ``repro.obs`` span on the profiler's clock."""
import collections
import glob
import os
import re
from types import SimpleNamespace as NS

import pytest

import jax
import jax.numpy as jnp

import tiny
from bench import phases, trace
from bench.drive.train import TrainCell
from test_bench_trace import HLO, ev, fixture

HLO_SCOPED = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0: u32[8]) -> f32[8] {
  %param_0 = u32[8]{0} parameter(0)
  %shift-right-logical.1 = u32[8]{0} shift-right-logical(%param_0, %param_0), metadata={op_name="jit(step)/zo_perturb/shift_right_logical"}
  %xor.1 = u32[8]{0} xor(%param_0, %shift-right-logical.1), metadata={op_name="jit(step)/zo_perturb/xor"}
  ROOT %convert.1 = f32[8]{0} convert(%xor.1), metadata={op_name="jit(step)/zo_perturb/convert_element_type"}
}

%fused_computation.2 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %add.2 = f32[8]{0} add(%param_0.1, %param_0.1), metadata={op_name="jit(step)/transpose(jvp(bp_tail))/add"}
}

%fused_computation.3 (param_0.2: f32[8]) -> f32[8] {
  %param_0.2 = f32[8]{0} parameter(0)
  %sub.3 = f32[8]{0} subtract(%param_0.2, %param_0.2), metadata={op_name="jit(step)/zo_update/sub"}
  ROOT %copy.3 = f32[8]{1,0} copy(%sub.3)
}

ENTRY %main (p: u32[8], /*index=1*/q: f32[8]) -> f32[8] {
  %p = u32[8]{0} parameter(0)
  %q = f32[8]{0} parameter(1)
  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(zo_forward)/while/body/checkpoint/zo_perturb/add"}
  %fusion.8 = f32[8]{0} fusion(%fusion.7), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(step)/transpose(jvp(bp_tail))/add"}
  %fusion.9 = f32[8]{0} fusion(%fusion.8), kind=kLoop, calls=%fused_computation.3
  %subtract.4 = f32[8]{0} subtract(%fusion.9, %fusion.9), metadata={op_name="jit(step)/sub"}
  ROOT %dot.5 = f32[8]{0} dot(%subtract.4, %subtract.4), metadata={op_name="jit(step)/jvp(zo_forward)/dot_general"}
}
"""


def scoped_fixture():
    """The step's module runs 900..9100: fusion.7 (the noise, in
    zo_perturb nested in zo_forward) 1000..3000, fusion.8 (bp_tail's
    backward) 3000..4000, a while that contains dot.5 (zo_forward)
    4000..6000, fusion.9 (no metadata of its own: zo_update by its
    fused computation's last op_name) 6000..6500, subtract.4 (no scope)
    7000..7500; a transfer outside the module 9500..9700, and an op
    after the window 10500..10600."""
    ops = [
        ev("%fusion.7 = f32[8]{0} fusion(u32[8]{0} %p), kind=kLoop, "
           "calls=%fused_computation.1", 1_000, 2_000),
        ev("%fusion.8 = f32[8]{0} fusion(f32[8]{0} %fusion.7), kind=kLoop, "
           "calls=%fused_computation.2", 3_000, 1_000),
        ev("%while.1 = (f32[8]{0}) while((f32[8]{0}) %t), "
           "condition=%cond, body=%body", 4_000, 2_000),
        ev("%dot.5 = f32[8]{0} dot(f32[8]{0} %a, f32[8]{0} %b)", 4_200,
           1_500),
        ev("%fusion.9 = f32[8]{0} fusion(f32[8]{0} %fusion.8), kind=kLoop, "
           "calls=%fused_computation.3", 6_000, 500),
        ev("%subtract.4 = f32[8]{0} subtract(f32[8]{0} %x, f32[8]{0} %x)",
           7_000, 500),
        ev("%copy.1 = f32[8]{0} copy(f32[8]{0} %y)", 9_500, 200),
        ev("%dot.5 = f32[8]{0} dot(f32[8]{0} %a, f32[8]{0} %b)", 10_500,
           100),
    ]
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_step(1)", 900, 8_200),
                                       ev("jit_step(2)", 10_400, 300)]),
        NS(name="XLA Ops", events=ops)])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench/window", 0, 10_000)])])
    return NS(planes=[host, device])


OLD_KEYS = ("busy_s", "window_s", "ops", "kernels", "kernel_calls",
            "noise_s", "noise_matmul_s", "noise_matched", "breakdown")


def test_phases_read_from_scoped_op_names():
    ph = phases.split(scoped_fixture(), [HLO_SCOPED])
    assert ph["zo_perturb"] == pytest.approx(2e-6)     # nested: innermost
    assert ph["bp_tail"] == pytest.approx(1e-6)        # transpose(jvp(..))
    assert ph["zo_forward"] == pytest.approx(1.5e-6)   # the while is not
    assert ph["zo_update"] == pytest.approx(5e-7)      # from its fused root
    assert ph["unscoped"] == pytest.approx(7e-7)       # subtract + transfer
    r = trace.reduce(scoped_fixture(), [HLO_SCOPED])
    assert sum(ph.values()) == pytest.approx(sum(r["ops"].values()))
    assert r["noise_s"] == pytest.approx(2e-6)
    # the metadata moves no value the trace reduction returns
    bare_hlo = re.sub(r", metadata=\{[^}]*\}", "", HLO_SCOPED)
    bare = trace.reduce(scoped_fixture(), [bare_hlo])
    assert {k: r[k] for k in OLD_KEYS} == {k: bare[k] for k in OLD_KEYS}
    unnamed = phases.split(scoped_fixture(), [bare_hlo])
    assert set(unnamed) == set(ph)
    assert unnamed["unscoped"] == pytest.approx(sum(ph.values()))


def test_phases_of_an_unscoped_program():
    """The trace module's hand-built trace: HLO without metadata leaves
    every op unscoped; without the step's HLO there is no split."""
    ph = phases.split(fixture(), [HLO])
    assert ph == {"zo_perturb": 0.0, "zo_forward": 0.0, "bp_tail": 0.0,
                  "zo_update": 0.0,
                  "unscoped": pytest.approx(trace.reduce(fixture(), [HLO])
                                            ["busy_s"])}
    assert phases.split(fixture(), []) is None


@pytest.mark.parametrize("op_name,phase", [
    ("jit(step)/zo_perturb/add", "zo_perturb"),
    ("jit(step)/jvp(zo_forward)/while/body/closed_call/checkpoint/"
     "dot_general", "zo_forward"),
    ("jit(step)/transpose(jvp(bp_tail))/while/body/transpose", "bp_tail"),
    ("jit(step)/jvp(zo_forward)/while/body/zo_perturb/mul", "zo_perturb"),
    ("jit(step)/zo_update/sub", "zo_update"),
    ("jit(step)/sub", "unscoped"),
    ("jit(step)/my_zo_forward_x/add", "unscoped"),
    ("", "unscoped"),
])
def test_phase_is_the_innermost_scope(op_name, phase):
    assert phases.phase_of(op_name) == phase


def test_the_yardstick_names_the_programs_scopes():
    from repro.core import zo
    assert phases.PHASES == (zo.PERTURB, zo.FORWARD, zo.TAIL, zo.UPDATE)


def test_armed_obs_span_is_on_the_profiler_clock(tmp_path):
    from repro import obs
    rec = obs.install()
    try:
        jax.profiler.start_trace(str(tmp_path))
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            with rec.span("serve/decode", track="serve"):
                jnp.ones(4).block_until_ready()
        jax.profiler.stop_trace()
    finally:
        obs.uninstall()
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    pd = jax.profiler.ProfileData.from_file(path)
    host = [(e.name, e.duration_ns) for p in pd.planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events]
    noted = [d for n, d in host if n == "serve/decode"]
    assert len(noted) == 1 and noted[0] > 0
    assert rec.spans[-1]["name"] == "serve/decode"


def tiny_cell(fused: bool):
    ctx = tiny.train_ctx()
    ctx["traffic"]["lane"]["fused_probes"] = fused
    cell = TrainCell(ctx)
    state, _, _ = cell.start(ctx["seed"])
    return cell, state, cell.seeds(ctx["seed"])[2]


@pytest.fixture(scope="module", params=[False, True],
                ids=["unfused", "fused_probes"])
def started(request):
    return tiny_cell(request.param)


def test_step_hlo_names_every_phase(started):
    cell, _, _ = started
    named = collections.Counter(
        phases.op_phases(cell.compiled.as_text()).values())
    for p in phases.PHASES:
        assert named[p] > 0, (p, named)


def test_serving_programs_carry_no_training_phase(started):
    cell, _, _ = started
    m = cell.model
    params = m.abstract_params()
    tokens = jax.ShapeDtypeStruct((2, 8), jnp.int32)
    prefill = jax.jit(m.prefill_step).lower(params, {"tokens": tokens})
    caches = m.abstract_caches()
    decode = jax.jit(m.decode_step).lower(
        params, jax.ShapeDtypeStruct((2, 1), jnp.int32), caches,
        jax.ShapeDtypeStruct((), jnp.int32))
    for lowered in (prefill, decode):
        text = lowered.compile().as_text()
        assert re.search(r'op_name="', text)
        assert set(phases.op_phases(text).values()) == {phases.UNSCOPED}


def cpu_as_device(pd, module: str):
    """A CPU trace laid out as the TPU's: the step's op events (their
    ``hlo_module`` stat) on an ``XLA Ops`` line, one ``XLA Modules``
    event per run spanning its ops."""
    ops, runs = [], collections.defaultdict(list)
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                st = dict(e.stats)
                if st.get("hlo_module") == module and "hlo_op" in st:
                    ops.append(NS(name=e.name, start_ns=e.start_ns,
                                  duration_ns=e.duration_ns))
                    runs[st["run_id"]].append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    mods = [NS(name=f"{module}({r})", start_ns=min(s for s, _ in iv),
               duration_ns=max(e for _, e in iv) - min(s for s, _ in iv))
            for r, iv in runs.items()]
    return NS(planes=[NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=mods),
        NS(name="XLA Ops", events=ops)])]), ops


def test_cpu_trace_splits_into_phases(started, tmp_path):
    cell, state, dseed = started
    b = cell.feed(dseed, 1)
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(2):
        state, m = cell.step_fn(state, b, cell.mask)
        jax.block_until_ready(state)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    text = cell.compiled.as_text()
    pd, ops = cpu_as_device(jax.profiler.ProfileData.from_file(path),
                            trace.module_name(text))
    assert ops
    split = phases.split(pd, [text])
    assert set(split) == set(phases.PHASES) | {phases.UNSCOPED}
    for p in phases.PHASES:
        assert split[p] > 0, (p, split)
    summed = sum(o.duration_ns for o in ops) / 1e9
    assert sum(split.values()) == pytest.approx(summed, rel=1e-9)
