"""From a profiler trace of the window to the numbers the metrics read.

``Tracer`` records the window with JAX's profiler (host Python tracing
off, so the harness's own ``TraceAnnotation`` spans and the device's
operations are what it holds) into a temporary directory, and ``reduce``
turns the ``.xplane.pb`` into:

* ``busy_s``: the union of the device's operation intervals within the
  window, and ``window_s``;
* ``ops``: device seconds per operation name;
* ``kernels`` / ``kernel_calls``: device seconds and per-call
  (seconds, (rows, vocab)) of the Pallas kernels. A TPU trace names each
  operation by its HLO instruction text, and the program's kernels are
  custom calls named after their jitted wrappers (``paged_attention_step``,
  ``topk_topp_mask``);
* ``noise_s``: device seconds of the fusions whose computation, in the
  compiled HLO of the module they ran in, holds the ZO hash's u32 ``xor``
  and ``shift-right-logical`` (fusions that also hold a matmul are
  counted apart, as ``noise_matmul_s``);
* ``breakdown``: the ten operations that took most time, and the idle
  time of the device split by the harness annotation that was open on
  the host meanwhile.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
from collections import defaultdict

KERNELS = {"paged_attn": "%paged_attention_step", "topk_mask": "%topk_topp_mask"}
WINDOW = "bench/window"


class Tracer:
    def __init__(self):
        self.dir = None

    def start(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self, window_s: float, hlo_texts=()) -> dict:
        import jax
        from jax.profiler import ProfileData
        jax.profiler.stop_trace()
        try:
            path = sorted(glob.glob(os.path.join(
                self.dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
            out = reduce(ProfileData.from_file(path), hlo_texts)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        out["window_s"] = out.get("window_s") or window_s
        return out


# ------------------------------------------------------------------ #
# compiled HLO
# ------------------------------------------------------------------ #
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\([^)]*\))?.*\{\s*$")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_SHAPE = re.compile(r"f32\[(\d+),(\d+)(?:,(\d+))?\]")
_CONTAINER = re.compile(r"\) (?:while|conditional|call)\(")


def short_name(op_text: str) -> str:
    """Instruction name and result shape of a trace operation."""
    head, _, rest = op_text.partition(" = ")
    shape = re.sub(r"\{[^}]*\}", "", rest.split(" ", 1)[0])
    return f"{head.lstrip('%')} {shape}"[:120]


def hlo_index(text: str) -> dict:
    """From ``compiled.as_text()``: every instruction's computation,
    opcode and called computations, the opcodes of every computation
    and the computations each one calls."""
    instrs, comps, calls, cur = {}, defaultdict(set), defaultdict(set), None
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m and cur is not None:
            name, rest = m.groups()
            op = re.search(r"\b([a-z][\w\-]*)\(", rest.split("metadata=")[0])
            opcode = op.group(1) if op else ""
            called = _CALLS.findall(rest)
            comps[cur].add(opcode)
            calls[cur].update(called)
            instrs[name] = {"comp": cur, "calls": called, "op": opcode}
            continue
        m = _COMP.match(line)
        if m and "=" not in line.split("{")[0]:
            cur = m.group(1)
    return {"instrs": instrs, "comps": comps, "calls": calls}


def _ops_of(idx, comp, memo) -> set:
    """Opcodes of a computation and of everything it calls."""
    if comp not in memo:
        memo[comp] = set()
        ops = set(idx["comps"].get(comp, ()))
        for c in idx["calls"].get(comp, ()):
            ops |= _ops_of(idx, c, memo)
        memo[comp] = ops
    return memo[comp]


def module_name(text: str) -> str:
    first = text.split("\n", 1)[0]
    return first.split()[1].rstrip(",") if first.startswith("HloModule") \
        else ""


def noise_fusions(text: str) -> dict:
    """{fusion instruction name: holds_matmul} of the fusions whose
    computation holds both the hash's xor and logical right shift."""
    idx = hlo_index(text)
    out, memo = {}, {}
    for name, ins in idx["instrs"].items():
        if ins["op"] != "fusion":
            continue
        ops = set()
        for c in ins["calls"]:
            ops |= _ops_of(idx, c, memo)
        if "xor" in ops and "shift-right-logical" in ops:
            out[name] = bool(ops & {"dot", "convolution"})
    return out


def kernel_of(op_text: str):
    """(kernel, (rows, vocab) or None) of a trace operation, or None."""
    for kernel, prefix in KERNELS.items():
        if op_text.startswith(prefix) and "custom-call" in op_text:
            shape = None
            if kernel == "topk_mask":
                m = _SHAPE.search(op_text.split(" = ", 1)[1])
                if m:
                    a, b, c = m.groups()
                    shape = (int(a), int(b) * int(c or 1))
            return kernel, shape
    return None


# ------------------------------------------------------------------ #
# the trace
# ------------------------------------------------------------------ #
def _events(line):
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def _merge(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(pd, hlo_texts=()) -> dict:
    device = [p for p in pd.planes if p.name.startswith("/device:")
              and "CPU" not in p.name]
    host = [p for p in pd.planes if p.name.startswith("/host:")]
    ops = []
    for plane in device[:1]:
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops = _events(line)
    notes = []
    for plane in host:
        for line in plane.lines:
            notes += [ev for ev in _events(line) if ev[0].startswith("bench/")]
    win = [(s, s + d) for n, s, d in notes if n == WINDOW]
    if win:
        lo, hi = win[0]
    elif ops:
        lo = min(s for _, s, _ in ops)
        hi = max(s + d for _, s, d in ops)
    else:
        lo = hi = 0.0
    clipped = [(n, max(s, lo), min(s + d, hi)) for n, s, d in ops]
    clipped = [(n, s, e) for n, s, e in clipped if e > s]
    busy = _merge([(s, e) for _, s, e in clipped])
    busy_ns = sum(e - s for s, e in busy)

    per_op = defaultdict(float)
    for n, s, e in clipped:
        if not _CONTAINER.search(n.split(" = ", 1)[-1].split("{")[0]
                                 + n):
            per_op[short_name(n)] += (e - s) / 1e9

    modules = {module_name(t): noise_fusions(t) for t in hlo_texts}
    mods = []
    for plane in device[:1]:
        for line in plane.lines:
            if line.name == "XLA Modules":
                mods = sorted((s, s + d, n.split("(")[0])
                              for n, s, d in _events(line))
    kernels, calls = defaultdict(float), defaultdict(list)
    noise = noise_mm = 0.0
    matched = False
    mi = 0
    for n, s, e in sorted(clipped, key=lambda x: x[1]):
        k = kernel_of(n)
        if k is not None:
            kernels[k[0]] += (e - s) / 1e9
            calls[k[0]].append(((e - s) / 1e9, k[1]))
            continue
        while mi < len(mods) and mods[mi][1] < s:
            mi += 1
        if mi < len(mods) and mods[mi][0] <= s and mods[mi][2] in modules:
            fused = modules[mods[mi][2]]
            name = n.split(" = ", 1)[0].lstrip("%")
            if name in fused:
                matched = True
                if fused[name]:
                    noise_mm += (e - s) / 1e9
                else:
                    noise += (e - s) / 1e9

    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    host_iv = sorted((s, s + d, n) for n, s, d in notes if n != WINDOW)
    idle = defaultdict(float)
    for gs, ge in gaps:
        covered = 0.0
        for hs, he, n in host_iv:
            ov = min(ge, he) - max(gs, hs)
            if ov > 0:
                idle[n] += ov / 1e9
                covered += ov
        if ge - gs - covered > 0:
            idle["(no harness span)"] += (ge - gs - covered) / 1e9

    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    gaps_top = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": (hi - lo) / 1e9 if win else None,
        "ops": dict(per_op),
        "kernels": dict(kernels),
        "kernel_calls": dict(calls),
        "noise_s": noise,
        "noise_matmul_s": noise_mm,
        "noise_matched": matched,
        "breakdown": {"device_ops": [[n, v] for n, v in top],
                      "idle_gaps": [[n, v] for n, v in gaps_top]},
    }
