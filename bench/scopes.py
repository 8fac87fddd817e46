"""Device time of the scopes nested inside the elastic-ZO step's phases
(``mla``, ``moe_route``, ``moe_experts``), each split by phase, from the
trace reduction's per-operation seconds and the step's compiled HLO.

The program names the parts of a latent-attention MoE layer with
``jax.named_scope`` inside the four phases (core/zo.py); ``op_scopes``
gives every instruction of ``compiled.as_text()`` the innermost of the
three names in its ``op_name`` path and the innermost phase
(``phases.phase_of``). A fusion without ``op_name`` takes the last one of
the computation it fuses, as ``phases.op_phases`` does.

The grouped matmuls are the kernels the TPU compiler writes for
``jax.lax.ragged_dot``: custom calls named ``ragged-dot-none`` (and their
``ragged-dot-metadata``) whose ``op_name`` is the compiler's own, not the
program's path. They count as ``moe_experts``, and as the kernel ``gmm``,
in the phase of the nearest instruction before them in their computation
that names one (in scheduled HLO text order, the work they follow).

The names are the yardstick's own copy, independent of the program's.
"""
from __future__ import annotations

import re

from . import phases, trace

SCOPES = ("mla", "moe_route", "moe_experts")
GMM = "ragged-dot"

_SCOPE = re.compile(r"\b(" + "|".join(SCOPES) + r")\b")


def scope_of(op_name: str):
    found = _SCOPE.findall(op_name)
    return found[-1] if found else None


def op_scopes(text: str) -> dict:
    """{instruction name: (scope or None, phase, is_gmm)} over every
    instruction of a compiled HLO text."""
    order, own, calls, last, cur = [], {}, {}, {}, None
    for line in text.splitlines():
        m = trace._INSTR.match(line)
        if m is None:
            header = trace._COMP.match(line)
            if header:
                cur = header.group(1)
            continue
        if cur is None:
            continue
        name, rest = m.groups()
        op = phases._OP_NAME.search(rest)
        own[name] = op.group(1) if op else None
        calls[name] = trace._CALLS.findall(rest.split("metadata=")[0])
        order.append((cur, name))
        if op:
            last[cur] = op.group(1)
    out, phase_in = {}, {}
    for comp, name in order:
        op = own[name] or next((last[c] for c in calls[name] if c in last),
                               "")
        gmm = name.startswith(GMM)
        phase = phases.phase_of(op)
        if phase != phases.UNSCOPED:
            phase_in[comp] = phase
        elif gmm:
            phase = phase_in.get(comp, phases.UNSCOPED)
        out[name] = ("moe_experts" if gmm else scope_of(op), phase, gmm)
    return out


def split(ops: dict, hlo_texts) -> dict | None:
    """From ``trace.reduce``'s ``ops`` ({"<instruction> <shape>": device
    seconds}): {"scopes": {scope: {phase: seconds}}, "gmm": {phase:
    seconds}, "phases": {phase: seconds of every instruction}}, or None
    when no operation of the window is an instruction of
    ``hlo_texts``."""
    table = {}
    for t in hlo_texts:
        table.update(op_scopes(t))
    out = {"scopes": {s: {} for s in SCOPES}, "gmm": {}, "phases": {}}
    found = False
    for key, sec in ops.items():
        info = table.get(key.split(" ", 1)[0])
        if info is None:
            continue
        found = True
        scope, phase, gmm = info
        out["phases"][phase] = out["phases"].get(phase, 0.0) + sec
        if scope is not None:
            d = out["scopes"][scope]
            d[phase] = d.get(phase, 0.0) + sec
        if gmm:
            out["gmm"][phase] = out["gmm"].get(phase, 0.0) + sec
    return out if found else None


def seconds(split_out: dict, scope: str) -> float:
    """A scope's device seconds over every phase."""
    return sum(split_out["scopes"][scope].values())
