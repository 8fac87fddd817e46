"""Device time of the elastic-ZO step's four named phases, from a
profiler trace and the step's compiled HLO.

The program names its phases with ``jax.named_scope`` (``zo_perturb``,
``zo_forward``, ``bp_tail``, ``zo_update``); the names reach each
instruction's ``op_name`` metadata in ``compiled.as_text()``. ``split``
gives every device operation the innermost of the four names in its
instruction's ``op_name`` path, through ``jvp(...)`` and
``transpose(...)`` wrappers. An instruction without ``op_name`` (a fusion
whose root a compiler pass added, such as a layout copy or a convert)
takes the last ``op_name`` of the computation it fuses. Operations that
carry none of the names, or that ran outside the modules whose HLO was
given, count as ``unscoped``; control-flow containers (``while``,
``conditional``, ``call``) are not counted, as in ``trace.reduce``'s
``ops``.

The names are the yardstick's own copy, independent of the program's.
``trace.reduce`` does not call ``split``: the harness reports no phase
metric yet (PERF.md, Open questions).
"""
from __future__ import annotations

import re

from . import trace

PHASES = ("zo_perturb", "zo_forward", "bp_tail", "zo_update")
UNSCOPED = "unscoped"

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_PHASE = re.compile(r"\b(" + "|".join(PHASES) + r")\b")


def phase_of(op_name: str) -> str:
    """The innermost of the four phase scopes an ``op_name`` path names."""
    found = _PHASE.findall(op_name)
    return found[-1] if found else UNSCOPED


def op_phases(text: str) -> dict:
    """{instruction name: phase} over every instruction of a compiled
    HLO text (instruction names are unique in a module).

    Computations are told apart with ``trace``'s own patterns, but
    without ``trace.hlo_index``'s guard against an ``=`` before the
    header's ``{``: that guard drops an ENTRY header whose parameter list
    holds ``/*index=N*/``, and the entry's instructions would then count
    as the previous computation's."""
    own, calls, last, cur = {}, {}, {}, None
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
        op = _OP_NAME.search(rest)
        own[name] = op.group(1) if op else None
        calls[name] = trace._CALLS.findall(rest.split("metadata=")[0])
        if op:
            last[cur] = op.group(1)
    return {name: phase_of(op or next(
                (last[c] for c in calls[name] if c in last), ""))
            for name, op in own.items()}


def split(pd, hlo_texts) -> dict | None:
    """{phase: device seconds, ``unscoped``: seconds} of the operations
    inside the ``bench/window`` annotation (the whole trace without
    one), or None when no operation ran in a module of ``hlo_texts``."""
    phased = {trace.module_name(t): op_phases(t) for t in hlo_texts}
    ops, mods, win = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            win += [(s, s + d) for line in plane.lines
                    for n, s, d in trace._events(line) if n == trace.WINDOW]
    device = [p for p in pd.planes if p.name.startswith("/device:")
              and "CPU" not in p.name]
    for plane in device[:1]:
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops = trace._events(line)
            elif line.name == "XLA Modules":
                mods = sorted((s, s + d, n.split("(")[0])
                              for n, s, d in trace._events(line))
    lo, hi = win[0] if win else (float("-inf"), float("inf"))
    clipped = sorted(((n, max(s, lo), min(s + d, hi)) for n, s, d in ops),
                     key=lambda x: x[1])

    out = dict.fromkeys(PHASES + (UNSCOPED,), 0.0)
    found, mi = False, 0
    for n, s, e in clipped:
        if e <= s or trace._CONTAINER.search(
                n.split(" = ", 1)[-1].split("{")[0] + n):
            continue
        while mi < len(mods) and mods[mi][1] < s:
            mi += 1
        mod = mods[mi][2] if mi < len(mods) and mods[mi][0] <= s else None
        phase = UNSCOPED
        if mod in phased:
            found = True
            phase = phased[mod].get(n.split(" = ", 1)[0].lstrip("%"),
                                    UNSCOPED)
        out[phase] += (e - s) / 1e9
    return out if found else None
