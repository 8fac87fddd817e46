"""Update-engine refactor contract: golden digests (tests/golden_cases.py).

``preserved``: the engine-built fp32 elastic_zo/full_zo/full_bp steps
must reproduce the pinned single-probe digests bit for bit (re-pinned
on jax 0.9.0, whose jax.random streams differ from jax<0.5; see
golden_cases.py). ``canonical``: multi-probe
fp32 (accumulate-then-cast probe fold) and the int8 lane (per-probe key
schedule + accumulate-then-clamp) pin the engine's canonical semantics
against future refactors.

Float digests are platform-pinned; the fixture's ``canary`` (a step-free
init+forward digest) detects an environment whose baseline numerics
differ, in which case the float cases skip instead of false-failing.
Integer (int8) cases assert unconditionally on every platform.
"""
import json
from pathlib import Path

import pytest

import golden_cases as gc  # tests/ is on sys.path in pytest rootdir mode

FIXTURE = json.loads(
    (Path(__file__).parent / "golden" / "engine_steps.json").read_text())


def _check(section, name):
    fn = getattr(gc, section.upper())[name]
    want = FIXTURE[section][name]
    if not name.startswith("int8") and gc.run_canary() != FIXTURE["canary"]:
        pytest.skip("platform float numerics differ from the fixture's "
                    "(canary mismatch) — regenerate via golden_cases.py")
    got = fn()
    assert got == want, (
        f"{section}/{name}: engine output diverged from the golden digest"
        f"\n got  {got}\n want {want}")


@pytest.mark.parametrize("name", sorted(gc.PRESERVED))
def test_preserved_bitwise(name):
    """fp32 behavior is preserved bitwise through the engine refactor."""
    _check("preserved", name)


@pytest.mark.parametrize("name", sorted(gc.CANONICAL))
def test_canonical_pinned(name):
    """The engine's canonical semantics are pinned for future PRs."""
    _check("canonical", name)
