"""Dry-run infrastructure: HLO collective parser + roofline accounting.

The SPMD pieces run in a subprocess (they need a multi-device CPU platform
flag that must not leak into the other tests' jax runtime).
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_sub(code: str) -> str:
    return subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin"},
    ).stdout


def test_collective_parser_counts_scan_trips():
    out = run_sub("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.hlo_analysis import collective_bytes, summarize
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        def step(ws, x):
            def body(c, w):
                # row-sharded matmul -> all-reduce inside the scan body
                return jnp.tanh(c @ w), None
            y, _ = jax.lax.scan(body, x, ws)
            return jnp.sum(y)
        wspec = jax.ShapeDtypeStruct((6, 256, 256), jnp.float32)
        xspec = jax.ShapeDtypeStruct((16, 256), jnp.float32)
        c = jax.jit(step, in_shardings=(
            NamedSharding(mesh, P(None, "model", None)),
            NamedSharding(mesh, P("data", "model")),
        )).lower(wspec, xspec).compile()
        total, ops = collective_bytes(c.as_text())
        inside = [o for o in ops if o.trips > 1]
        print("TOTAL", total)
        print("TRIPS", max((o.trips for o in ops), default=0))
    """)
    lines = dict(l.split(" ", 1) for l in out.strip().splitlines() if " " in l)
    assert float(lines["TOTAL"]) > 0
    # the scan has 6 iterations; the body collective must be multiplied
    assert int(lines["TRIPS"]) >= 6


def test_roofline_model_flops_sane():
    from benchmarks.roofline import model_flops_per_device
    r = model_flops_per_device("llama3-8b", "train_4k", 256)
    # llama3-8b: ~8B params -> 2N ~ 16 GF/token; elastic x(2*(1+f_tail))
    per_tok_global = r["total"] / (256 * 4096)
    assert 2e10 < per_tok_global < 2e11, per_tok_global
    d = model_flops_per_device("llama3-8b", "decode_32k", 256)
    assert d["total"] < r["total"] / 1000


def test_cell_matrix_covers_assignment():
    from repro.configs import cell_matrix, ARCHS, SHAPES
    cells = cell_matrix()
    assert len(cells) == len(ARCHS) * len(SHAPES)
    run = [c for c in cells if c[2]]
    skip = [c for c in cells if not c[2]]
    # long_500k runs only for the sub-quadratic archs
    assert {(a, s) for a, s, r, _ in cells if s == "long_500k" and r} == {
        ("mixtral-8x7b", "long_500k"), ("rwkv6-1.6b", "long_500k"),
        ("jamba-v0.1-52b", "long_500k")}
    n_sub = sum(a.subquadratic for a in ARCHS.values())
    n_mla = sum(a.is_mla for a in ARCHS.values())       # trained, not served
    long = sum(s.long_context for s in SHAPES.values())
    serve = sum(s.kind != "train" and not s.long_context
                for s in SHAPES.values())
    assert len(run) == (len(SHAPES) - long) * len(ARCHS) + long * n_sub \
        - serve * n_mla
    assert len(skip) == long * (len(ARCHS) - n_sub) + serve * n_mla


@pytest.mark.skipif(
    not (Path(__file__).resolve().parents[1] / "results" / "dryrun").exists(),
    reason="dry-run artifacts not generated yet")
def test_dryrun_artifacts_complete_and_ok():
    res = Path(__file__).resolve().parents[1] / "results" / "dryrun"
    from repro.configs import cell_matrix
    missing, failed = [], []
    for a, s, run, _ in cell_matrix():
        if not run:
            continue
        for mesh in ("single", "multi"):
            f = res / f"{a}__{s}__{mesh}.json"
            if not f.exists():
                missing.append(f.name)
                continue
            rec = json.loads(f.read_text())
            if rec.get("status") != "ok":
                failed.append(f.name)
    assert not missing, missing
    assert not failed, failed
