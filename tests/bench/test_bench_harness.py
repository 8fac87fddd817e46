"""The harness finds everything by name, its traffic is a function of the
seed, the serve warm-up covers every reachable program, and it refuses
to run without a TPU."""
import json
import math
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import common, run
from bench.drive import serve

SPEC = json.loads((common.ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_workload_resolves_by_name(cell):
    w = common.load("workloads", cell)
    entry = next(x for x in SPEC["workloads"] if x["name"] == cell)
    assert (entry["config"], entry["traffic"]) == (w["config"], w["traffic"])
    assert cell == f"{w['config']}.{w['traffic']}"
    c = common.load("configs", w["config"])
    spec_c = next(x for x in SPEC["configs"] if x["name"] == w["config"])
    assert spec_c["file"] == f"bench/configs/{w['config']}.json"
    assert sorted(spec_c["reduced"]) == sorted(c["reduced"])
    tr = common.load("traffic", w["traffic"])
    assert common.load_module("traffic", tr["generator"])
    assert hasattr(common.load_module("drive", w["drive"]), "run")
    for m in run.metric_specs(cell, traced=True):
        assert hasattr(common.load_module("metrics", m["name"]), "read")
    assert run.metric_specs(cell, traced=False)
    common.model_config(c)


def test_every_metric_reports_where_it_moves():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads",
                                                     m["workloads"]))


def test_token_batches_are_deterministic_in_the_seed():
    gen = common.load_module("traffic", "lm_tokens")
    p = {"batch": 4, "seq": 64}
    a = gen.batch(p, 2 ** 31 + 9, 3, 151936)
    b = gen.batch(p, 2 ** 31 + 9, 3, 151936)
    c = gen.batch(p, 2 ** 31 + 9, 4, 151936)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert np.array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    assert len({r.tobytes() for r in a["tokens"]}) == 4


def test_requests_are_deterministic_and_seeds_share_the_work():
    tr = common.load("traffic", "serve.chat-poisson")
    gen = common.load_module("traffic", tr["generator"])
    with pytest.raises(ValueError, match="rate_per_s"):
        gen.requests(tr, 1, 40, 200064)         # no rate until a sweep
    tr = dict(tr, rate_per_s=3.5)
    a = gen.requests(tr, 2 ** 31 + 3, 40, 200064)
    b = gen.requests(tr, 2 ** 31 + 3, 40, 200064)
    c = gen.requests(tr, 17, 40, 200064)
    assert [r["due_s"] for r in a] == [r["due_s"] for r in b]
    assert all(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b))
    assert sorted(len(r["prompt"]) for r in a) == \
        sorted(len(r["prompt"]) for r in c)
    assert sorted(r["max_new"] for r in a) == sorted(r["max_new"] for r in c)
    assert [r["due_s"] for r in a] != [r["due_s"] for r in c]
    assert max(r["due_s"] for r in a) < 40
    lo, hi = tr["prompt"]["min"], tr["prompt"]["max"]
    assert all(lo <= len(r["prompt"]) <= hi for r in a)


def test_serve_warm_up_covers_every_reachable_program():
    from repro.serve.engine import _next_pow2
    tr = common.load("traffic", "serve.chat-poisson")
    scfg = serve.serve_config(tr)
    shapes = serve.reachable(tr, scfg)
    buckets = {min(_next_pow2(n), scfg.max_seq_len)
               for n in range(tr["prompt"]["min"], tr["prompt"]["max"] + 1)}
    want = {(b, nb) for b in buckets
            for nb in range(1, scfg.max_batch_slots + 1)}
    assert set(shapes["prefill"]) == want
    assert shapes["waves"] == list(range(1, scfg.max_batch_slots + 1))
    # the scheduler's horizon is at most the megastep and the ticks to the
    # next page boundary
    assert shapes["horizons"] == list(
        range(1, min(scfg.megastep, scfg.page_size) + 1))
    assert all(sum(s) <= scfg.max_batch_slots and len(s) <= len(buckets)
               for s in shapes["splits"])
    # every composition of a wave of 2..16 into 2..len(buckets) parts
    assert len(shapes["splits"]) == sum(
        math.comb(n - 1, k - 1) for n in range(2, scfg.max_batch_slots + 1)
        for k in range(2, len(buckets) + 1))
    # every pool page a full slot needs is there, so nothing is preempted
    assert scfg.num_pages - 1 == scfg.max_batch_slots * scfg.max_pages_per_seq


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def test_exits_non_zero_without_a_tpu():
    p = _run(common.ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_exits_non_zero_without_the_program(tmp_path):
    shutil.copytree(common.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(common.ROOT / "BENCHMARK.json", tmp_path)
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert not p.stdout.strip()
