"""Paged serving: kernel-vs-ref, paged-vs-dense parity, preemption
robustness, dense cache-growth regression, CLI smoke."""
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, LaneConfig, ShapeConfig, ServeConfig, reduced
from repro.core import api
from repro.kernels import ref
from repro.kernels.paged_attn import paged_attention_step
from repro.serve import Engine, SamplingParams, dense_generate
from repro.sharding.rules import ShardingRules

# minutes-scale integration suite: dense-vs-paged parity + CLI smoke
pytestmark = pytest.mark.slow


# ------------------------------------------------------------------ #
# kernel vs oracle (interpret mode)
# ------------------------------------------------------------------ #
def _fused_case(seed=0):
    rng = np.random.default_rng(seed)
    B, KVd, G, Dh, N, ps, P = 3, 2, 4, 16, 16, 8, 4
    q = jnp.asarray(rng.normal(size=(B, KVd, G, Dh)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(B, KVd, Dh)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(B, KVd, Dh)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(N, ps, KVd, Dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(N, ps, KVd, Dh)), jnp.float32)
    pt = np.zeros((B, P), np.int32)
    pt[0, :2] = [3, 7]
    pt[1, :4] = [1, 2, 4, 5]
    pt[2, :1] = [9]
    sl = jnp.asarray([11, 30, 3], jnp.int32)
    return q, kn, vn, kp, vp, pt, sl


@pytest.mark.parametrize("window", [0, 6])
@pytest.mark.parametrize("pages_per_block", [1, 2, 4])
def test_paged_kernel_matches_ref(window, pages_per_block):
    q, kn, vn, kp, vp, pt, sl = _fused_case()
    o_ref, kr, vr = ref.paged_attn_step_ref(
        q, kn, vn, kp, vp, jnp.asarray(pt), sl, scale=0.25, window=window)
    o_pal, kpal, vpal = paged_attention_step(
        q, kn, vn, kp, vp, jnp.asarray(pt), sl, scale=0.25, window=window,
        pages_per_block=pages_per_block, interpret=True)
    assert float(jnp.max(jnp.abs(o_ref - o_pal))) < 1e-5
    # the fused KV write must land identically on both paths
    assert bool(jnp.array_equal(kr, kpal))
    assert bool(jnp.array_equal(vr, vpal))
    # and actually hold the incoming token at (page_of(pos), pos % ps)
    ps_ = kp.shape[1]
    for b, pos in enumerate(np.asarray(sl)):
        page = pt[b, pos // ps_]
        assert bool(jnp.array_equal(kpal[page, pos % ps_], kn[b]))


def test_paged_kernel_skips_reclaimed_null_pages():
    """SWA reclamation re-nulls fully windowed-out table entries after
    freeing their pages. The kernel must skip them (no read), and the
    output must equal the un-reclaimed run because the window mask
    already excluded those positions."""
    window = 6
    q, kn, vn, kp, vp, pt, sl = _fused_case()
    o_full, _, _ = paged_attention_step(
        q, kn, vn, kp, vp, jnp.asarray(pt), sl, scale=0.25, window=window,
        interpret=True)
    # row 1 sits at pos 30: window (24, 30] lives entirely in logical
    # page 3, so pages 0..2 are fully out of window -> reclaimed
    rec = pt.copy()
    rec[1, :3] = 0
    o_rec, krec, vrec = paged_attention_step(
        q, kn, vn, kp, vp, jnp.asarray(rec), sl, scale=0.25, window=window,
        interpret=True)
    assert float(jnp.max(jnp.abs(o_full[1] - o_rec[1]))) < 1e-6
    r_ref, kr, vr = ref.paged_attn_step_ref(
        q, kn, vn, kp, vp, jnp.asarray(rec), sl, scale=0.25, window=window)
    assert float(jnp.max(jnp.abs(r_ref - o_rec))) < 1e-5
    assert bool(jnp.array_equal(kr, krec))
    assert bool(jnp.array_equal(vr, vrec))


# ------------------------------------------------------------------ #
# paged engine vs dense static-batch path: identical greedy streams
# ------------------------------------------------------------------ #
# mixtral covers the SWA path: full_kv prefill, paged window mask, and
# window-capped dense growth (bitwise parity holds while the dense ring
# hasn't wrapped — cached 16 tokens == reduced window here)
@pytest.mark.parametrize("arch",
                         ["qwen3-4b", "jamba-v0.1-52b", "mixtral-8x7b"])
def test_paged_matches_dense(arch):
    cfg = reduced(ARCHS[arch])
    serve = ServeConfig(page_size=8, num_pages=64, max_batch_slots=3,
                        max_seq_len=64, max_new_tokens=6)
    eng = Engine(cfg, serve)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (3, 10)).astype(np.int32)
    paged = eng.generate([list(p) for p in prompts], SamplingParams(), 6)
    dense = dense_generate(cfg, eng.params, prompts, 6)
    assert [list(d) for d in dense] == paged
    eng.sched.check_invariants()
    assert eng.sched.pool.used_pages == 0          # all pages returned


def test_megastep_equals_tick_by_tick():
    """The multi-tick fused megastep (ServeConfig.megastep > 1) must be
    invisible in the token streams: same engine, same requests — mixed
    prompt lengths (exercising grouped wave admission) and mixed sampling
    knobs (greedy + temperature/top-k/top-p rows inside one scan) — run
    once with fusion disabled and once with a big horizon cap. SWA arch,
    so reclamation postponement to horizon boundaries is in play too."""
    cfg = reduced(ARCHS["mixtral-8x7b"])           # sliding_window = 16
    base = dict(page_size=4, num_pages=64, max_batch_slots=3,
                max_seq_len=48, max_new_tokens=12)
    rng = np.random.default_rng(7)
    prompts = [list(rng.integers(0, cfg.vocab_size, n).astype(int))
               for n in (5, 9, 14)]
    sampling = [SamplingParams(),
                SamplingParams(temperature=0.8, top_k=7, seed=11),
                SamplingParams(temperature=1.1, top_p=0.9, seed=23)]

    def run(serve):
        eng = Engine(cfg, serve, params=run.params)
        if run.params is None:
            run.params = eng.params
        rids = [eng.submit(p, sp, 12) for p, sp in zip(prompts, sampling)]
        out = eng.run()
        return [out[r] for r in rids], eng.steps_run
    run.params = None

    tick_by_tick, steps1 = run(ServeConfig(**base, megastep=1))
    fused, stepsN = run(ServeConfig(**base, megastep=32))
    assert fused == tick_by_tick
    assert stepsN < steps1, "megastep fusion never engaged"


def test_swa_bounded_pool_long_decode():
    """SWA reclamation: a pool sized to the *window* must complete a
    decode longer than the window (pages return to the pool as they
    slide out), token-identical to an uncontended big-pool run. The
    same request in a non-reclaiming scheduler (window 0) is rejected
    at submit — the pre-reclamation behavior."""
    cfg = reduced(ARCHS["mixtral-8x7b"])           # sliding_window = 16
    w, ps = cfg.sliding_window, 4
    assert w == 16
    new_tok = 24                                   # decode well past window
    total = 8 + new_tok
    # worst case with reclamation: pages_for(window) + 1 = 5 usable pages
    bounded = ServeConfig(page_size=ps, num_pages=1 + (w // ps + 1),
                          max_batch_slots=1, max_seq_len=total,
                          max_new_tokens=new_tok)
    big = Engine(cfg, ServeConfig(page_size=ps, num_pages=32,
                                  max_batch_slots=1, max_seq_len=total,
                                  max_new_tokens=new_tok))
    rng = np.random.default_rng(2)
    prompt = list(rng.integers(0, cfg.vocab_size, 8).astype(np.int32))
    want = big.generate([prompt], SamplingParams(), new_tok)
    small = Engine(cfg, bounded, params=big.params)
    got = small.generate([prompt], SamplingParams(), new_tok)
    assert got == want
    assert small.sched.reclaimed_pages > 0
    assert small.page_utilization()["peak_pages"] <= w // ps + 1
    assert sum(s.preemptions for s in small.sched.finished) == 0, \
        "bounded pool should reclaim, not thrash via preemption"
    small.sched.check_invariants()
    assert small.sched.pool.used_pages == 0
    # without reclamation the same request can never fit: the submit
    # worst-case guard (pages_for(total+1) > pool) rejects it
    from repro.serve.scheduler import Scheduler
    with pytest.raises(ValueError, match="worst case"):
        Scheduler(bounded, window=0).submit(prompt, SamplingParams(),
                                            new_tok)


def test_preemption_preserves_streams():
    """A pool too small for all requests at once forces preemption +
    recompute re-admission; greedy output must equal the uncontended run."""
    cfg = reduced(ARCHS["qwen3-4b"])
    rng = np.random.default_rng(1)
    prompts = [list(t) for t in
               rng.integers(0, cfg.vocab_size, (4, 9)).astype(np.int32)]
    big = Engine(cfg, ServeConfig(page_size=4, num_pages=64,
                                  max_batch_slots=4, max_seq_len=32,
                                  max_new_tokens=8))
    want = big.generate(prompts, SamplingParams(), 8)
    # 9 usable pages; one sequence needs ceil((9+8+1)/4) = 5 -> contention
    small = Engine(cfg, ServeConfig(page_size=4, num_pages=10,
                                    max_batch_slots=4, max_seq_len=32,
                                    max_new_tokens=8),
                   params=big.params)
    got = small.generate(prompts, SamplingParams(), 8)
    assert got == want
    assert sum(s.preemptions for s in small.sched.finished) > 0, \
        "test did not actually exercise preemption"
    small.sched.check_invariants()


def test_sampled_serving_runs_and_is_seeded():
    cfg = reduced(ARCHS["llama3-8b"])
    serve = ServeConfig(page_size=8, num_pages=32, max_batch_slots=2,
                        max_seq_len=48, max_new_tokens=5)
    eng = Engine(cfg, serve)
    prompts = [[1, 2, 3, 4], [9, 8, 7, 6]]
    sp = SamplingParams(temperature=0.8, top_k=40, top_p=0.95, seed=123)
    a = eng.generate(prompts, sp, 5)
    eng2 = Engine(cfg, serve, params=eng.params)
    b = eng2.generate(prompts, sp, 5)
    assert a == b                                   # seed-replay property
    assert all(len(x) == 5 for x in a)
    # sampled tokens must stay inside the REAL vocab (padded unembed
    # columns carry arbitrary weights and are masked out of sampling)
    assert all(0 <= t < cfg.vocab_size for x in a for t in x)


# ------------------------------------------------------------------ #
# dense-path cache growth regression (the old shape heuristic)
# ------------------------------------------------------------------ #
def test_grow_dense_caches_ignores_lookalike_dims():
    """whisper smoke: encoder_seq == prompt length. The old grow() padded
    any dim-2 == prompt-length leaf, corrupting cross-attn KV; the
    path-aware growth must leave everything but self-attn k/v alone."""
    from repro.serve import grow_dense_caches
    cfg = reduced(ARCHS["whisper-small"])          # encoder_seq = 16
    Lp = cfg.encoder_seq                           # collide on purpose
    lane = LaneConfig()
    ps_ = ShapeConfig("p", seq_len=Lp, global_batch=2, kind="prefill")
    mp = api.build(cfg, ps_, lane, ShardingRules(None, cfg, ps_))
    params = mp.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (2, Lp)), jnp.int32),
        "frames": jnp.zeros((2, cfg.encoder_seq, cfg.d_model),
                            jnp.dtype(cfg.dtype))}
    _, caches = jax.jit(mp.prefill_step)(params, batch)
    total = Lp + 8
    grown = grow_dense_caches(caches, cfg, total)
    for part in ("zo", "bp"):
        for old, new in zip(caches[part], grown[part]):
            assert new["k"].shape[2] == total
            assert new["v"].shape[2] == total
            assert new["ck"].shape == old["ck"].shape      # untouched
            assert new["cv"].shape == old["cv"].shape
            assert bool(jnp.array_equal(new["ck"], old["ck"]))


def test_dense_generate_whisper_lookalike_end_to_end():
    """Full dense serve at the collision length must decode fine."""
    cfg = reduced(ARCHS["whisper-small"])
    lane = LaneConfig()
    shape = ShapeConfig("i", seq_len=32, global_batch=1, kind="prefill")
    m = api.build(cfg, shape, lane, ShardingRules(None, cfg, shape))
    params = m.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size,
                           (2, cfg.encoder_seq)).astype(np.int32)
    out = dense_generate(cfg, params, prompts, 4)
    assert out.shape == (2, 4)
    assert (out >= 0).all()


# ------------------------------------------------------------------ #
# CLI smoke (acceptance: --smoke --paged completes)
# ------------------------------------------------------------------ #
def test_serve_cli_paged_smoke():
    from pathlib import Path
    src = str(Path(__file__).resolve().parents[1] / "src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "qwen3-4b",
         "--smoke", "--paged", "--batch", "2", "--prompt-len", "8",
         "--tokens", "4", "--page-size", "4"],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": src, "PATH": "/usr/bin:/bin",
             # the CLI's main() turns the persistent cache on; tests don't
             "JAX_ENABLE_COMPILATION_CACHE": "false"})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "[serve] paged:" in r.stdout
    assert "pages: peak" in r.stdout
