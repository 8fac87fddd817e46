"""The correctness check must reject what it exists to reject.

At a tiny size on the CPU, the harness's look for a chip is skipped and
the rest of a run is driven with the timed path broken underneath: a
training step that returns its state unchanged, a step that leaves half
of the batch out, and a served token altered where it is produced. Each
run must come out not correct under the cell's own limits, and so must
the control: the reference computed in fp8 put in the program's place.
"""
import dataclasses

import jax.numpy as jnp
import pytest

import tiny
from bench import run
from bench.drive import serve, train


def _broken_build(monkeypatch, break_step):
    from repro.core import api
    real = api.build

    def build(*a, **k):
        m = real(*a, **k)
        return dataclasses.replace(m, train_step=break_step(m.train_step))
    monkeypatch.setattr(api, "build", build)


def test_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from repro.core.elastic import TrainState

    def unchanged(step):
        def f(state, batch, mask):
            new, metrics = step(state, batch, mask)
            return TrainState(state.params, new.step, new.seed), metrics
        return f
    _broken_build(monkeypatch, unchanged)
    out = train.run(tiny.train_ctx())
    assert not run.verdict(out)
    gaps = {c.name: c.value for c in out["checks"]}
    assert gaps["tail_grad_gap"] == pytest.approx(1.0)
    assert gaps["zo_step_gap"] == pytest.approx(1.0)
    assert gaps["zo_g_gap"] == pytest.approx(1.0)


def test_step_that_leaves_half_the_batch_out_is_not_correct(monkeypatch):
    def half(step):
        def f(state, batch, mask):
            n = batch["tokens"].shape[0] // 2
            return step(state, {k: v[:n] for k, v in batch.items()}, mask)
        return f
    _broken_build(monkeypatch, half)
    out = train.run(tiny.train_ctx())
    assert not run.verdict(out)


def _zo(params):
    return {"embed": params["embed"], "periods_zo": params["periods_zo"]}


def _moved(a, b, factor):
    """a + factor * (b - a), in a's dtype."""
    return (a.astype(jnp.float32) + factor * (b.astype(jnp.float32)
                                             - a.astype(jnp.float32))
            ).astype(a.dtype)


def test_step_with_a_wrong_zo_update_is_not_correct(monkeypatch):
    """One ZO leaf, the embedding, moved twice as far as the others."""
    from repro.core.elastic import TrainState

    def wrong(step):
        def f(state, batch, mask):
            new, metrics = step(state, batch, mask)
            p0, p1 = _zo(state.params), _zo(new.params)
            zo = dict(p1, embed=_moved(p0["embed"], p1["embed"], 2.0))
            return TrainState(dict(new.params, **zo), new.step,
                              new.seed), metrics
        return f
    _broken_build(monkeypatch, wrong)
    out = train.run(tiny.train_ctx())
    assert not run.verdict(out)


def test_served_token_altered_is_not_correct(monkeypatch):
    from repro.serve import sampler
    V = tiny.config(False)["vocab_size"]
    real = sampler.sample_tokens

    def shifted(logits, *knobs, vocab_size=0):
        tok = real(logits, *knobs, vocab_size=vocab_size)
        return ((tok + 1) % V).astype(jnp.int32)
    monkeypatch.setattr(sampler, "sample_tokens", shifted)
    out = serve.run(tiny.serve_ctx())
    assert not run.verdict(out)


def test_train_control_in_fp8_is_not_correct():
    cell = train.TrainCell(tiny.train_ctx())
    seed = 2 ** 31 + 21
    ref = cell.reference(seed)
    checks = cell.compare(
        cell.as_program(cell.reference(seed, precision="fp8")), ref)
    assert not all(c.ok for c in checks)


def test_serve_control_in_fp8_is_not_correct():
    ctx = tiny.serve_ctx()
    cell = serve.ServeCell(ctx)
    seed = 2 ** 31 + 22
    cell.load(seed)
    cell.offer(cell.requests(seed, ctx["seconds"]), ctx["seconds"])
    served = cell.served()
    cell.free()
    g = cell.gaps(seed, served, control=True)
    assert g["control_gap"] > cell.limits["sampled_logit_gap"]
