"""Training driver: metrics, checkpoint cadence, crash recovery, stragglers.

The loop is deliberately dumb about data: batches are pure functions of the
step index (data/synthetic.py), so the *entire* restart state is the
checkpointed (params, step) — after a crash or an elastic re-mesh, training
resumes bit-exactly (ZO noise included, because core/prng.py noise is
mesh-independent).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs
from ..core.elastic import TrainState
from . import checkpoint as ckpt


@dataclass
class LoopConfig:
    total_steps: int = 100
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    log_every: int = 10
    keep: int = 3
    seed: int = 0
    # straggler simulation/mitigation: probability a probe is dropped and
    # masked out instead of waited for (docs/design.md §8)
    probe_drop_rate: float = 0.0
    n_probes: int = 1
    # explicit per-step probe masks (fp32[n_probes]), e.g. the realized
    # commit masks of a fleet run (repro.fleet) replayed through the
    # single-process reference; overrides the rng drop stream.
    mask_fn: Optional[Callable[[int], Any]] = None
    # jit=False runs step_fn as-is: required for host-side composite steps
    # (fleet/reference.py) whose sub-programs are jitted individually and
    # must not be re-fused into one program (FMA contraction would shift
    # the stream by ~1 ulp vs the fleet's update path).
    jit: bool = True

    @classmethod
    def for_lane(cls, lane, **kwargs) -> "LoopConfig":
        """Derive the probe count from the lane instead of hand-syncing.

        The engine-built step asserts its probe_mask shape against the
        lane, so a mismatched manual ``n_probes`` fails loudly at trace
        time; this constructor makes it impossible to mismatch.
        """
        if "n_probes" in kwargs:
            raise ValueError("n_probes is derived from lane.zo_num_probes")
        return cls(n_probes=lane.zo_num_probes, **kwargs)


def init_state(params, seed: int) -> TrainState:
    return TrainState(params, jnp.int32(0),
                      jax.random.key_data(jax.random.key(seed)))


@dataclass
class RunResult:
    """Terminal state of a training run plus the logged (step, loss) curve.

    Unpacks as ``state, history = run(...)`` — ``run`` used to smuggle the
    curve out via a ``run.history`` function attribute, which was both
    thread-hostile and invisible to callers.
    """
    state: TrainState
    history: list

    def __iter__(self):
        return iter((self.state, self.history))


def run(step_fn: Callable, state: TrainState,
        batch_fn: Callable[[int], Dict[str, Any]],
        cfg: LoopConfig,
        param_shardings=None) -> "RunResult":
    """batch_fn(step) -> device-ready batch dict."""
    saver = ckpt.AsyncCheckpointer(cfg.ckpt_dir, cfg.keep) if cfg.ckpt_dir else None
    jstep = jax.jit(step_fn, donate_argnums=(0,)) \
        if cfg.jit and not isinstance(step_fn, jax.stages.Wrapped) else step_fn

    # resume if a committed checkpoint exists
    start = int(state.step)
    if cfg.ckpt_dir:
        last = ckpt.latest_step(cfg.ckpt_dir)
        if last is not None and last > start:
            params, last = ckpt.restore(cfg.ckpt_dir, state.params,
                                        shardings=param_shardings)
            state = TrainState(params, jnp.int32(last), state.seed)
            start = last
            obs.log("train", f"resumed from step {last}", step=last)

    rec = obs.get()
    mem = rec.memory
    if rec.enabled:
        # params are rebound (donation replaces them in place each step,
        # sizes constant); the batch is tracked per step below
        mem.rebind("train.params", obs.memory.tree_nbytes(state.params),
                   key=("train.params", id(cfg)))
    rng = np.random.default_rng(cfg.seed + 17)
    t0 = obs.monotonic()
    history = []
    for step in range(start, cfg.total_steps):
        batch = batch_fn(step)
        if rec.enabled:
            batch_nbytes = mem.alloc("train.batch",
                                     obs.memory.tree_nbytes(batch))
        if cfg.mask_fn is not None:
            mask = np.asarray(cfg.mask_fn(step), np.float32)
        else:
            mask = (rng.uniform(size=cfg.n_probes) >=
                    cfg.probe_drop_rate).astype(np.float32)
            if mask.sum() == 0:
                mask[0] = 1.0      # never drop every probe
        with rec.span("train/step", track="train", step=step) as sp:
            state, metrics = jstep(state, batch, jnp.asarray(mask))
            if rec.enabled:
                jax.block_until_ready(metrics)
        if rec.enabled:
            mem.free("train.batch", batch_nbytes)
            rec.histogram("train.step_ms").observe(sp.dur_ns / 1e6)
            toks = batch.get("tokens")      # absent for vision batches
            ntok = int(np.prod(toks.shape)) if hasattr(toks, "shape") else 0
            if ntok:
                rec.counter("train.tokens").inc(ntok)
            rec.gauge("train.loss").set(float(metrics["loss"]))
            if "moe.held_rows" in metrics:
                rec.counter("moe.held_rows").inc(
                    int(metrics["moe.held_rows"]))
                top = rec.counter("moe.max_expert_rows")  # the largest yet
                top.inc(max(0, int(metrics["moe.max_expert_rows"])
                            - top.value))
        if cfg.log_every and (step % cfg.log_every == 0
                              or step == cfg.total_steps - 1):
            if rec.enabled:
                obs.memory.sample()   # reconcile tagged vs jax.live_arrays
            loss = float(metrics["loss"])
            history.append((step, loss))
            dt = obs.monotonic() - t0
            obs.log("train",
                    f"step {step:6d} loss {loss:.4f} "
                    f"({dt / max(step - start + 1, 1):.3f}s/step)",
                    step=step, loss=loss)
        if saver and step > start and step % cfg.ckpt_every == 0:
            saver.save(step, state.params, extra={"loss": float(metrics['loss'])})
    if saver:
        saver.save(cfg.total_steps, state.params)
        saver.wait()
    return RunResult(state, history)
