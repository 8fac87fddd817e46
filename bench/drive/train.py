"""Training cell: the jitted elastic-ZO step of ``core.api.build``,
driven back to back on a seeded token stream.

Set-up makes the weights from the seed on the device, compiles the step
(or loads it from the cache) and drives that same compiled step through
its first step; its loss, the |g| it reports and the change of every
leaf are kept for the check. The window then goes on from step 2 with
the same object, state donated. After the window the program's state is
freed and the float32 reference follows the first step from the same
seed.

Compared (PERF.md gives the readings behind each limit):
- ``loss_rel_gap``: the step's loss against the reference's;
- ``tail_grad_gap``: per BP-tail leaf, the norm of its change (its
  gradient as the optimizer applied it) against the reference's;
- ``zo_step_gap``: per ZO leaf, the norm of its signed change less
  bf16(theta0 - lr * g * z) - theta0, with z replayed by the reference,
  lr * |g| in float32 from the program's reported ``zo_g`` and the sign
  of g that of the step the program applied, read off all ZO leaves
  together by projecting their change on z; against the norm of that
  leaf's expected change or of the median leaf's, whichever is larger:
  a leaf moved along other noise, left unmoved or moved twice reads
  about 1 or more, unless it is among the smallest leaves;
- ``zo_g_gap``: the |g| of that applied step against the reported
  ``zo_g``.
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp

from .. import common, trace as trace_mod, weights

CHECK_STEPS = 1          # steps compared: the first (PERF.md says why)
EMBED_BLOCKS = 8         # blocks of embedding rows read at a time


class TrainCell:
    """The cell's compiled step, reusable across seeds."""

    def __init__(self, ctx: dict):
        common.use_program()
        from repro.configs.base import LaneConfig, ShapeConfig
        from repro.core import api
        from repro.sharding.rules import ShardingRules
        self.c, self.tr = ctx["config"], ctx["traffic"]
        self.limits = ctx["workload"]["limits"]
        self.gen = common.load_module("traffic", self.tr["generator"])
        cfg = common.model_config(self.c)
        lane = LaneConfig(**self.tr["lane"])
        self.B, self.S = int(self.tr["batch"]), int(self.tr["seq"])
        shape = ShapeConfig(ctx["cell"], seq_len=self.S, global_batch=self.B,
                            kind="train")
        self.model = api.build(cfg, shape, lane,
                               ShardingRules(None, cfg, shape))
        self.first_bp = cfg.num_layers \
            - api.tail_periods(cfg, lane) * len(cfg.pattern)
        self.V = cfg.vocab_size
        self.probes = lane.zo_num_probes
        self.step_fn = jax.jit(self.model.train_step, donate_argnums=(0,))
        self.compiled = None

    def seeds(self, seed: int):
        return (weights.sub_seed(seed, "weights"),
                weights.sub_seed(seed, "train"),
                weights.sub_seed(seed, "data"))

    def feed(self, dseed: int, i: int):
        b = self.gen.batch(self.tr, dseed, i, self.V)
        return {k: jnp.asarray(v) for k, v in b.items()}

    def start(self, seed: int):
        """Weights from the seed, then the first step through the
        compiled step. Returns (state, the program's readings, seconds
        spent reading them)."""
        from repro.train.train_loop import init_state
        from ..reference import noise
        wseed, tseed, dseed = self.seeds(seed)
        params = weights.make_params(self.model.abstract_params(), wseed,
                                     self.first_bp, self.V)
        state = init_state(params, tseed)
        del params
        self.mask = jnp.ones((self.probes,), jnp.float32)
        if self.compiled is None:
            self.compiled = self.step_fn.lower(
                state, self.feed(dseed, 0), self.mask).compile()
        state, m = self.step_fn(state, self.feed(dseed, 0), self.mask)
        t0 = common.now()
        read = {"loss": float(m["loss"]), "zo_g": float(m["zo_g"])}
        p = state.params
        zo = {"embed": p["embed"], "periods_zo": p["periods_zo"]}
        lr = float(self.tr["lane"]["learning_rate"])
        pseed = noise.probe_seed(tseed, 0)
        s1 = zo_step_stats(zo, wseed, pseed, 0.0, self.V)
        dz = sum(v[0] for v in s1.values())
        zz = sum(v[1] for v in s1.values())
        read["g_applied"] = -dz / zz / lr
        step = math.copysign(float(np.float32(lr) * np.float32(read["zo_g"])),
                             read["g_applied"])
        s2 = zo_step_stats(zo, wseed, pseed, step, self.V)
        read["zo_step"] = {k: (v[2] ** 0.5, v[3] ** 0.5)
                           for k, v in s2.items()}
        read["zo_counts"] = {k: (v[4], v[5]) for k, v in s2.items()}
        tail = {k: v for k, v in p.items() if k not in zo}
        read["norms"] = weights.change_norms(tail, wseed, self.first_bp,
                                             self.V)
        return state, read, common.now() - t0

    def reference(self, seed: int, precision="f32", half_batch=False):
        """The reference's readings of the first step, followed from the
        seed: loss, L+, L-, g and the BP-tail leaves' change norms."""
        from ..reference.ezo import EzoReference
        from ..reference.model import Dims
        wseed, tseed, dseed = self.seeds(seed)
        ref = EzoReference(Dims.from_config(self.c), self.tr["lane"], wseed,
                           tseed, precision=precision, half_batch=half_batch)
        loss = ref.step(0, self.gen.batch(self.tr, dseed, 0, self.V))
        lp, lm, g = ref.last
        return {"loss": loss, "lp": lp, "lm": lm, "g": g,
                "norms": ref.tail_change_norms()}

    @staticmethod
    def as_program(ref: dict) -> dict:
        """A reference's readings in the program's place (the control and
        the faults planted in the reference): its ZO update is exact."""
        return {"loss": ref["loss"], "zo_g": abs(ref["g"]),
                "g_applied": ref["g"], "zo_step": {"all": (0.0, 1.0)},
                "norms": ref["norms"]}

    def compare(self, prog: dict, ref: dict) -> list:
        lim = self.limits
        finite = all(math.isfinite(prog[k])
                     for k in ("loss", "zo_g", "g_applied"))
        loss_gap = abs(prog["loss"] - ref["loss"]) / abs(ref["loss"])
        expected = sorted(e for _, e in prog["zo_step"].values())
        med = expected[len(expected) // 2]
        step_gap = max(d / max(e, med) if max(e, med) > 0 else math.inf
                       for d, e in prog["zo_step"].values())
        g_gap = abs(abs(prog["g_applied"]) - prog["zo_g"]) / prog["zo_g"] \
            if prog["zo_g"] > 0 else math.inf
        if not finite:
            loss_gap = step_gap = g_gap = math.inf
        return [
            common.Check("loss_rel_gap", loss_gap, lim["loss_rel_gap"]),
            common.Check("tail_grad_gap",
                         common.worst_leaf_gap(prog["norms"], ref["norms"]),
                         lim["tail_grad_gap"]),
            common.Check("zo_step_gap", step_gap, lim["zo_step_gap"]),
            common.Check("zo_g_gap", g_gap, lim["zo_g_gap"]),
        ]


def zo_step_stats(zo, wseed: int, pseed, coeff: float, vocab: int) -> dict:
    """Per ZO leaf of the program, over its elements, with d = leaf -
    theta0, z the reference's replay of the step's noise and e =
    bf16(theta0 - coeff * z) - theta0: the sums of d z, z z, (d - e)^2
    and e^2, and the counts of elements where d != e and where e != 0.
    One layer (or block of embedding rows) at a time."""
    out = _zo_stats(zo, jnp.uint32(wseed), pseed, jnp.float32(coeff), vocab)
    return {k: tuple(float(x) for x in v) for k, v in out.items()}


@functools.partial(jax.jit, static_argnums=(4,))
def _zo_stats(zo, wseed, pseed, coeff, vocab):
    from ..reference import noise

    def sums(block, w0, z):
        d = block.astype(jnp.float32) - w0.astype(jnp.float32)
        e = weights.cast(w0.astype(jnp.float32) - coeff * z, block.dtype) \
            .astype(jnp.float32) - w0.astype(jnp.float32)
        return jnp.stack([jnp.sum(d * z), jnp.sum(z * z),
                          jnp.sum((d - e) ** 2), jnp.sum(e * e),
                          jnp.sum((d != e).astype(jnp.float32)),
                          jnp.sum((e != 0).astype(jnp.float32))])

    def leaf(path, name, stacked):
        salt = noise.leaf_salt(path)
        n = stacked.shape[0]
        per = stacked.shape[1:]
        size = math.prod(per)

        def one(i):
            w0 = weights.layer_leaf(wseed, name, per, stacked.dtype,
                                    layer=i, vocab=vocab)
            z = noise.normal(pseed, salt, per,
                             offset=i.astype(jnp.uint32) * jnp.uint32(size))
            return sums(stacked[i], w0, z)
        return jnp.sum(jax.lax.map(one, jnp.arange(n)), axis=0)

    out = {}
    emb = zo["embed"]
    rows = emb.shape[0]
    k = EMBED_BLOCKS if rows % EMBED_BLOCKS == 0 else 1
    out["embed"] = leaf("['embed']", "embed",
                        emb.reshape(k, rows // k, emb.shape[1]))
    for path, v in jax.tree_util.tree_leaves_with_path(zo["periods_zo"]):
        keys = [str(getattr(p, "key", p)) for p in path]
        gpath = "['periods_zo']" + "".join(f"['{x}']" for x in keys)
        out["periods_zo/" + "/".join(keys)] = leaf(gpath, "/".join(keys), v)
    return out


def run(ctx: dict) -> dict:
    t_setup = common.now()
    cell = TrainCell(ctx)
    state, prog, read_s = cell.start(ctx["seed"])
    dseed = cell.seeds(ctx["seed"])[2]
    setup_s = common.now() - t_setup - read_s

    counter = common.CompileCounter()
    tracer = trace_mod.Tracer() if ctx["trace"] else None
    window_losses = []
    i = CHECK_STEPS
    if tracer:
        tracer.start()
    counter.armed = True
    t0 = common.now()
    with jax.profiler.TraceAnnotation(trace_mod.WINDOW):
        while common.now() - t0 < ctx["seconds"]:
            with jax.profiler.TraceAnnotation("bench/feed"):
                b = cell.feed(dseed, i)
            with jax.profiler.TraceAnnotation("bench/dispatch_step"):
                state, m = cell.step_fn(state, b, cell.mask)
            window_losses.append(m["loss"])
            if len(window_losses) > 2:
                with jax.profiler.TraceAnnotation("bench/wait_step"):
                    window_losses[-3].block_until_ready()
            i += 1
        with jax.profiler.TraceAnnotation("bench/wait_step"):
            jax.block_until_ready(state)
    window_s = common.now() - t0
    counter.armed = False
    steps = len(window_losses)
    layer = {}
    if tracer:
        layer["trace"] = tracer.stop(window_s,
                                     hlo_texts=[cell.compiled.as_text()])
    peak = common.peak_bytes() + common.temp_bytes(cell.compiled)
    wl = np.asarray([float(x) for x in window_losses])
    failed = int(np.sum(~np.isfinite(wl)))
    del state, m, window_losses

    checks = cell.compare(prog, cell.reference(ctx["seed"]))
    tokens = steps * cell.B * cell.S
    layer.update({"window_s": window_s, "steps": steps, "tokens": tokens,
                  "compiles": counter.count, "config": cell.c,
                  "traffic": cell.tr})
    return {
        "end_to_end": {"train_tokens_per_s": tokens / window_s,
                       "train_peak_gb": peak / 1e9, "setup_s": setup_s},
        "layer": layer, "attempted": steps, "failed": failed,
        "memory_peak_bytes": peak, "checks": checks,
    }
