"""Training cell of a latent-attention MoE decoder (DeepSeek-V2): the
jitted elastic-ZO step of ``core.api.build``, driven back to back on a
seeded token stream, as ``train.TrainCell`` drives the dense model.

What differs from the dense cell: the model's configuration (latent
attention with YaRN, a leading dense layer, the chip's share of each MoE
layer's routed experts, shared experts), its weights from the seed (the
leading layer's stack ``lead`` is a ZO group of its own), the reference
(bench/reference/ezo_mla_moe.py) and the replay of the ZO leaves' noise.
The window also keeps the step's device-side counts (``moe.held_rows``,
``moe.max_expert_rows``) and, traced, the device time of the scopes
nested in the step's phases (bench/scopes.py). The checks are
``TrainCell.compare``'s, with the |g| of the applied step read as the
ZO leaves' change projected on z over the projection of its bf16 replay
(times the replay's lr |g|), which stays exact for a step too small to
move most bf16 weights.

Readings for the limits (PERF.md):

    python -m bench.drive.train_mla_moe --workload <cell> --seeds 1,2 \\
        [--control-seeds 1]
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import sys

import numpy as np

import jax
import jax.numpy as jnp

from .. import common, scopes, trace as trace_mod, weights
from ..reference import mla_moe
from .train import CHECK_STEPS, EMBED_BLOCKS, TrainCell

ZO_GROUPS = ("embed", "lead", "periods_zo")


def model_config(c: dict):
    """The program's ModelConfig for the configuration file: the router
    keeps the published count of experts and this chip holds the first
    ``n_routed_experts`` of them."""
    common.use_program()
    from repro.configs.archs import ARCHS
    if c.get("tie_word_embeddings") or c.get("q_lora_rank"):
        raise ValueError("the program holds an untied head and no q-LoRA")
    y = c["rope_scaling"]
    return dataclasses.replace(
        ARCHS[c["program_arch"]],
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        yarn_factor=float(y["factor"]),
        yarn_original_max_pos=int(y["original_max_position_embeddings"]),
        yarn_beta_fast=float(y["beta_fast"]),
        yarn_beta_slow=float(y["beta_slow"]), yarn_mscale=float(y["mscale"]),
        yarn_mscale_all_dim=float(y["mscale_all_dim"]),
        num_experts=c["published"]["n_routed_experts"],
        experts_held=c["n_routed_experts"], experts_first=0,
        experts_per_token=c["num_experts_per_tok"],
        moe_d_ff=c["moe_intermediate_size"],
        num_shared_experts=c["n_shared_experts"],
        norm_topk_prob=bool(c["norm_topk_prob"]),
        routed_scaling=float(c["routed_scaling_factor"]),
        first_dense_layers=c["first_k_dense_replace"], tie_embeddings=False,
        dtype=c["torch_dtype"])


# ------------------------------------------------------------------ #
# the program's weights from the seed (as mla_moe.leaf makes each layer)
# ------------------------------------------------------------------ #
def first_layer(group: str, dims: mla_moe.Dims, zo_periods: int) -> int:
    """The model layer of a stack's first slice."""
    return {"lead": 0, "periods_zo": dims.dense_layers,
            "periods_bp": dims.dense_layers + zo_periods}[group]


def program_leaf(seed, path, abstract, dims, zo_periods: int):
    group, name = weights.logical(path)
    if group == "" and path and str(getattr(path[0], "key", "")) == "lead":
        group, name = "lead", name.split("/", 1)[1]
    if not group:
        return weights.layer_leaf(seed, name, abstract.shape, abstract.dtype,
                                  vocab=dims.vocab)
    n, per = abstract.shape[0], abstract.shape[1:]
    if weights.is_norm(name):
        return jnp.ones(abstract.shape, abstract.dtype)
    first = first_layer(group, dims, zo_periods)
    w = weights.uniform(seed, name, (n,) + per, offset=first * math.prod(per))
    w = w * np.float32(math.sqrt(3.0 / mla_moe.fan_in(name, per)))
    return weights.cast(w, abstract.dtype)


def make_params(abstract, seed: int, dims, zo_periods: int):
    def build(s):
        return jax.tree_util.tree_map_with_path(
            lambda p, a: program_leaf(s, p, a, dims, zo_periods), abstract)
    return jax.jit(build)(jnp.uint32(seed))


def change_norms(params, seed: int, dims, zo_periods: int) -> dict:
    def f(tree, s):
        def one(p, leaf):
            a = jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
            w0 = program_leaf(s, p, a, dims, zo_periods)
            d = leaf.astype(jnp.float32) - w0.astype(jnp.float32)
            return jnp.sqrt(jnp.sum(d * d))
        return jax.tree_util.tree_map_with_path(one, tree)
    out = jax.jit(f)(params, jnp.uint32(seed))
    return {"/".join(x for x in weights.logical(p) if x): float(v)
            for p, v in jax.tree_util.tree_leaves_with_path(out)}


class MlaMoeCell(TrainCell):
    """The cell's compiled step, reusable across seeds."""

    def __init__(self, ctx: dict):
        common.use_program()
        from repro.configs.base import LaneConfig, ShapeConfig
        from repro.core import api
        from repro.sharding.rules import ShardingRules
        self.c, self.tr = ctx["config"], ctx["traffic"]
        self.limits = ctx["workload"]["limits"]
        self.gen = common.load_module("traffic", self.tr["generator"])
        cfg = model_config(self.c)
        lane = LaneConfig(**self.tr["lane"])
        self.B, self.S = int(self.tr["batch"]), int(self.tr["seq"])
        shape = ShapeConfig(ctx["cell"], seq_len=self.S, global_batch=self.B,
                            kind="train")
        self.model = api.build(cfg, shape, lane,
                               ShardingRules(None, cfg, shape))
        self.dims = mla_moe.Dims.from_config(self.c)
        self.zo_periods = cfg.num_periods - api.tail_periods(cfg, lane)
        self.V = cfg.vocab_size
        self.probes = lane.zo_num_probes
        self.step_fn = jax.jit(self.model.train_step, donate_argnums=(0,))
        self.compiled = None

    def start(self, seed: int):
        """Weights from the seed, then the first step through the
        compiled step. Returns (state, the program's readings, seconds
        spent reading them)."""
        from repro.train.train_loop import init_state
        from ..reference import noise
        wseed, tseed, dseed = self.seeds(seed)
        params = make_params(self.model.abstract_params(), wseed, self.dims,
                             self.zo_periods)
        state = init_state(params, tseed)
        del params
        self.mask = jnp.ones((self.probes,), jnp.float32)
        if self.compiled is None:
            self.compiled = self.step_fn.lower(
                state, self.feed(dseed, 0), self.mask).compile()
        state, m = self.step_fn(state, self.feed(dseed, 0), self.mask)
        t0 = common.now()
        read = {"loss": float(m["loss"]), "zo_g": float(m["zo_g"]),
                "held_rows": int(m["moe.held_rows"]),
                "max_expert_rows": int(m["moe.max_expert_rows"])}
        p = state.params
        zo = {k: p[k] for k in ZO_GROUPS}
        lr = float(self.tr["lane"]["learning_rate"])
        pseed = noise.probe_seed(tseed, 0)
        s1 = self.zo_step_stats(zo, wseed, pseed, 0.0)
        dz = sum(v[0] for v in s1.values())
        zz = sum(v[1] for v in s1.values())
        read["g_projected"] = -dz / zz / lr
        step = math.copysign(float(np.float32(lr) * np.float32(read["zo_g"])),
                             read["g_projected"])
        s2 = self.zo_step_stats(zo, wseed, pseed, step)
        # The change's projection on z read against the bf16 replay's: a
        # step under half a bf16 ulp of a weight leaves it unmoved, so the
        # bare projection reads small steps short (PERF.md).
        dz2 = sum(v[0] for v in s2.values())
        ez = sum(v[6] for v in s2.values())
        read["g_applied"] = dz2 / ez * step / lr if ez \
            else read["g_projected"]
        read["zo_step"] = {k: (v[2] ** 0.5, v[3] ** 0.5)
                           for k, v in s2.items()}
        read["zo_counts"] = {k: (v[4], v[5]) for k, v in s2.items()}
        tail = {k: v for k, v in p.items() if k not in zo}
        read["norms"] = change_norms(tail, wseed, self.dims, self.zo_periods)
        return state, read, common.now() - t0

    def zo_step_stats(self, zo, wseed: int, pseed, coeff: float) -> dict:
        out = _zo_stats(zo, jnp.uint32(wseed), pseed, jnp.float32(coeff),
                        self.dims, self.zo_periods)
        return {k: tuple(float(x) for x in v) for k, v in out.items()}

    def reference(self, seed: int, precision="f32", half_batch=False):
        from ..reference.ezo_mla_moe import EzoReference
        wseed, tseed, dseed = self.seeds(seed)
        ref = EzoReference(self.dims, self.tr["lane"], wseed, tseed,
                           precision=precision, half_batch=half_batch)
        loss = ref.first_step(self.gen.batch(self.tr, dseed, 0, self.V))
        lp, lm, g = ref.last
        return {"loss": loss, "lp": lp, "lm": lm, "g": g,
                "norms": ref.tail_change_norms()}


@functools.partial(jax.jit, static_argnums=(4, 5))
def _zo_stats(zo, wseed, pseed, coeff, dims, zo_periods):
    """Per ZO leaf, over its elements, with d = leaf - theta0, z the
    reference's replay of the step's noise and e = bf16(theta0 - coeff z)
    - theta0: the sums of d z, z z, (d - e)^2 and e^2, the counts of
    elements where d != e and where e != 0, and the sum of e z. One layer
    (or block of embedding rows) at a time."""
    from ..reference import noise

    def sums(block, w0, z):
        d = block.astype(jnp.float32) - w0.astype(jnp.float32)
        e = weights.cast(w0.astype(jnp.float32) - coeff * z, block.dtype) \
            .astype(jnp.float32) - w0.astype(jnp.float32)
        return jnp.stack([jnp.sum(d * z), jnp.sum(z * z),
                          jnp.sum((d - e) ** 2), jnp.sum(e * e),
                          jnp.sum((d != e).astype(jnp.float32)),
                          jnp.sum((e != 0).astype(jnp.float32)),
                          jnp.sum(e * z)])

    def stack(gpath, init, stacked):
        salt = noise.leaf_salt(gpath)
        per = stacked.shape[1:]
        size = math.prod(per)

        def one(i):
            z = noise.normal(pseed, salt, per,
                             offset=i.astype(jnp.uint32) * jnp.uint32(size))
            return sums(stacked[i], init(i, per, stacked.dtype), z)
        return jnp.sum(jax.lax.map(one, jnp.arange(stacked.shape[0])), 0)

    out = {}
    emb = zo["embed"]
    rows = emb.shape[0]
    k = EMBED_BLOCKS if rows % EMBED_BLOCKS == 0 else 1
    out["embed"] = stack(
        "['embed']",
        lambda i, per, dt: weights.layer_leaf(wseed, "embed", per, dt,
                                              layer=i, vocab=dims.vocab),
        emb.reshape(k, rows // k, emb.shape[1]))
    for group in ("lead", "periods_zo"):
        first = first_layer(group, dims, zo_periods)
        for path, v in jax.tree_util.tree_leaves_with_path(zo[group]):
            keys = [str(getattr(p, "key", p)) for p in path]
            name = "/".join(keys[1:])                  # past "blk0"
            gpath = f"['{group}']" + "".join(f"['{x}']" for x in keys)
            out[f"{group}/" + "/".join(keys)] = stack(
                gpath,
                lambda i, per, dt, name=name, first=first: mla_moe.leaf(
                    wseed, name, per, dt, first + i), v)
    return out


def run(ctx: dict) -> dict:
    t_setup = common.now()
    cell = MlaMoeCell(ctx)
    state, prog, read_s = cell.start(ctx["seed"])
    dseed = cell.seeds(ctx["seed"])[2]
    setup_s = common.now() - t_setup - read_s

    counter = common.CompileCounter()
    tracer = trace_mod.Tracer() if ctx["trace"] else None
    window_losses, counts = [], []
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
            counts.append((m["moe.held_rows"], m["moe.max_expert_rows"]))
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
        text = cell.compiled.as_text()
        layer["trace"] = tracer.stop(window_s, hlo_texts=[text])
        layer["scopes"] = scopes.split(layer["trace"]["ops"], [text])
        print("scopes " + json.dumps(layer["scopes"]), file=sys.stderr)
    peak = common.peak_bytes() + common.temp_bytes(cell.compiled)
    wl = np.asarray([float(x) for x in window_losses])
    failed = int(np.sum(~np.isfinite(wl)))
    held = sum(int(h) for h, _ in counts)
    most = max((int(x) for _, x in counts), default=0)
    del state, m, window_losses
    print(f"window: {steps} steps in {window_s:.3f} s, peak {peak} bytes; "
          f"moe.held_rows {held}, moe.max_expert_rows {most}; first step "
          f"{prog['held_rows']}, {prog['max_expert_rows']}", file=sys.stderr)

    checks = cell.compare(prog, cell.reference(ctx["seed"]))
    tokens = steps * cell.B * cell.S
    layer.update({"window_s": window_s, "steps": steps, "tokens": tokens,
                  "compiles": counter.count, "config": cell.c,
                  "traffic": cell.tr, "moe_held_rows": held,
                  "moe_max_expert_rows": most,
                  "zo_periods": cell.zo_periods})
    return {
        "end_to_end": {"train_tokens_per_s": tokens / window_s,
                       "train_peak_gb": peak / 1e9, "setup_s": setup_s},
        "layer": layer, "attempted": steps, "failed": failed,
        "memory_peak_bytes": peak, "checks": checks,
    }


def calibrate(ctx: dict, seeds, control_seeds) -> None:
    """One JSON line per seed: the numbers the cell compares, from the
    program (the lower readings) and, for a control seed, from the fp8
    control and the half-batch fault put in the program's place (the
    upper readings)."""
    cell = MlaMoeCell(ctx)
    for seed in seeds:
        state, prog, _ = cell.start(seed)
        del state
        ref = cell.reference(seed)
        row = {"seed": seed,
               "program": {c.name: c.value
                           for c in cell.compare(prog, ref)},
               "raw": {"loss": prog["loss"], "zo_g": prog["zo_g"],
                       "g_applied": prog["g_applied"],
                       "g_projected": prog["g_projected"],
                       "held_rows": prog["held_rows"],
                       "max_expert_rows": prog["max_expert_rows"],
                       "ref_loss": ref["loss"], "ref_lp": ref["lp"],
                       "ref_lm": ref["lm"], "ref_g": ref["g"]}}
        if seed in control_seeds:
            for name, kw in (("control_fp8", {"precision": "fp8"}),
                             ("fault_half_batch", {"half_batch": True})):
                low = cell.reference(seed, **kw)
                row[name] = {c.name: c.value for c in
                             cell.compare(cell.as_program(low), ref)}
        print(json.dumps(row), flush=True)


def main(argv=None, require_tpu: bool = True):
    from .. import run as run_mod
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    ctx = run_mod.prepare(args.workload, require_tpu)
    calibrate(ctx, [int(s) for s in args.seeds.split(",")],
              {int(s) for s in args.control_seeds.split(",") if s})


if __name__ == "__main__":
    main()
