"""LM training launcher: ``python -m repro.launch.train --arch <id> ...``

Runs real steps on the available devices (CPU smoke scale by default, the
full production mesh when launched on a TPU slice). For the compile-only
512-way proof use ``repro.launch.dryrun``. ``main`` returns the run's
``RunResult`` (final state, logged losses).
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp

from .. import obs
from ..configs import LaneConfig, ShapeConfig, get_arch, reduced
from ..core import api
from ..data.synthetic import token_batch
from ..sharding.params import param_shardings
from ..sharding.rules import ShardingRules
from ..train.train_loop import LoopConfig, RunResult, init_state, run
from .cache import enable_compile_cache
from .mesh import make_mesh


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--lane", default="elastic_zo",
                    choices=["elastic_zo", "full_zo", "full_bp"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-trainable)")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the depth to this many layers, every width "
                         "kept (0 = the published depth)")
    ap.add_argument("--bp-tail-layers", type=int, default=1)
    ap.add_argument("--probes", type=int, default=1)
    ap.add_argument("--probe-drop", type=float, default=0.0)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--eps", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir")
    ap.add_argument("--mesh", default="",
                    help="e.g. '2x2:data,model' to shard across local devices")
    ap.add_argument("--profile-phases", action="store_true",
                    help="time the engine's canonical step phases "
                         "(separately-jitted diagnostic programs with "
                         "device syncs; the production step is untouched)")
    obs.add_observability_args(ap)
    args = ap.parse_args(argv)
    obs.configure_from_args(args)
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    if args.layers:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    shape = ShapeConfig("cli", seq_len=args.seq, global_batch=args.batch,
                        kind="train")
    lane = LaneConfig(lane=args.lane, bp_tail_layers=args.bp_tail_layers,
                      zo_num_probes=args.probes, learning_rate=args.lr,
                      zo_eps=args.eps)
    mesh = None
    if args.mesh:
        spec, axes = args.mesh.split(":")
        mesh = make_mesh(tuple(int(x) for x in spec.split("x")),
                         tuple(axes.split(",")))
    rules = ShardingRules(mesh, cfg, shape)
    model = api.build(cfg, shape, lane, rules)
    params = model.init(jax.random.key(0))
    pshard = param_shardings(model.abstract_params(), rules)
    if mesh is not None:
        params = jax.tree.map(jax.device_put, params, pshard)
    state = init_state(params, seed=0)

    def batch_fn(step):
        x, y, m = token_batch(args.batch, args.seq - cfg.num_image_tokens
                              if cfg.num_image_tokens else args.seq,
                              cfg.vocab_size, seed=1, step=step)
        b = {"tokens": jnp.asarray(x), "labels": jnp.asarray(y),
             "mask": jnp.asarray(m)}
        if cfg.encoder_layers:
            b["frames"] = jnp.zeros((args.batch, cfg.encoder_seq, cfg.d_model),
                                    jnp.dtype(cfg.dtype))
        if cfg.num_image_tokens:
            b["img"] = jnp.zeros((args.batch, cfg.num_image_tokens, cfg.d_model),
                                 jnp.dtype(cfg.dtype))
        return b

    # n_probes is derived from the lane (LoopConfig.for_lane): the step
    # asserts the mask shape, so the two can never drift apart again
    loop = LoopConfig.for_lane(lane, total_steps=args.steps,
                               ckpt_dir=args.ckpt_dir,
                               log_every=max(args.steps // 10, 1),
                               probe_drop_rate=args.probe_drop)
    if args.profile_phases:
        from ..core import engine as eng
        phases = eng.profile_step_phases(
            eng.engine_for(lane, model.partition_fn
                           if hasattr(model, "partition_fn") else None),
            model.loss_fn, state, batch_fn(0))
        for name, us in phases.items():
            obs.log("train", f"phase {name:10s} {us:10.1f} us")

    state, history = run(model.train_step, state, batch_fn, loop,
                         param_shardings=pshard)
    obs.log("train", f"done at step {int(state.step)}; "
            f"logged {len(history)} loss points")
    obs.write_outputs(args)
    return RunResult(state, history)


if __name__ == "__main__":
    main()
