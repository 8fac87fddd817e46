"""Serving launcher: paged continuous-batching engine (or dense baseline).

``python -m repro.launch.serve --arch qwen3-4b --smoke --paged``

--paged drives repro.serve.Engine: paged KV pool, admission queue,
preemption, per-request sampling. Without it, the legacy dense
static-batch greedy loop runs (kept as the baseline; its cache growth now
uses the path-aware grow_dense_caches instead of a shape heuristic).
"""
from __future__ import annotations

import argparse

import numpy as np

from .. import obs
from ..configs import LaneConfig, ServeConfig, get_arch, reduced
from ..serve import Engine, SamplingParams, dense_generate
from .cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--paged", action="store_true",
                    help="serve through the paged continuous-batching engine")
    ap.add_argument("--batch", type=int, default=2,
                    help="number of requests")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=0,
                    help="pool pages per layer (0 = auto-size)")
    ap.add_argument("--slots", type=int, default=0,
                    help="decode batch slots (0 = --batch)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    obs.add_observability_args(ap)
    args = ap.parse_args(argv)
    obs.configure_from_args(args)
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    total = cfg.num_image_tokens + args.prompt_len + args.tokens
    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, args.prompt_len)).astype(np.int32)

    if not args.paged:
        if args.temperature != 0.0 or args.top_k or args.top_p != 1.0:
            ap.error("--temperature/--top-k/--top-p require --paged "
                     "(the dense baseline is greedy-only)")
        t0 = obs.monotonic()
        out = dense_generate(cfg, _init_params(cfg, total), prompts,
                             args.tokens)
        dt = obs.monotonic() - t0
        obs.log("serve", f"dense: {args.tokens} tok/seq x{args.batch} in "
                f"{dt:.2f}s ({args.batch * args.tokens / dt:.1f} tok/s)")
        obs.log("serve", f"sample: {out[0][:16]}")
        obs.write_outputs(args)
        return

    slots = args.slots or args.batch
    ps = args.page_size
    num_pages = args.num_pages or (
        1 + slots * (-(-(total + 1) // ps)))      # null + worst case/slot
    serve = ServeConfig(page_size=ps, num_pages=num_pages,
                        max_batch_slots=slots, max_seq_len=total,
                        max_new_tokens=args.tokens)
    eng = Engine(cfg, serve)
    sampling = SamplingParams(temperature=args.temperature,
                              top_k=args.top_k, top_p=args.top_p,
                              seed=args.seed)
    t0 = obs.monotonic()
    outs = eng.generate([list(p) for p in prompts], sampling, args.tokens)
    dt = obs.monotonic() - t0
    util = eng.page_utilization()
    n_tok = sum(len(o) for o in outs)
    obs.log("serve",
            f"paged: {n_tok} tokens across {args.batch} requests in "
            f"{dt:.2f}s ({n_tok / dt:.1f} tok/s, {eng.steps_run} engine "
            "steps)", tokens=n_tok, wall_s=dt, steps=eng.steps_run)
    obs.log("serve",
            f"pages: peak {util['peak_pages']}/{util['total_pages']} "
            f"({100 * util['peak_util']:.0f}%), mean "
            f"{100 * util['mean_util']:.0f}%")
    obs.log("serve", f"sample: {outs[0][:16]}")
    obs.write_outputs(args)


def _init_params(cfg, total):
    import jax
    from ..configs import ShapeConfig
    from ..core import api
    from ..sharding.rules import ShardingRules
    shape = ShapeConfig("cli_init", seq_len=total, global_batch=1,
                        kind="prefill")
    m = api.build(cfg, shape, LaneConfig(), ShardingRules(None, cfg, shape))
    return m.init(jax.random.key(0))


if __name__ == "__main__":
    main()
