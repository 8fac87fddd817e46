"""Smoke test of the main paths on a TPU, at published widths.

    python chip_smoke.py               # one chip: every phase below
    python chip_smoke.py --four-chips  # one host of four chips (2x2):
                                       # sharded fine-tuning only

Every phase runs in this one process, because a chip serves one process:

  paper   LeNet-5, the paper's own width: a few elastic-ZO fp32 and
          ElasticZO-INT8 steps, and the int8 golden digest of
          tests/golden_cases.py, which must equal the CPU fixture.
  lm      qwen3-4b elastic-ZO fine-tuning through ``launch/train.py`` at
          every published width, depth cut to what one chip holds.
  serve   qwen3-4b at full depth: paged serving of a greedy and a
          top-k/top-p wave through ``serve.Engine``; the decode program
          must hold Pallas kernels; Pallas paged attention against its
          reference at these widths.
  fleet   ``launch/fleet.py --lane int8`` with its bit-exact self-check;
          the fp32 fleet's self-check is run and recorded.

With ``--four-chips``: the one-chip depth on one of the four devices
against the same depth sharded over the 2x2 mesh, then qwen3-4b at full
depth on the mesh.

Timings, losses and bytes printed on the way are set-up figures of a
smoke run, not benchmark results. Without a TPU the script fails at
once. It prints ``{"ok": true, "device": ...}`` as its last line only
when every phase passed, and exits non-zero otherwise.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
import traceback
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parent

LM_ARCH = "qwen3-4b"
# Deepest elastic-ZO step at 4 x 1024 tokens that the TPU compiler fits
# in a v5e's 15.75 GiB (16.91e9 bytes), compiled for a described v5e:
# memory_analysis() peak (arguments + outputs + temporaries - aliased)
# 35 layers 16.67e9 bytes, 34 layers 16.25e9; 36 layers is refused
# (17.06e9 bytes of live buffers). At 28 layers that peak reads 15.13e9
# where the chip measured 12.88e9 bytes in use.
LM_LAYERS_ONE_CHIP = 35
LM_ARGS = ["--arch", LM_ARCH, "--steps", "3", "--batch", "4",
           "--seq", "1024"]
MESH = "2x2:data,model"
# Sharded and one-device steps run the same bf16 program with matmul
# partial sums reordered over "model" and the batch split over "data";
# each loss is a mean over 4096 tokens. 1e-2 relative is 2.5 bf16
# epsilons (2^-8), and far below the spread of the loss across steps.
LOSS_RTOL = 1e-2

SERVE_REQUESTS = 4
PROMPT_LEN = 512
NEW_TOKENS = 33            # the prefill's token + 2 megasteps of 16
PAGE_SIZE = 16
# Pallas paged attention (bf16 in, float32 inside, bf16 out) against the
# float32 reference at highest precision: outputs are softmax averages
# of N(0, 1) values, so bf16 output rounding alone is up to 2^-8 * 4.
PAGED_ATOL = 3e-2


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def finite_losses(losses, what: str) -> None:
    check(len(losses) > 0 and all(math.isfinite(x) for x in losses),
          f"{what}: losses not finite: {losses}")


def require_tpu(count: int):
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SmokeFailure(f"needs a TPU; JAX found {d.platform}")
    if len(devs) < count:
        raise SmokeFailure(f"needs {count} chips; JAX found {len(devs)}")
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    return d


def peak_bytes() -> dict:
    from repro.obs.memory import device_memory_stats
    st = device_memory_stats()          # raises on a TPU without stats
    return {"peak_bytes_in_use": st["peak_bytes_in_use"],
            "bytes_limit": st.get("bytes_limit")}


# ------------------------------------------------------------------ #
# paper path: LeNet-5
# ------------------------------------------------------------------ #
def phase_paper(steps: int = 3, batch: int = 32) -> None:
    from benchmarks.paper_tables import (INT8_LANES, int8_lane_config,
                                         lenet_lane_configs)
    from repro.core.elastic import TrainState, make_elastic_step
    from repro.core.elastic_int8 import make_int8_elastic_step
    from repro.core.int8 import quant_from_float
    from repro.data.synthetic import glyphs
    from repro.models import lenet

    xs, ys = glyphs(steps * batch, seed=0)

    def batch_at(s):
        return (jnp.asarray(xs[s * batch:(s + 1) * batch]),
                jnp.asarray(ys[s * batch:(s + 1) * batch]))

    _, lane, c = next(x for x in lenet_lane_configs()
                      if x[0] == "zo_feat_cls1")
    step = jax.jit(make_elastic_step(
        lenet.lenet5_loss, lane,
        partition_fn=lambda p: lenet.partition_at(p, c)))
    state = TrainState(lenet.init_lenet5(jax.random.key(7)), jnp.int32(0),
                       jax.random.key_data(jax.random.key(11)))
    mask = jnp.ones((lane.zo_num_probes,), jnp.float32)
    losses = []
    for s in range(steps):
        bx, by = batch_at(s)
        state, m = step(state, {"x": bx, "y": by}, mask)
        losses.append(float(m["loss"]))
    finite_losses(losses, "lenet elastic_zo fp32")
    log(f"paper: lenet elastic_zo fp32 (zo_feat_cls1) losses {losses}")

    _, c, tail = next(x for x in INT8_LANES if x[0] == "zo_feat_cls1")
    step = jax.jit(make_int8_elastic_step(
        lenet.lenet5_forward_int8,
        partition_fn=lambda p: lenet.partition_at(p, c),
        tail_fcs=tail, lane=int8_lane_config(), loss_mode="int"))
    state = TrainState(lenet.init_lenet5_int8(jax.random.key(7)),
                       jnp.int32(0), jax.random.key_data(jax.random.key(13)))
    losses = []
    for s in range(steps):
        bx, by = batch_at(s)
        state, m = step(state, {"x": quant_from_float(bx), "y": by},
                        jnp.ones((1,), jnp.float32))
        losses.append(float(m["loss"]))
    finite_losses(losses, "lenet elastic_zo int8")
    log(f"paper: lenet elastic_zo int8 (zo_feat_cls1) losses {losses}")

    sys.path.insert(0, str(ROOT / "tests"))
    import golden_cases as gc
    want = json.loads(gc.FIXTURE.read_text())["canonical"][
        "int8_elastic_intloss"]
    got = gc.CANONICAL["int8_elastic_intloss"]()
    if got != want:
        # the init is the one float stage: name it for the comparison
        inits = {name: gc.digest_tree(fn(jax.random.key(7))) for name, fn in
                 (("init_lenet5", lenet.init_lenet5),
                  ("init_lenet5_int8", lenet.init_lenet5_int8))}
        raise SmokeFailure(f"int8 golden digest differs from the CPU "
                           f"fixture:\n got  {got}\n want {want}\n"
                           f" init digests on this device: {inits}")
    log(f"paper: int8 golden digest equals the CPU fixture "
        f"({got['params_sha256'][:16]}...)")


# ------------------------------------------------------------------ #
# qwen3-4b fine-tuning
# ------------------------------------------------------------------ #
def train_losses(argv) -> list:
    """Run ``launch/train.py`` in this process; its logged losses."""
    from repro.launch import train
    res = train.main(argv)
    return [loss for _, loss in res.history]


def phase_lm(layers: int = LM_LAYERS_ONE_CHIP, base=LM_ARGS) -> None:
    t0 = time.perf_counter()
    losses = train_losses(base + ["--layers", str(layers)])
    finite_losses(losses, "lm elastic_zo")
    from repro.configs import get_arch
    mem = peak_bytes()
    log(f"lm: {LM_ARCH} elastic_zo, {layers} of "
        f"{get_arch(LM_ARCH).num_layers} layers, {' '.join(base[2:])}: "
        f"losses {losses}; {mem}; "
        f"{time.perf_counter() - t0:.1f}s with compiles (set-up figures)")


# ------------------------------------------------------------------ #
# qwen3-4b paged serving
# ------------------------------------------------------------------ #
def serve_engine(cfg, n: int = SERVE_REQUESTS, prompt_len: int = PROMPT_LEN,
                 new_tokens: int = NEW_TOKENS, page_size: int = PAGE_SIZE):
    from repro.configs import ServeConfig
    from repro.serve import Engine
    total = prompt_len + new_tokens
    pages = -(-(total + 1) // page_size)
    return Engine(cfg, ServeConfig(page_size=page_size,
                                   num_pages=1 + n * pages,
                                   max_batch_slots=n, max_seq_len=total,
                                   max_new_tokens=new_tokens))


def serve_waves(eng, n: int = SERVE_REQUESTS, prompt_len: int = PROMPT_LEN,
                new_tokens: int = NEW_TOKENS) -> None:
    """A greedy wave and a top-k/top-p wave of ``n`` requests."""
    from repro.serve import SamplingParams
    vocab = eng.cfg.vocab_size
    prompts = np.random.default_rng(0).integers(
        0, vocab, (n, prompt_len)).tolist()
    for name, sp in (("greedy", SamplingParams()),
                     ("top-k/top-p", SamplingParams(temperature=0.8, top_k=50,
                                                    top_p=0.9, seed=1))):
        t0 = time.perf_counter()
        outs = eng.generate(prompts, sp, new_tokens)
        dt = time.perf_counter() - t0
        check(len(outs) == n and all(len(o) == new_tokens for o in outs),
              f"serve {name}: wrong output lengths")
        check(all(0 <= t < vocab for o in outs for t in o),
              f"serve {name}: token id out of range")
        log(f"serve: {name} wave, {n} x {prompt_len} prompt tokens -> "
            f"{new_tokens} new each, {dt:.1f}s with compiles (set-up "
            f"figure); first request {outs[0][:8]}...")


def decode_program_text(eng) -> str:
    """Compiled text of the engine's last sampled decode megastep."""
    d = eng._dev_plan
    horizon = eng.serve.page_size
    lowered = eng._fused.lower(
        eng.params, eng.caches, d["tokens"], d["page_table"], d["seq_lens"],
        d["mask"], d["temperature"], d["top_k"], d["top_p"], d["seed"],
        d["step"], horizon=horizon, greedy=False)
    return lowered.compile().as_text()


def paged_kernel_error(cfg, n: int = SERVE_REQUESTS,
                       page_size: int = PAGE_SIZE, pages: int = 35):
    """Max abs error of the Pallas paged decode step against the float32
    reference at highest precision, at cfg's attention widths. The pool
    writes must agree exactly."""
    from repro.kernels import ref
    from repro.kernels.paged_attn import paged_attention_step
    KVd, Dh = cfg.num_kv_heads, cfg.head_dim
    G = cfg.num_heads // KVd
    N = 1 + n * pages
    key = jax.random.key(3)
    ks = jax.random.split(key, 5)
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (n, KVd, G, Dh), bf)
    kn = jax.random.normal(ks[1], (n, KVd, Dh), bf)
    vn = jax.random.normal(ks[2], (n, KVd, Dh), bf)
    kp = jax.random.normal(ks[3], (N, page_size, KVd, Dh), bf)
    vp = jax.random.normal(ks[4], (N, page_size, KVd, Dh), bf)
    table = (1 + np.random.default_rng(1).permutation(N - 1)).reshape(
        n, pages).astype(np.int32)
    # first page, mid-page, a page boundary and the last slot
    seq_lens = np.asarray([5, pages // 2 * page_size + 7,
                           (pages - 1) * page_size, pages * page_size - 1][:n],
                          np.int32)
    pt, sl = jnp.asarray(table), jnp.asarray(seq_lens)
    scale = 1.0 / math.sqrt(Dh)
    o, kpo, vpo = paged_attention_step(q, kn, vn, kp, vp, pt, sl, scale=scale)
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        o_ref, kpr, vpr = ref.paged_attn_step_ref(
            q.astype(f32), kn.astype(f32), vn.astype(f32), kp.astype(f32),
            vp.astype(f32), pt, sl, scale=scale)
    check(bool(jnp.array_equal(kpo.astype(f32), kpr))
          and bool(jnp.array_equal(vpo.astype(f32), vpr)),
          "paged attention: pool writes differ from the reference")
    return float(jnp.max(jnp.abs(o.astype(f32) - o_ref)))


def phase_serve() -> None:
    from repro.configs import get_arch
    cfg = get_arch(LM_ARCH)
    eng = serve_engine(cfg)
    serve_waves(eng)
    text = decode_program_text(eng)
    n_kernels = text.count("tpu_custom_call")
    check(n_kernels > 0, "decode program holds no tpu_custom_call")
    log(f"serve: sampled decode program holds {n_kernels} tpu_custom_call "
        "sites (Pallas paged attention and top-k/top-p)")
    log(f"serve: {peak_bytes()}")
    del eng
    err = paged_kernel_error(cfg)
    check(err <= PAGED_ATOL, f"paged attention: max abs error {err} > "
          f"{PAGED_ATOL}")
    log(f"serve: Pallas paged attention vs float32 reference, "
        f"{LM_ARCH} widths: max abs error {err:.3e} (limit {PAGED_ATOL})")


# ------------------------------------------------------------------ #
# fleet
# ------------------------------------------------------------------ #
FLEET_CHAOS = ["--workers", "4", "--steps", "6", "--dropout", "0.2",
               "--max-delay", "1", "--deadline", "1", "--crash", "2:1:2"]


def phase_fleet() -> None:
    from repro.launch import fleet
    try:
        fleet.main(["--lane", "int8"] + FLEET_CHAOS)
    except SystemExit as e:
        raise SmokeFailure(f"int8 fleet self-check failed (exit {e.code})")
    log("fleet: int8 lane bit-exact with its coordinator and with the "
        "single-process reference")
    # The fp32 bitwise contract is stated for backends off the TPU
    # (kernels/ref.py): record the outcome, it does not gate the run.
    try:
        fleet.main(["--arch", LM_ARCH, "--smoke", "--lane", "elastic_zo",
                    "--seq", "32", "--batch", "2"] + FLEET_CHAOS)
        verdict = "passed"
    except SystemExit as e:
        verdict = f"FAILED (exit {e.code})"
    log(f"fleet: fp32 lane ({LM_ARCH} smoke) self-check {verdict} "
        "(recorded, not gating)")


# ------------------------------------------------------------------ #
# four chips
# ------------------------------------------------------------------ #
def placement(params) -> dict:
    """Parameter bytes held by each device, and the total."""
    per_dev: dict = {}
    total = 0
    for leaf in jax.tree.leaves(params):
        total += leaf.nbytes
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) \
                + sh.data.nbytes
    return {"per_device": per_dev, "total": total}


def bytes_in_use() -> dict:
    return {d.id: d.memory_stats()["bytes_in_use"] for d in jax.devices()}


def phase_four_chips() -> None:
    # the one-device run comes first, on chips nothing else has touched:
    # it needs nearly all of device 0
    base = LM_ARGS + ["--layers", str(LM_LAYERS_ONE_CHIP)]
    one = train_losses(base)
    sharded = train_losses(base + ["--mesh", MESH])
    finite_losses(one + sharded, "lm depth-cut comparison")
    diffs = [abs(a - b) / abs(a) for a, b in zip(one, sharded)]
    check(len(one) == len(sharded) and max(diffs) <= LOSS_RTOL,
          f"sharded losses {sharded} vs one device {one}: relative "
          f"differences {diffs} > {LOSS_RTOL}")
    log(f"four-chips: {LM_LAYERS_ONE_CHIP} layers, one device {one} vs "
        f"{MESH} {sharded}: max relative difference {max(diffs):.2e} "
        f"(limit {LOSS_RTOL})")

    from repro.launch import train
    res = train.main(LM_ARGS + ["--mesh", MESH])
    losses = [loss for _, loss in res.history]
    finite_losses(losses, "lm 2x2 full depth")
    place = placement(res.state.params)
    in_use = bytes_in_use()
    del res
    per_dev = place["per_device"]
    check(len(per_dev) == 4 and
          max(per_dev.values()) < 0.5 * place["total"],
          f"parameters not spread over four devices: {place}")
    from repro.configs import get_arch
    log(f"four-chips: {LM_ARCH} {get_arch(LM_ARCH).num_layers} layers on "
        f"{MESH}: losses {losses}; "
        f"parameter bytes per device {per_dev} of {place['total']}; "
        f"bytes in use per device after the run {in_use}")


# ------------------------------------------------------------------ #
def run_phases(phases) -> list:
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
            status = "passed"
        except (Exception, SystemExit):        # report, run the rest
            traceback.print_exc()
            status = "FAILED"
            failed.append(name)
        log(f"phase {name} {status} in {time.perf_counter() - t0:.1f}s")
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded fine-tuning on a 2x2 mesh")
    args = ap.parse_args(argv)
    chips = 4 if args.four_chips else 1
    try:
        dev = require_tpu(chips)
    except SmokeFailure as e:
        log(f"FAILED: {e}")
        return 1
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro.launch.cache import enable_compile_cache
    log(f"compilation cache: {enable_compile_cache()}")
    if args.four_chips:
        phases = [("four_chips", phase_four_chips)]
    else:
        phases = [("paper", phase_paper), ("lm", phase_lm),
                  ("serve", phase_serve), ("fleet", phase_fleet)]
    failed = run_phases(phases)
    if failed:
        log(f"FAILED phases: {', '.join(failed)}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
