"""Benchmark driver: one section per paper table/figure + the roofline.

``PYTHONPATH=src python -m benchmarks.run [--fast] [--section NAME]``

Each section prints a human-readable '#'-prefixed table and returns a
flat metrics dict; the driver merges them into ``BENCH_paper.json`` on
the standardized bench_util schema ({name, config, metrics}) so the
paper-reproduction trajectory is diffable across PRs like every other
benchmark (BENCH_fleet.json, BENCH_serve.json).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

from .bench_util import write_bench


def section_accuracy(fast: bool):
    from . import paper_tables as pt
    metrics = {}
    steps = 150 if fast else 800
    t0 = time.perf_counter()
    res = pt.lenet_lanes(steps=steps)
    dt = (time.perf_counter() - t0) * 1e6 / steps
    order = ["full_zo", "zo_feat_cls2", "zo_feat_cls1", "full_bp"]
    accs = {k: res[k][0] for k in order}
    print("# Table1(FP32 glyphs): " +
          " ".join(f"{k}={accs[k]*100:.1f}%" for k in order))
    metrics["table1_fp32_lenet_us_per_step"] = dt
    metrics.update({f"table1_fp32_lenet_acc_{k}": accs[k] for k in order})

    t0 = time.perf_counter()
    res8 = pt.lenet_int8_lanes(steps=steps, loss_mode="int")
    dt8 = (time.perf_counter() - t0) * 1e6 / steps
    accs8 = {k: res8[k][0] for k in res8}
    print("# Table1(INT8* glyphs): " +
          " ".join(f"{k}={v*100:.1f}%" for k, v in accs8.items()))
    metrics["table1_int8star_lenet_us_per_step"] = dt8
    metrics.update({f"table1_int8star_lenet_acc_{k}": v
                    for k, v in accs8.items()})

    psteps = 100 if fast else 400
    t0 = time.perf_counter()
    resp = pt.pointnet_lanes(steps=psteps)
    dtp = (time.perf_counter() - t0) * 1e6 / psteps
    print("# Table1(PointNet clouds): " +
          " ".join(f"{k}={v[0]*100:.1f}%" for k, v in resp.items()))
    metrics["table1_pointnet_us_per_step"] = dtp
    metrics.update({f"table1_pointnet_acc_{k}": v[0]
                    for k, v in resp.items()})
    return metrics


def section_finetune(fast: bool):
    from . import paper_tables as pt
    import jax
    import jax.numpy as jnp
    from repro.models import lenet
    from repro.configs import LaneConfig
    from repro.core.elastic import TrainState, make_elastic_step
    from repro.data.synthetic import glyphs
    metrics = {}
    steps = 100 if fast else 400
    # pretrain with BP on upright glyphs (paper: 1-100 epochs of BP)
    params = lenet.init_lenet5(jax.random.key(7))
    lane = LaneConfig(lane="full_bp", learning_rate=0.05)
    step = jax.jit(make_elastic_step(lenet.lenet5_loss, lane))
    state = TrainState(params, jnp.int32(0),
                       jax.random.key_data(jax.random.key(1)))
    xs, ys = glyphs(2048, seed=0)
    for s in range(steps):
        i0 = (s * 32) % 2048
        state, _ = step(state, {"x": jnp.asarray(xs[i0:i0 + 32]),
                                "y": jnp.asarray(ys[i0:i0 + 32])},
                        jnp.ones((1,), jnp.float32))
    pre = state.params
    for deg in (30, 45):
        xs_r, ys_r = glyphs(512, seed=5, rotate_deg=deg, start=20_000)
        logits, _ = lenet.lenet5_forward(pre, jnp.asarray(xs_r))
        acc0 = float(jnp.mean((jnp.argmax(logits, -1) == jnp.asarray(ys_r))
                              .astype(jnp.float32)))
        t0 = time.perf_counter()
        res = pt.lenet_lanes(steps=steps, rotate=deg, init_params=pre,
                             zo_lr=5e-3)
        dt = (time.perf_counter() - t0) * 1e6 / steps
        print(f"# Table2(rot{deg}): before={acc0*100:.1f}% " +
              " ".join(f"{k}={v[0]*100:.1f}%" for k, v in res.items()))
        metrics[f"table2_rot{deg}_us_per_step"] = dt
        metrics[f"table2_rot{deg}_acc_before"] = acc0
        metrics.update({f"table2_rot{deg}_acc_{k}": v[0]
                        for k, v in res.items()})
    return metrics


# The structured reconciliation table section_memory builds for
# BENCH_paper.json's "memory" section (write_bench merges it with the
# recorder's tagged-ledger snapshot). Module-level because sections
# return flat scalar metrics only.
MEMORY_DOC: dict = {}


def section_memory(_fast: bool):
    from . import paper_tables as pt
    metrics = {}
    for b in (32, 256):
        t = pt.lenet_memory_table(b)
        full_bp = t["full_bp"]["fp32_bytes"]
        fz = t["full_zo"]["fp32_bytes"]
        print(f"# Fig4/5 (LeNet B={b}): " + " ".join(
            f"{k}: fp32={v['fp32_bytes']/1e6:.2f}MB "
            f"int8={v['int8_bytes']/1e6:.2f}MB" for k, v in t.items()))
        metrics[f"memory_lenet_b{b}_bp_over_zo"] = full_bp / fz
        metrics[f"memory_lenet_b{b}_cls1_overhead_pct"] = \
            (t["zo_feat_cls1"]["fp32_bytes"] - fz) / fz * 100
        metrics[f"memory_lenet_b{b}_int8_saving"] = \
            fz / t["full_zo"]["int8_bytes"]
        metrics[f"memory_lenet_b{b}_int8_saving_reused"] = \
            fz / t["full_zo"]["int8_reused_bytes"]
    p = pt.pointnet_memory_table(32)
    print(f"# Fig6 (PointNet B=32): full_bp={p['full_bp']['fp32_bytes']/1e6:.1f}MB "
          f"full_zo={p['full_zo']['fp32_bytes']/1e6:.1f}MB "
          f"cls1={p['zo_feat_cls1']['fp32_bytes']/1e6:.1f}MB")
    metrics["memory_pointnet_b32_bp_over_zo"] = \
        p["full_bp"]["fp32_bytes"] / p["full_zo"]["fp32_bytes"]

    # ---- MEASURED: XLA buffer assignment per lane, reconciled -------- #
    mb = 32
    analytic = pt.lenet_memory_table(mb)
    meas = pt.lenet_measured_memory(mb)
    MEMORY_DOC.clear()
    MEMORY_DOC.update({"model": "lenet5", "batch": mb,
                       "instrument": "xla_buffer_assignment "
                                     "(Compiled.memory_analysis)",
                       "lanes": {}, "int8_lanes": {}})
    for k, fp in meas.items():
        a = analytic[k]["fp32_bytes"]
        resid = fp["peak_bytes"] - a
        metrics[f"memory_measured_lenet_b{mb}_{k}_peak_bytes"] = \
            fp["peak_bytes"]
        metrics[f"memory_resid_lenet_b{mb}_{k}_bytes"] = resid
        MEMORY_DOC["lanes"][k] = {**fp, "analytic_bytes": a,
                                  "residual_bytes": resid}
    metrics[f"memory_measured_lenet_b{mb}_bp_over_zo"] = \
        meas["full_bp"]["peak_bytes"] / meas["full_zo"]["peak_bytes"]
    metrics[f"memory_measured_lenet_b{mb}_cls1_overhead_pct"] = \
        (meas["zo_feat_cls1"]["peak_bytes"] - meas["full_zo"]["peak_bytes"]) \
        / meas["full_zo"]["peak_bytes"] * 100
    print(f"# Fig4/5 measured (LeNet B={mb}, XLA): " + " ".join(
        f"{k}={v['peak_bytes']/1e6:.2f}MB" for k, v in meas.items())
        + f"  bp_over_zo={metrics[f'memory_measured_lenet_b{mb}_bp_over_zo']:.2f}")

    meas8 = pt.lenet_int8_measured_memory(mb)
    for k, fp in meas8.items():
        a = analytic[k]["int8_reused_bytes"]
        resid = fp["peak_bytes"] - a
        metrics[f"memory_measured_int8_lenet_b{mb}_{k}_peak_bytes"] = \
            fp["peak_bytes"]
        metrics[f"memory_resid_int8_lenet_b{mb}_{k}_bytes"] = resid
        MEMORY_DOC["int8_lanes"][k] = {
            **fp, "analytic_bytes": a,
            "analytic_noreuse_bytes": analytic[k]["int8_bytes"],
            "residual_bytes": resid}
    # measured fp32/int8 ratio — honest: the int8 *simulation* upcasts
    # to int32 in XLA, so this sits below 1.0 (the paper's MCU 1.46-1.60x
    # lives in memory_lenet_b*_int8_saving_reused above)
    metrics[f"memory_measured_lenet_b{mb}_int8_ratio"] = \
        meas["full_zo"]["peak_bytes"] / meas8["full_zo"]["peak_bytes"]
    print(f"# Fig4/5 measured (LeNet B={mb}, INT8 sim): " + " ".join(
        f"{k}={v['peak_bytes']/1e6:.2f}MB" for k, v in meas8.items()))
    return metrics


def section_steptime(fast: bool):
    from . import paper_tables as pt
    bd = pt.steptime_breakdown(iters=5 if fast else 20)
    print("# Fig7 (step-time, this host): " +
          " ".join(f"{k}={v:.0f}us" for k, v in bd.items()))
    metrics = dict(bd)
    fp32_total = bd["fp32_forward_us"] + bd["fp32_perturb_us"] \
        + bd["fp32_update_us"] + bd["fp32_bp_tail_us"]
    metrics["steptime_fp32_total_us"] = fp32_total
    metrics["steptime_fp32_fwd_share"] = bd["fp32_forward_us"] / fp32_total
    metrics["steptime_int8_fwdperturb_us"] = \
        bd["int8_forward_us"] + bd["int8_perturb_us"]
    return metrics


def section_signagree(_fast: bool):
    from . import paper_tables as pt
    t0 = time.perf_counter()
    rate, total = pt.sign_agreement()
    dt = (time.perf_counter() - t0) * 1e6 / max(total, 1)
    print(f"# §4.3 sign agreement: {rate*100:.1f}% over {total} trials "
          "(paper: ~95%)")
    return {"int_loss_sign_agreement": rate,
            "int_loss_sign_trials": total,
            "int_loss_sign_us_per_trial": dt}


def section_roofline(_fast: bool):
    from . import roofline as rl
    rows = rl.full_table("single")
    ok = [r for r in rows if r.get("status") == "ok"]
    print("# Roofline (single-pod 16x16, per-device):")
    print("\n".join("# " + l for l in rl.format_table(rows).splitlines()))
    metrics = {}
    for r in ok:
        key = f"roofline_{r['arch']}_{r['shape']}"
        metrics[f"{key}_us"] = max(r["t_compute_s"], r["t_memory_s"],
                                   r["t_collective_s"]) * 1e6
        metrics[f"{key}_fraction"] = r["roofline_fraction"]
        metrics[f"{key}_useful_flops_ratio"] = r["useful_flops_ratio"]
    out = Path(__file__).resolve().parent.parent / "results" / "roofline_single.json"
    out.parent.mkdir(exist_ok=True)
    out.write_text(json.dumps(rows, indent=1, default=str))
    return metrics


SECTIONS = {
    "signagree": section_signagree,
    "memory": section_memory,
    "roofline": section_roofline,
    "steptime": section_steptime,
    "accuracy": section_accuracy,
    "finetune": section_finetune,
}


def main() -> None:
    from repro import obs
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--section", choices=sorted(SECTIONS), action="append")
    ap.add_argument("--out", default="")
    obs.add_observability_args(ap)
    args = ap.parse_args()
    obs.configure_from_args(args)
    if not obs.get().enabled:
        obs.install()      # BENCH_paper.json always carries timings
    ran = []
    failed = []
    metrics = {}
    rec = obs.get()
    for name, fn in SECTIONS.items():
        if args.section and name not in args.section:
            continue
        t0 = time.perf_counter()
        try:
            with rec.span(f"bench/{name}", track="main"):
                metrics.update(fn(args.fast))
            ran.append(name)
        except Exception:  # noqa: BLE001 — report, run the rest, exit 1
            traceback.print_exc()
            print(f"# [{name}] ERROR")
            failed.append(name)
        print(f"# [{name}] done in {time.perf_counter()-t0:.1f}s")
    if failed:
        sys.exit(f"# sections failed: {', '.join(failed)}; "
                 "BENCH_paper.json not written")
    obs.memory.sample()        # final tagged-vs-jax reconciliation
    write_bench("paper", {"fast": args.fast, "sections": ",".join(ran)},
                metrics, out=args.out or None,
                memory=MEMORY_DOC or None)
    obs.write_outputs(args)


if __name__ == '__main__':
    main()
