"""Readings that set a cell's correctness limits, taken on the chip.

    python -m bench.calibrate --workload <cell> --seeds 1,2,3 \\
        [--control-seeds 4,5,6] [--seconds 15]
    python -m bench.calibrate --workload <serve cell> --seeds 1 \\
        --sweep 2,3,4,5 [--seconds 30]

For every seed, the numbers a run of the cell compares, from the program
as the cell runs it (the lower readings). For every control seed also
the control's: the reference computed in fp8 put in the program's place,
and for a training cell the fault of half the batch left out (the upper
readings). One process serves all seeds, so set-up is paid once. Prints
one JSON line per seed.

``--sweep`` instead offers the serving cell's traffic at each rate in
turn (requests per second) and reports how the queue behaved: the
highest rate whose backlog does not grow through the window is the knee
from which the cell's fixed rate is set.
"""
from __future__ import annotations

import argparse
import json

from . import run as run_mod


def _numbers(checks) -> dict:
    return {c.name: c.value for c in checks}


def train(ctx, seeds, control_seeds):
    from .drive.train import TrainCell
    cell = TrainCell(ctx)
    for seed in seeds:
        state, prog, _ = cell.start(seed)
        del state
        ref = cell.reference(seed)
        row = {"seed": seed, "program": _numbers(cell.compare(prog, ref)),
               "raw": {"loss": prog["loss"], "zo_g": prog["zo_g"],
                       "g_applied": prog["g_applied"],
                       "ref_loss": ref["loss"], "ref_lp": ref["lp"],
                       "ref_lm": ref["lm"], "ref_g": ref["g"]},
               "zo_leaves": {k: [d / e if e > 0 else None, d, e,
                                 *prog["zo_counts"][k]]
                             for k, (d, e) in prog["zo_step"].items()}}
        if seed in control_seeds:
            for name, kw in (("control_fp8", {"precision": "fp8"}),
                             ("fault_half_batch", {"half_batch": True})):
                low = cell.reference(seed, **kw)
                row[name] = _numbers(cell.compare(cell.as_program(low), ref))
                row[name + "_g"] = low["g"]
        print(json.dumps(row), flush=True)


def serve(ctx, seeds, control_seeds, seconds):
    from .drive.serve import ServeCell
    cell = ServeCell(ctx)
    for seed in seeds:
        cell.load(seed)
        res = cell.offer(cell.requests(seed, seconds), seconds)
        served = cell.served()
        cell.engine.params = None
        g = cell.gaps(seed, served, control=seed in control_seeds)
        row = {"seed": seed, "finished": len(served),
               "attempted": len(res["log"]), "program": g}
        print(json.dumps(row), flush=True)


def _ms(f, values):
    return 1e3 * f(values) if values else None


def sweep(ctx, seed, rates, seconds):
    import statistics
    from .drive.serve import ServeCell, p90
    cell = ServeCell(ctx)
    for rate in rates:
        cell.tr = dict(cell.tr, rate_per_s=rate)
        cell.load(seed)
        backlog = []
        engine = cell.engine
        reqs = cell.requests(seed, seconds)
        res = cell.offer(reqs, seconds, on_close=lambda _: backlog.append(
            len(engine.sched.waiting)))
        log = [e for e in res["log"] if e["first"] is not None]
        half = [e["first"] - e["due"] for e in log if e["due"] < seconds / 2]
        late = [e["first"] - e["due"] for e in log if e["due"] >= seconds / 2]
        toks = sum(e["n_window"] for e in res["log"])
        print(json.dumps({
            "rate": rate, "requests": len(reqs), "finished": len(log),
            "waiting_at_close": backlog[0],
            "tokens_per_s_in_window": toks / res["window_s"],
            "ttft_p50_first_half_ms": _ms(statistics.median, half),
            "ttft_p50_second_half_ms": _ms(statistics.median, late),
            "ttft_p90_ms": 1e3 * p90(half + late),
            "tpot_p90_ms": 1e3 * p90([(e["last"] - e["first"]) / (e["n"] - 1)
                                      for e in log if e["n"] > 1]),
        }), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--sweep", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    ctx = run_mod.prepare(args.workload)
    if args.sweep:
        sweep(ctx, seeds[0], [float(r) for r in args.sweep.split(",")],
              args.seconds)
    elif ctx["workload"]["drive"] == "train":
        train(ctx, seeds, control)
    else:
        serve(ctx, seeds, control, args.seconds)


if __name__ == "__main__":
    main()
