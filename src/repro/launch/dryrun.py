import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces (results/dryrun/<arch>__<shape>__<mesh>.json):
  - memory_analysis: per-device argument/output/temp bytes (fits-in-HBM proof)
  - cost_analysis at full depth, plus depth-2/depth-4 variants for the
    while-body cost extrapolation (docs/design.md §7)
  - per-device collective bytes parsed from the post-SPMD HLO
    (trip-count-weighted; launch/hlo_analysis.py)

The FIRST two lines of this file set XLA_FLAGS before any jax import so the
CPU platform exposes 512 placeholder devices; smoke tests and benchmarks
never import this module and keep seeing 1 device.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh single|multi|both]
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
"""
import argparse
import dataclasses
import json
import sys
import traceback
from pathlib import Path

import jax

from .. import obs
from ..configs import LaneConfig, cell_matrix, get_arch, get_shape
from ..core import api
from ..core.elastic import TrainState
from ..sharding.params import cache_shardings, param_shardings
from ..sharding.rules import ShardingRules
from .hlo_analysis import collective_bytes, summarize
from .cache import enable_compile_cache
from .mesh import make_production_mesh

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"

# TPU v5e hardware model (roofline constants; see docs/design.md §7)
PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # B/s
ICI_BW = 50e9                # B/s per chip


def depth_variant(cfg, depth_periods: int):
    plen = len(cfg.pattern)
    kw = dict(num_layers=depth_periods * plen)
    if cfg.encoder_layers:
        kw["encoder_layers"] = depth_periods
    return dataclasses.replace(cfg, **kw)


def build_cell(cfg, shape, mesh, lane, scan_unroll=False, strategy="tp"):
    rules = ShardingRules(mesh, cfg, shape, strategy=strategy)
    model = api.build(cfg, shape, lane, rules, scan_unroll=scan_unroll)
    specs = model.input_specs()
    bshard = api.batch_shardings(specs, rules)
    aparams = model.abstract_params()
    pshard = param_shardings(aparams, rules)
    return model, rules, specs, bshard, aparams, pshard


def lower_cell(cfg, shape, mesh, lane, scan_unroll=False, strategy="tp"):
    """Returns (lowered, compiled).  Never allocates device memory."""
    model, rules, specs, bshard, aparams, pshard = build_cell(
        cfg, shape, mesh, lane, scan_unroll=scan_unroll, strategy=strategy)
    scalar = None if mesh is None else jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec())

    if shape.kind == "train":
        state_spec = model.abstract_state()
        state_shard = TrainState(pshard, scalar, scalar)
        pm = specs.pop("probe_mask")
        bshard = {k: v for k, v in bshard.items() if k != "probe_mask"}
        fn = jax.jit(model.train_step,
                     in_shardings=(state_shard, bshard, scalar),
                     donate_argnums=(0,))
        lowered = fn.lower(state_spec,
                           {k: v for k, v in specs.items()}, pm)
    elif shape.kind == "prefill":
        fn = jax.jit(model.prefill_step,
                     in_shardings=(pshard, bshard))
        lowered = fn.lower(aparams, specs)
    else:  # decode
        acaches = model.abstract_caches()
        cshard = cache_shardings(acaches, model.rules)
        fn = jax.jit(model.decode_step,
                     in_shardings=(pshard, bshard["tokens"], cshard, scalar),
                     donate_argnums=(2,))
        lowered = fn.lower(aparams, specs["tokens"], acaches,
                           specs["cache_len"])
    compiled = lowered.compile()
    return lowered, compiled


def analyze(compiled):
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    mem = {}
    if ma is not None:
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            mem[f] = getattr(ma, f, None)
    hlo = compiled.as_text()
    coll_total, ops = collective_bytes(hlo)
    return {
        "flops": ca.get("flops", 0.0),
        "bytes_accessed": ca.get("bytes accessed", 0.0),
        "transcendentals": ca.get("transcendentals", 0.0),
        "memory": mem,
        "collective_bytes": coll_total,
        "collectives": summarize(ops),
    }


def add_depth_extrapolation(rec, cfg, shape, mesh, lane, strategy="tp"):
    """Depth-2/4 *unrolled* compiles -> exact per-period cost slope.

    The full-depth module keeps lax.scan (memory/collective truth), but its
    cost_analysis counts the body once; the unrolled shallow variants give
    cost(P) = base + P * per_period exactly (docs/design.md §7).
    """
    for d in (2, 4):
        dc = depth_variant(cfg, d)
        _, comp_d = lower_cell(dc, shape, mesh, lane, scan_unroll=True,
                               strategy=strategy)
        rec[f"depth{d}"] = analyze(comp_d)
        del comp_d
    P = cfg.num_periods
    f2, f4 = rec["depth2"]["flops"], rec["depth4"]["flops"]
    b2, b4 = (rec["depth2"]["bytes_accessed"],
              rec["depth4"]["bytes_accessed"])
    rec["extrapolated"] = {
        "flops": f2 + (f4 - f2) / 2.0 * (P - 2),
        "bytes_accessed": b2 + (b4 - b2) / 2.0 * (P - 2),
        "periods": P,
        "per_period_flops": (f4 - f2) / 2.0,
    }


def update_depth(arch: str, shape_name: str, lane: LaneConfig, out_dir: Path):
    """Recompute only the depth variants of an existing cell JSON."""
    out = out_dir / f"{arch}__{shape_name}__single.json"
    rec = json.loads(out.read_text())
    if rec.get("status") != "ok":
        return rec
    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=False)
    t0 = obs.monotonic()
    try:
        add_depth_extrapolation(rec, cfg, shape, mesh, lane)
        rec["depth_mode"] = "unrolled"
    except Exception as e:  # noqa: BLE001
        rec["depth_error"] = f"{type(e).__name__}: {e}"
    rec["depth_elapsed_s"] = round(obs.monotonic() - t0, 1)
    out.write_text(json.dumps(rec, indent=1))
    return rec


def run_cell(arch: str, shape_name: str, mesh_kind: str, lane: LaneConfig,
             out_dir: Path, force=False, depth_variants=True,
             strategy="tp"):
    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    suffix = "" if strategy == "tp" else f"+{strategy}"
    out = out_dir / f"{arch}__{shape_name}__{mesh_kind}{suffix}.json"
    if out.exists() and not force:
        return json.loads(out.read_text())
    t0 = obs.monotonic()
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "strategy": strategy,
           "mesh_shape": dict(zip(mesh.axis_names,
                                  (int(s) for s in mesh.devices.shape))),
           "lane": lane.lane, "status": "ok"}
    try:
        lowered, compiled = lower_cell(cfg, shape, mesh, lane,
                                       strategy=strategy)
        rec["full"] = analyze(compiled)
        rules = ShardingRules(mesh, cfg, shape, strategy=strategy)
        rec["attn_plan"] = dataclasses.asdict(rules.attn)
        rec["moe_plan"] = rules.moe
        del lowered, compiled
        if depth_variants and mesh_kind == "single":
            add_depth_extrapolation(rec, cfg, shape, mesh, lane,
                                    strategy=strategy)
    except Exception as e:  # noqa: BLE001 - record the failure and move on
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc(limit=20)
    rec["elapsed_s"] = round(obs.monotonic() - t0, 1)
    out_dir.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(rec, indent=1))
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--lane", default="elastic_zo")
    ap.add_argument("--no-depth-variants", action="store_true")
    ap.add_argument("--strategy", default="tp",
                    choices=["tp", "fsdp", "serve"])
    ap.add_argument("--update-depth", action="store_true",
                    help="recompute only depth variants of existing cells")
    ap.add_argument("--out", default=str(RESULTS))
    args = ap.parse_args(argv)
    enable_compile_cache()

    lane = LaneConfig(lane=args.lane)
    out_dir = Path(args.out)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    cells = []
    if args.all:
        for a, s, run, why in cell_matrix():
            if run:
                cells.append((a, s))
            else:
                print(f"SKIP {a} x {s}: {why}")
    else:
        if not (args.arch and args.shape):
            raise SystemExit("--arch/--shape or --all")
        cells = [(args.arch, args.shape)]

    # small cells first for early signal
    def cell_cost(c):
        cfg, sh = get_arch(c[0]), get_shape(c[1])
        return cfg.param_count() * (sh.seq_len if sh.kind != "decode" else 1)
    cells.sort(key=cell_cost)

    failures = 0
    if args.update_depth:
        for a, s in cells:
            rec = update_depth(a, s, lane, out_dir)
            ex = rec.get("extrapolated", {})
            err = rec.get("depth_error", "")
            print(f"DEPTH {a} x {s}: flops={ex.get('flops', 0):.3e} "
                  f"per_period={ex.get('per_period_flops', 0):.3e} "
                  f"{err} ({rec.get('depth_elapsed_s')}s)", flush=True)
            failures += bool(err)
        print(f"\ndone; failures={failures}")
        return 1 if failures else 0
    for a, s in cells:
        for mk in meshes:
            rec = run_cell(a, s, mk, lane, out_dir, force=args.force,
                           depth_variants=not args.no_depth_variants,
                           strategy=args.strategy)
            st = rec["status"]
            if st != "ok":
                failures += 1
                print(f"FAIL {a} x {s} x {mk}: {rec.get('error')}",
                      flush=True)
            else:
                f = rec.get("extrapolated", rec["full"]).get("flops", 0)
                cb = rec["full"]["collective_bytes"]
                tmp = rec["full"]["memory"].get("temp_size_in_bytes")
                print(f"OK   {a} x {s} x {mk}: flops/dev={f:.3e} "
                      f"coll/dev={cb:.3e}B temp={tmp} "
                      f"({rec['elapsed_s']}s)", flush=True)
    print(f"\ndone; failures={failures}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
