"""Run one benchmark cell on the chip and print its result line.

    python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell ``bench/workloads/<cell>.json`` names its configuration, its
traffic and the driver (``bench/drive/<drive>.py``) that runs it. Set-up
(weights from the seed, every program the cell can reach compiled or
loaded from the persistent cache) is timed as ``setup_s``; then the
window runs for ``--seconds``; then the outputs are compared with the
float32 reference. With ``--trace 0`` the result carries the cell's
end-to-end metrics; with ``--trace 1`` the window is traced and the
result carries its per-layer metrics, read by ``bench/metrics/<name>.py``.

Exits 1 without printing a result when JAX finds no TPU, or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import math
import sys

from . import common


def _chips(cell: str) -> int:
    spec = json.loads((common.ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        if w["name"] == cell:
            return int(w["chips"])
    raise KeyError(f"{cell} is not a workload of BENCHMARK.json")


def metric_specs(cell: str, traced: bool) -> list:
    """The metrics this cell reports, as BENCHMARK.json lists them."""
    spec = json.loads((common.ROOT / "BENCHMARK.json").read_text())
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not traced:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in moved
                             else [])]


def device_info(jax, chips: int) -> dict:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU (JAX found {devs[0].platform})")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def prepare(cell: str, require_tpu: bool = True) -> dict:
    """Check the device, turn the compile cache on and load the cell's
    files; returns the context a driver runs from."""
    workload = common.load("workloads", cell)
    chips = _chips(cell)
    common.use_program()
    import jax
    if require_tpu:
        device = device_info(jax, chips)
    else:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": chips}
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return {"cell": cell, "workload": workload,
            "config": common.load("configs", workload["config"]),
            "traffic": common.load("traffic", workload["traffic"]),
            "device": device}


def main(argv=None, require_tpu: bool = True) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    ctx = prepare(args.workload, require_tpu)
    ctx.update(seed=args.seed, seconds=args.seconds, trace=bool(args.trace))
    cell, workload, device = ctx["cell"], ctx["workload"], ctx["device"]
    driver = common.load_module("drive", workload["drive"])
    out = driver.run(ctx)

    if ctx["trace"]:
        out["layer"]["peaks"] = common.peaks(device["kind"])
    metrics = {}
    for m in metric_specs(cell, ctx["trace"]):
        if ctx["trace"]:
            reader = common.load_module("metrics", m["name"])
            v = reader.read(out["layer"])
        else:
            v = out["end_to_end"].get(m["name"])
        if v is not None and math.isfinite(v):
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    if ctx["trace"]:
        device["busy_s"] = out["layer"]["trace"]["busy_s"]
        device["window_s"] = out["layer"]["trace"]["window_s"]
    checks = out["checks"]
    correct = verdict(out)
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if ctx["trace"]:
        result["breakdown"] = out["layer"]["trace"]["breakdown"]
    result["checks"] = {c.name: {"value": _finite(c.value), "limit": c.limit}
                        for c in checks}
    for c in checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    print(json.dumps(result, allow_nan=False), flush=True)
    return result


def verdict(out: dict) -> bool:
    """A run is correct when it compared something, every number is
    within its limit and no step or request failed."""
    return bool(out["checks"]) and all(c.ok for c in out["checks"]) \
        and out["failed"] == 0


def _finite(x: float):
    """A number as JSON can hold it (a non-finite reading prints null)."""
    return float(x) if math.isfinite(x) else None


if __name__ == "__main__":
    main()
