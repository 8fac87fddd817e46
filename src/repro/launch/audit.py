import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
"""Collective audit: the §Perf microscope.

Lowers one (arch x shape x strategy) cell and prints the top collectives by
per-device bytes (trip-weighted), so each hillclimb iteration names the op
it intends to kill before changing anything.

  PYTHONPATH=src python -m repro.launch.audit --arch llama3-8b \
      --shape train_4k --strategy tp [--top 15]
"""
import argparse

from ..configs import LaneConfig, get_arch, get_shape
from .dryrun import lower_cell
from .hlo_analysis import collective_bytes
from .cache import enable_compile_cache
from .mesh import make_production_mesh


def audit(arch: str, shape_name: str, strategy: str = "tp", top: int = 15,
          multi_pod: bool = False, lane: str = "elastic_zo"):
    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    _, compiled = lower_cell(cfg, shape, mesh, LaneConfig(lane=lane),
                             strategy=strategy)
    total, ops = collective_bytes(compiled.as_text())
    ops.sort(key=lambda o: -o.bytes_moved)
    print(f"total per-device collective bytes: {total:.3e} "
          f"({total/50e9*1e3:.1f} ms @50GB/s)")
    for o in ops[:top]:
        print(f"  {o.bytes_moved:10.3e}B  {o.kind:18s} group={o.group:4d} "
              f"trips={o.trips:4d}  in {o.computation[:60]}")
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    print(f"flops/dev={ca.get('flops', 0):.3e}  "
          f"bytes/dev={ca.get('bytes accessed', 0):.3e}  "
          f"temp={ma.temp_size_in_bytes/1e9:.2f}GB  "
          f"args={ma.argument_size_in_bytes/1e9:.2f}GB")
    return total, ops


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--strategy", default="tp")
    ap.add_argument("--lane", default="elastic_zo")
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--multi", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache()
    audit(args.arch, args.shape, args.strategy, args.top, args.multi,
          args.lane)


if __name__ == "__main__":
    main()
