"""What every cell's driver shares: the files found by name, the model
configuration, the device, the compile counter and the checks."""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


def load(kind: str, name: str) -> dict:
    """bench/<kind>/<name>.json."""
    with open(BENCH / kind / f"{name}.json") as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """bench/<kind>/<name>.py. Metric readers are named after their
    metrics, which may hold dots, so they are imported by path; drivers
    and generators are modules of the package."""
    if name.isidentifier():
        return importlib.import_module(f"bench.{kind}.{name}")
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def use_program() -> None:
    """Put the program under test (``src``) on the import path."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def model_config(c: dict):
    """The program's ModelConfig for a configuration file, every size
    taken from the file."""
    use_program()
    from repro.configs.archs import ARCHS
    if c.get("tie_word_embeddings"):
        raise ValueError("the program holds a separate output head: a "
                         "configuration that ties it cannot run")
    return dataclasses.replace(
        ARCHS[c["program_arch"]],
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        qk_norm=bool(c["qk_norm"]), tie_embeddings=False,
        dtype=c["torch_dtype"])


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip; an unknown kind is an error."""
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json")
    return table["devices"][device_kind]


class CompileCounter:
    """Counts programs lowered (compiled or loaded from the persistent
    cache) while armed, from JAX's own monitoring events."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _dur, **_kw):
        if self.armed and name == self.EVENT:
            self.count += 1


def peak_bytes() -> int:
    """Peak bytes in use on the chip so far (0 where the backend keeps no
    statistics, as the CPU in tests)."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def temp_bytes(compiled) -> int:
    """Bytes of scratch a compiled program holds while it runs, by the
    compiler's buffer assignment. The TPU runtime keeps them apart from
    the allocator whose peak ``memory_stats`` reports, so a step's peak
    on the chip is that peak and these together."""
    ma = compiled.memory_analysis()
    return int(getattr(ma, "temp_size_in_bytes", 0) or 0) if ma else 0


def now() -> float:
    return time.perf_counter()


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def worst_leaf_gap(prog: dict, ref: dict) -> float:
    """Largest |prog - ref| over leaves, each against the larger of the
    reference's norm of that leaf and of the median leaf. Leaves whose
    reference norm is under a thousandth of the median are left out
    (they move by rounding alone)."""
    vals = sorted(ref.values())
    med = vals[len(vals) // 2]
    gap = 0.0
    for k, r in ref.items():
        if r < 1e-3 * med:
            continue
        gap = max(gap, abs(prog[k] - r) / max(r, med))
    return gap
