"""Golden-digest harness for the update-engine refactor contract.

The engine refactor (core/engine.py) must preserve fp32 single-process
training bitwise. This module defines the pinned cases and the digest
function; the fixture ``tests/golden/engine_steps.json`` stores, per
case, the sha256 of the post-step parameter bytes and the per-step loss
floats (as exact hex) captured on the **pre-refactor** implementation.

Every section was re-pinned on jax 0.9.0: since jax 0.5 the flag
``jax_threefry_partitionable`` defaults to True, which changes every
``jax.random`` stream (the LeNet init and the probe keys alike). Flipping
the flag back reproduces the older fixture exactly, so the engine itself
did not change. The int8 cases were pinned again when
``init_lenet5_int8`` moved to an integer-only init, which gives the same
bits on the CPU and the TPU.

Fixture sections:
  * ``preserved``  — cases whose behavior the refactor must not change
    at all: the three fp32 lanes at n_probes=1 (where per-probe and
    accumulate-then-cast application coincide).
  * ``canonical``  — multi-probe fp32 cases pinning the engine's
    canonical accumulate-then-cast order (docs/design.md §10). These
    digests are generated on the engine implementation itself and guard
    *future* refactors.

Float digests are platform-pinned (XLA CPU codegen varies across ISAs /
jax versions), so the fixture also stores a ``canary``: the digest of a
step-free computation (init + forward loss) that the refactor does not
touch. If the canary mismatches, the environment's baseline numerics
differ and the float cases are skipped; if the canary matches but a case
digest doesn't, the refactor changed semantics. Integer cases assert
unconditionally.

Regenerate (section-selective; run from the repo root):
    PYTHONPATH=src python tests/golden_cases.py preserved
    PYTHONPATH=src python tests/golden_cases.py canonical
"""
from __future__ import annotations

import hashlib
import json
import sys
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp

FIXTURE = Path(__file__).parent / "golden" / "engine_steps.json"
STEPS = 3
BATCH = 16


def digest_tree(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in sorted(jax.tree_util.tree_flatten_with_path(tree)[0],
                             key=lambda kv: jax.tree_util.keystr(kv[0])):
        h.update(jax.tree_util.keystr(path).encode())
        a = np.asarray(jax.device_get(leaf))
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _glyph_batch():
    from repro.data.synthetic import glyphs
    xs, ys = glyphs(BATCH, seed=0)
    return jnp.asarray(xs), jnp.asarray(ys)


def run_canary() -> str:
    """Init + forward + loss only — independent of the step construction."""
    from repro.models import lenet
    params = lenet.init_lenet5(jax.random.key(7))
    bx, by = _glyph_batch()
    loss = lenet.lenet5_loss(params, {"x": bx, "y": by})
    return digest_tree({"params": params, "loss": loss})


def run_fp32_case(lane_name: str, n_probes: int, mask) -> dict:
    from repro.configs import LaneConfig
    from repro.core.elastic import TrainState, make_elastic_step
    from repro.models import lenet
    lane = LaneConfig(lane=lane_name, learning_rate=0.05,
                      tail_learning_rate=0.05 if lane_name == "elastic_zo"
                      else None,
                      zo_eps=1e-2, zo_num_probes=n_probes,
                      lr_decay_factor=0.5, lr_decay_every=2)
    part = (lambda p: lenet.partition_at(p, 4)) \
        if lane_name == "elastic_zo" else None
    step = jax.jit(make_elastic_step(lenet.lenet5_loss, lane,
                                     partition_fn=part))
    params = lenet.init_lenet5(jax.random.key(7))
    state = TrainState(params, jnp.int32(0),
                       jax.random.key_data(jax.random.key(11)))
    bx, by = _glyph_batch()
    pm = jnp.asarray(mask, jnp.float32)
    losses = []
    for _ in range(STEPS):
        state, m = step(state, {"x": bx, "y": by}, pm)
        losses.append(float(m["loss"]))
    return {"params_sha256": digest_tree(state.params),
            "loss_hex": [np.float32(v).tobytes().hex() for v in losses]}


def run_int8_case(loss_mode: str) -> dict:
    from repro.configs import LaneConfig
    from repro.core.elastic import TrainState
    from repro.core.elastic_int8 import make_int8_elastic_step
    from repro.core.int8 import quant_from_float
    from repro.models import lenet
    lane = LaneConfig(lane="elastic_zo_int8", int8_r_max=3,
                      int8_p_zero=0.33, int8_b_zo=1, int8_b_bp=5)
    step = jax.jit(make_int8_elastic_step(
        lenet.lenet5_forward_int8,
        partition_fn=lambda p: lenet.partition_at(p, 4),
        tail_fcs=[("fc3", "fc3_in")], lane=lane, loss_mode=loss_mode))
    params = lenet.init_lenet5_int8(jax.random.key(7))
    state = TrainState(params, jnp.int32(0),
                       jax.random.key_data(jax.random.key(13)))
    bx, by = _glyph_batch()
    qx = quant_from_float(bx)
    gs = []
    for _ in range(STEPS):
        state, m = step(state, {"x": qx, "y": by},
                        jnp.ones((1,), jnp.float32))
        gs.append(int(m["g"]))
    return {"params_sha256": digest_tree(state.params), "g_signs": gs}


PRESERVED = {
    "fp32_full_zo_n1": lambda: run_fp32_case("full_zo", 1, [1.0]),
    "fp32_elastic_zo_n1": lambda: run_fp32_case("elastic_zo", 1, [1.0]),
    "fp32_full_bp": lambda: run_fp32_case("full_bp", 1, [1.0]),
}
# Engine-canonical cases, generated ON the engine implementation:
#  * multi-probe fp32 with a masked (straggler) probe pins the
#    accumulate-then-cast probe fold;
#  * the int8 lane pins the per-probe key schedule
#    fold_in(fold_in(base, step), probe_id) the engine unified with the
#    fleet (the pre-engine int8 step used the bare step key) and the
#    int32 accumulate-then-clamp update. Integer arithmetic is
#    platform-exact, so these assert regardless of the canary.
CANONICAL = {
    "fp32_full_zo_n3_masked": lambda: run_fp32_case(
        "full_zo", 3, [1.0, 0.0, 1.0]),
    "fp32_elastic_zo_n3_masked": lambda: run_fp32_case(
        "elastic_zo", 3, [1.0, 0.0, 1.0]),
    "int8_elastic_intloss": lambda: run_int8_case("int"),
    "int8_elastic_floatloss": lambda: run_int8_case("float"),
}


def regenerate(sections):
    doc = json.loads(FIXTURE.read_text()) if FIXTURE.exists() else {}
    doc.setdefault("meta", {})["jax"] = jax.__version__
    doc["canary"] = run_canary()
    for name, cases in (("preserved", PRESERVED), ("canonical", CANONICAL)):
        if name not in sections:
            continue
        doc[name] = {k: fn() for k, fn in cases.items()}
        print(f"[golden] regenerated section {name!r} "
              f"({len(doc[name])} cases)")
    FIXTURE.parent.mkdir(exist_ok=True)
    FIXTURE.write_text(json.dumps(doc, indent=1) + "\n")
    print(f"[golden] wrote {FIXTURE}")


if __name__ == "__main__":
    regenerate(set(sys.argv[1:]) or {"preserved", "canonical"})
