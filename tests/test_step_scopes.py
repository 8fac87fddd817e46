"""The train step's named phases and its signed loss difference.

Every lane names its device time with the same four ``jax.named_scope``
phases (core/zo.py); they reach the compiled HLO as ``op_name``
metadata. The fp32 engine also reports each probe's signed L+ - L-.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import LaneConfig
from repro.core.elastic import TrainState, make_elastic_step
from repro.core.elastic_int8 import make_int8_elastic_step
from repro.core.int8 import quant_from_float
from repro.data.synthetic import glyphs
from repro.models import lenet

PHASES = ("zo_perturb", "zo_forward", "bp_tail", "zo_update")


def scopes_in(hlo_text: str) -> set:
    names = " ".join(re.findall(r'op_name="([^"]*)"', hlo_text))
    return {p for p in PHASES if re.search(rf"\b{p}\b", names)}


def glyph_batch(n=8):
    xs, ys = glyphs(n, seed=0)
    return jnp.asarray(xs), jnp.asarray(ys)


def test_int8_lenet_step_names_its_phases():
    lane = LaneConfig(lane="elastic_zo_int8", int8_r_max=3,
                      int8_p_zero=0.33, int8_b_zo=1, int8_b_bp=5)
    step = make_int8_elastic_step(
        lenet.lenet5_forward_int8,
        partition_fn=lambda p: lenet.partition_at(p, 4),
        tail_fcs=[("fc3", "fc3_in")], lane=lane)
    params = lenet.init_lenet5_int8(jax.random.key(7))
    state = TrainState(params, jnp.int32(0),
                       jax.random.key_data(jax.random.key(13)))
    bx, by = glyph_batch()
    text = jax.jit(step).lower(state, {"x": quant_from_float(bx), "y": by},
                               jnp.ones((1,), jnp.float32)) \
        .compile().as_text()
    assert {"zo_perturb", "zo_forward", "zo_update"} <= scopes_in(text)
    assert "bp_tail" in scopes_in(text)     # the NITI tail of fc3


@pytest.mark.parametrize("lane_name,n", [("elastic_zo", 1),
                                         ("elastic_zo", 3),
                                         ("full_zo", 2)])
def test_zo_dl_is_the_signed_loss_difference(lane_name, n):
    """|zo_dl| / 2eps is each probe's |g| when nothing clips: with every
    probe kept, its mean is the reported zo_g."""
    eps = 1e-2
    lane = LaneConfig(lane=lane_name, learning_rate=0.05, zo_eps=eps,
                      zo_num_probes=n, zo_clip=None)
    part = (lambda p: lenet.partition_at(p, 4)) \
        if lane_name == "elastic_zo" else None
    step = jax.jit(make_elastic_step(lenet.lenet5_loss, lane,
                                     partition_fn=part))
    state = TrainState(lenet.init_lenet5(jax.random.key(7)), jnp.int32(0),
                       jax.random.key_data(jax.random.key(11)))
    bx, by = glyph_batch()
    _, m = step(state, {"x": bx, "y": by}, jnp.ones((n,), jnp.float32))
    dl = np.asarray(m["zo_dl"])
    assert dl.shape == (n,) and dl.dtype == np.float32
    assert np.all(dl != 0)
    g = np.abs(dl) / np.float32(2 * eps)
    assert float(np.mean(g)) == pytest.approx(float(m["zo_g"]), rel=1e-6)


def test_zo_dl_is_taken_before_the_clip_and_the_mask():
    lane = LaneConfig(lane="full_zo", learning_rate=0.05, zo_eps=1e-2,
                      zo_num_probes=2, zo_clip=1e-3)
    step = jax.jit(make_elastic_step(lenet.lenet5_loss, lane))
    state = TrainState(lenet.init_lenet5(jax.random.key(7)), jnp.int32(0),
                       jax.random.key_data(jax.random.key(11)))
    bx, by = glyph_batch()
    _, m = step(state, {"x": bx, "y": by}, jnp.asarray([1.0, 0.0]))
    dl = np.asarray(m["zo_dl"])
    assert np.all(np.abs(dl) / 2e-2 > 1e-3)          # above the clip
    assert dl[1] != 0                                 # the masked probe
    assert float(m["zo_g"]) == pytest.approx(1e-3 / 2, rel=1e-6)
