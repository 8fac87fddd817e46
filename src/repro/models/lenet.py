"""LeNet-5 exactly as the paper uses it (Fig. 1 top): same-padding convs,
2x2 max-pools, 784->120->84->10 FC head. 107,786 fp32 parameters — matching
the paper's ZO/BP split accounting (ZO-Feat-Cls1 trains 106,936, Cls2
96,772). INT8 variant follows NITI (no biases).
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from ..configs.paper_models import LeNet5Config
from ..core.int8 import (QTensor, qconv2d, qdense, qmaxpool2, qrelu,
                         quant_from_float)
from .layers import dense_init, subkey

LAYER_NAMES = ("conv1", "conv2", "fc1", "fc2", "fc3")


def init_lenet5(key, cfg: LeNet5Config = LeNet5Config(), dtype=jnp.float32):
    c1, c2 = cfg.conv_channels
    k = cfg.kernel
    flat = (cfg.in_shape[0] // 4) * (cfg.in_shape[1] // 4) * c2   # 7*7*16
    f1, f2, nc = cfg.fc_dims
    return {
        "conv1": {"w": dense_init(subkey(key, "c1"), (k, k, cfg.in_shape[2], c1),
                                  dtype, fan_in=k * k * cfg.in_shape[2]),
                  "b": jnp.zeros((c1,), dtype)},
        "conv2": {"w": dense_init(subkey(key, "c2"), (k, k, c1, c2), dtype,
                                  fan_in=k * k * c1),
                  "b": jnp.zeros((c2,), dtype)},
        "fc1": {"w": dense_init(subkey(key, "f1"), (flat, f1), dtype),
                "b": jnp.zeros((f1,), dtype)},
        "fc2": {"w": dense_init(subkey(key, "f2"), (f1, f2), dtype),
                "b": jnp.zeros((f2,), dtype)},
        "fc3": {"w": dense_init(subkey(key, "f3"), (f2, nc), dtype),
                "b": jnp.zeros((nc,), dtype)},
    }


def _conv_same(x, w, b):
    k = w.shape[0]
    pad = k // 2
    y = jax.lax.conv_general_dilated(
        x, w, (1, 1), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + b


def lenet5_forward(params, x):
    """x: [B,28,28,1] fp32 -> logits [B,10]; returns (logits, acts)."""
    acts = {}
    h = jax.nn.relu(_conv_same(x, params["conv1"]["w"], params["conv1"]["b"]))
    h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                              (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    h = jax.nn.relu(_conv_same(h, params["conv2"]["w"], params["conv2"]["b"]))
    h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                              (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    h = h.reshape(h.shape[0], -1)
    acts["fc1_in"] = h
    h = jax.nn.relu(h @ params["fc1"]["w"] + params["fc1"]["b"])
    acts["fc2_in"] = h
    h = jax.nn.relu(h @ params["fc2"]["w"] + params["fc2"]["b"])
    acts["fc3_in"] = h
    logits = h @ params["fc3"]["w"] + params["fc3"]["b"]
    return logits, acts


def lenet5_loss(params, batch):
    logits, _ = lenet5_forward(params, batch["x"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, batch["y"][:, None], axis=-1)[:, 0]
    return jnp.mean(logz - ll)


def partition_at(params: Dict, c: int):
    """Paper partition point: first c layers ZO, rest BP."""
    zo = {n: params[n] for n in LAYER_NAMES[:c]}
    bp = {n: params[n] for n in LAYER_NAMES[c:]}
    return zo, bp


# ------------------------------------------------------------------ #
# INT8 (NITI) variant — no biases, QTensor weights
# ------------------------------------------------------------------ #
def init_lenet5_int8(key, cfg: LeNet5Config = LeNet5Config()):
    """6-bit quantized N(0, 1/fan_in) weights, built without an inexact
    float op so that every backend gives the same bits: each normal draw
    is the sum of 12 uniform 16-bit integers of the threefry stream
    (Irwin-Hall), and the quantizer is exact. (``jax.random.normal``'s
    erf_inv differs in the last bit between the CPU and the TPU, which
    moves weights across rounding boundaries.)"""
    shapes = jax.eval_shape(lambda: init_lenet5(key, cfg))
    out = {}
    for name, k in zip(LAYER_NAMES, jax.random.split(key, len(LAYER_NAMES))):
        shape = shapes[name]["w"].shape
        u = jax.random.bits(k, (12,) + shape, jnp.uint32) >> 16
        s = jnp.sum(u.astype(jnp.int32), axis=0) - 6 * 65535   # mean 0, sd ~2^16
        fan_in = math.prod(shape[:-1])
        w = s.astype(jnp.float32) * jnp.float32(
            1.0 / (65536.0 * math.sqrt(fan_in)))
        out[name] = {"w": quant_from_float(w, bits=6)}
    return out


def lenet5_forward_int8(params, x: QTensor):
    """x: QTensor [B,28,28,1] -> (logits QTensor [B,10], acts)."""
    acts = {}
    h = qrelu(qconv2d_same(x, params["conv1"]["w"]))
    h = qmaxpool2(h)
    h = qrelu(qconv2d_same(h, params["conv2"]["w"]))
    h = qmaxpool2(h)
    h = QTensor(h.data.reshape(h.data.shape[0], -1), h.exp)
    acts["fc1_in"] = h
    h = qrelu(qdense(h, params["fc1"]["w"]))
    acts["fc2_in"] = h
    h = qrelu(qdense(h, params["fc2"]["w"]))
    acts["fc3_in"] = h
    logits = qdense(h, params["fc3"]["w"])
    return logits, acts


def qconv2d_same(x: QTensor, w: QTensor):
    k = w.data.shape[0]
    pad = k // 2
    xd = jnp.pad(x.data, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    return qconv2d(QTensor(xd, x.exp), w)
