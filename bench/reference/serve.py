"""Logit gaps of served tokens under the float32 reference.

Every sequence (prompt + served tokens but the last) runs through the
model once, all sequences together and padded to one length (padding at
the end is invisible to causal attention). At the position before each
served token the reference's logits judge that token:

* greedy request: gap = best logit - the served token's logit;
* sampled request (temperature T, top-k k, top-p p): gap = how far the
  token's logit lies below the smallest logit of the set that top-k then
  top-p keep of softmax(logits / T), or 0 inside the set.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from . import model
from .model import Dims


def hidden_states(dims: Dims, weight_seed: int, tokens: np.ndarray,
                  precision: str = "f32"):
    """Final hidden states [N, S, d] float32 of token rows [N, S]."""
    ws = jnp.uint32(weight_seed)
    lw = jax.jit(functools.partial(model.layer_weights, dims))
    hw = jax.jit(functools.partial(model.head_weights, dims))(ws)
    step = jax.jit(lambda w, x: model.layer(w, x, dims, precision))
    x = model.embed(hw, jnp.asarray(tokens, jnp.int32))
    for i in range(dims.layers):
        x = step(lw(ws, jnp.uint32(i)), x)
    return hw, x


def position_logits(dims: Dims, hw, x, rows, cols, precision="f32",
                    block: int = 256) -> np.ndarray:
    """Logits [n, vocab] at (rows[i], cols[i]) of hidden states x."""
    f = jax.jit(lambda h: model.logits(hw, h[None], dims, precision)[0,
                                                                      :, :dims.vocab])
    rows, cols = np.asarray(rows), np.asarray(cols)
    out = []
    for i in range(0, len(rows), block):
        r, c = rows[i:i + block], cols[i:i + block]
        pad = block - len(r)
        h = x[np.pad(r, (0, pad)), np.pad(c, (0, pad))]
        out.append(np.asarray(f(h))[:len(r)])
    return np.concatenate(out)


def kept_floor(lg: np.ndarray, temperature: float, top_k: int,
               top_p: float) -> float:
    """Smallest raw logit of the set that top-k, then top-p of the
    tempered distribution, keep."""
    s = np.sort(lg.astype(np.float64))[::-1]
    if top_k > 0:
        s = s[:top_k]
    z = s / temperature
    p = np.exp(z - z.max())
    p /= p.sum()
    cum = np.cumsum(p)
    n = int(np.sum((cum - p) < top_p)) if top_p < 1.0 else len(s)
    return float(s[max(n, 1) - 1])


def layout(requests, seq_len: int):
    """Token rows [N, seq_len] and, per served token, (row, col)."""
    toks = np.zeros((len(requests), seq_len), np.int32)
    rows, cols = [], []
    for i, r in enumerate(requests):
        seq = list(r["prompt"]) + list(r["served"][:-1])
        toks[i, :len(seq)] = seq
        lp = len(r["prompt"])
        for j in range(len(r["served"])):
            rows.append(i)
            cols.append(lp - 1 + j)
    return toks, np.asarray(rows), np.asarray(cols)


def gaps(dims: Dims, weight_seed: int, requests, seq_len: int,
         control: bool = False) -> dict:
    """{'greedy_gap', 'sampled_gap', 'tokens'}; with `control`, also
    'control_gap': the float32 gap of the token that the fp8 reference
    puts first, at every position."""
    toks, rows, cols = layout(requests, seq_len)
    hw, x = hidden_states(dims, weight_seed, toks)
    ref = position_logits(dims, hw, x, rows, cols)
    del x
    greedy, sampled = [0.0], [0.0]
    k = 0
    for r in requests:
        sp = r["sampling"]
        for tok in r["served"]:
            lg = ref[k]
            if sp["temperature"] <= 0:
                greedy.append(float(lg.max() - lg[tok]))
            else:
                floor = kept_floor(lg, sp["temperature"], sp["top_k"],
                                   sp["top_p"])
                sampled.append(max(0.0, floor - float(lg[tok])))
            k += 1
    out = {"greedy_gap": max(greedy), "sampled_gap": max(sampled),
           "tokens": int(len(rows))}
    if control:
        hw8, x8 = hidden_states(dims, weight_seed, toks, "fp8")
        low = position_logits(dims, hw8, x8, rows, cols, "fp8")
        pick = low.argmax(-1)
        out["control_gap"] = float(np.max(
            ref.max(-1) - ref[np.arange(len(pick)), pick]))
    return out
