"""The first elastic-ZO step (one probe) of the latent-attention MoE
decoder, followed from the seed in float32 (bench/reference/mla_moe.py).

As bench/reference/ezo.py for the dense model: with the first step's
probe seed s, the ZO layers (the leading dense ones and every MoE layer
but the BP tail's) and the embedding are perturbed to bf16(theta +/- eps
z), both passes run to a loss, g = clip((L+ - L-) / 2 eps), and the
tail's layers, the final norm and the output head take one SGD step on
the mean of their gradients at the two perturbed points. A leaf's noise
is that of its path in the program's ZO group (``['lead']...`` or
``['periods_zo']...``) at its layer's offset within that group's stack.

Only the first step is followed (the cell compares that one), so the ZO
layers are made from the seed inside the program that perturbs them and
are never held: the bf16 model (9.8 GB) and the tail's float32 gradient
do not fit one chip together. Their update, bf16(theta - lr g z), is the
cell's replay of the noise (``zo_step_gap``), not the reference's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import weights
from . import mla_moe, model, noise
from .mla_moe import Dims

HEAD_ROWS = 512          # rows of logits made at a time in the head


def group_of(dims: Dims, layer: int):
    """(ZO group, index in its stack) of a ZO layer."""
    if layer < dims.dense_layers:
        return "lead", layer
    return "periods_zo", layer - dims.dense_layers


def _path(group: str, name: str) -> str:
    return f"['{group}']['blk0']" + "".join(f"['{p}']"
                                            for p in name.split("/"))


def programs(dims: Dims, eps: float, prec: str) -> dict:
    def noisy(w, seed, group, idx, coeff):
        out = {}
        for k, v in w.items():
            z = noise.normal(seed, noise.leaf_salt(_path(group, k)), v.shape,
                             offset=idx * jnp.uint32(v.size))
            out[k] = weights.cast(v.astype(jnp.float32) + coeff * z, v.dtype)
        return out

    @functools.partial(jax.jit, static_argnums=(0, 1))
    def zo_layer(group, dense, wseed, layer, x, seed, idx, sign):
        w = mla_moe.layer_weights(dims, wseed, layer, dense)
        return mla_moe.layer(noisy(w, seed, group, idx, sign * eps), x, dims,
                             prec)

    @jax.jit
    def zo_embed(emb, tokens, seed, sign):
        z = noise.normal(seed, noise.leaf_salt("['embed']"), emb.shape)
        e = weights.cast(emb.astype(jnp.float32) + sign * eps * z, emb.dtype)
        return model.embed({"embed": e}, tokens)

    def tail_forward(tw, x):
        for w in tw["layers"]:
            x = mla_moe.layer(w, x, dims, prec)
        return model.rms_norm(x, tw["final_norm"], dims.norm_eps)

    @functools.partial(jax.jit, donate_argnums=(5,))
    def tail_grad(tw, unembed, x, labels, mask, acc):
        h, vjp = jax.vjp(lambda t: tail_forward(t, x), tw)
        loss, dh, acc = head_grad(unembed.astype(jnp.float32),
                                  h.reshape(-1, h.shape[-1]),
                                  labels.reshape(-1), mask.reshape(-1), acc)
        (dtw,) = vjp(dh.reshape(h.shape))
        return loss, dtw, acc

    def head_grad(U, h, labels, mask, acc):
        n, d = h.shape
        c = HEAD_ROWS if n % HEAD_ROWS == 0 else n
        count = jnp.maximum(jnp.sum(mask), 1.0)

        def body(i, carry):
            loss, dh, acc = carry
            hs = jax.lax.dynamic_slice_in_dim(h, i * c, c)
            ys = jax.lax.dynamic_slice_in_dim(labels, i * c, c)
            ms = jax.lax.dynamic_slice_in_dim(mask, i * c, c)
            lg = model.mm("nd,dv->nv", hs, U, prec)
            lg = jnp.where(jnp.arange(lg.shape[1]) < dims.vocab, lg, -jnp.inf)
            logz = jax.nn.logsumexp(lg, axis=-1)
            ll = jnp.take_along_axis(lg, ys[:, None], -1)[:, 0]
            loss = loss + jnp.sum((logz - ll) * ms)
            p = jnp.exp(lg - logz[:, None])
            p = p.at[jnp.arange(c), ys].add(-1.0) * (ms / count)[:, None]
            acc = acc + model.mm("nd,nv->dv", hs, p, prec)
            dh = jax.lax.dynamic_update_slice_in_dim(
                dh, model.mm("nv,dv->nd", p, U, prec), i * c, 0)
            return loss, dh, acc

        loss, dh, acc = jax.lax.fori_loop(
            0, n // c, body, (jnp.float32(0), jnp.zeros_like(h), acc))
        return loss / count, dh, acc

    @jax.jit
    def sgd(p, g, lr):
        return jax.tree.map(
            lambda a, b: weights.cast(a.astype(jnp.float32) - lr * 0.5 * b,
                                      a.dtype), p, g)

    return {"zo_layer": zo_layer, "zo_embed": zo_embed,
            "tail_grad": tail_grad, "sgd": sgd}


class EzoReference:
    def __init__(self, dims: Dims, lane: dict, weight_seed: int,
                 train_seed: int, precision: str = "f32",
                 half_batch: bool = False):
        self.dims, self.lane = dims, lane
        self.weight_seed, self.train_seed = weight_seed, train_seed
        self.half_batch = half_batch
        self.tail = int(lane["bp_tail_layers"])
        self.zo_layers = dims.layers - self.tail
        ws = jnp.uint32(weight_seed)
        self.tail_layers = [
            jax.jit(functools.partial(mla_moe.layer_weights, dims,
                                      layer=i))(ws)
            for i in range(self.zo_layers, dims.layers)]
        self.head = jax.jit(functools.partial(mla_moe.head_weights, dims))(ws)
        self.p = programs(dims, float(lane["zo_eps"]), precision)

    def first_step(self, batch: dict) -> float:
        """Run the first step on batch {tokens, labels, mask}; returns
        its loss 0.5 (L+ + L-)."""
        p, dims = self.p, self.dims
        ws = jnp.uint32(self.weight_seed)
        tokens = jnp.asarray(batch["tokens"])
        labels = jnp.asarray(batch["labels"])
        mask = jnp.asarray(batch["mask"], jnp.float32)
        if self.half_batch:
            mask = mask.at[mask.shape[0] // 2:].set(0.0)
        seed = noise.probe_seed(self.train_seed, 0)
        tw = jax.tree.map(lambda a: a.astype(jnp.float32),
                          {"layers": self.tail_layers,
                           "final_norm": self.head["final_norm"]})
        acc = jnp.zeros(self.head["unembed"].shape, jnp.float32)
        losses, grads = [], []
        for sign in (1.0, -1.0):
            x = p["zo_embed"](self.head["embed"], tokens, seed, sign)
            for i in range(self.zo_layers):
                group, idx = group_of(dims, i)
                x = p["zo_layer"](group, i < dims.dense_layers, ws,
                                  jnp.uint32(i), x, seed, jnp.uint32(idx),
                                  sign)
            loss, dtw, acc = p["tail_grad"](tw, self.head["unembed"], x,
                                            labels, mask, acc)
            losses.append(float(loss))
            grads.append(dtw)
        del tw, x
        lp, lm = losses
        eps, clip = float(self.lane["zo_eps"]), float(self.lane["zo_clip"])
        g = max(-clip, min(clip, (lp - lm) / (2.0 * eps)))
        self.last = (lp, lm, g)
        cur = {"layers": self.tail_layers,
               "final_norm": self.head["final_norm"],
               "unembed": self.head["unembed"]}
        g = jax.tree.map(jnp.add, grads[0], grads[1])
        g["unembed"] = acc
        del grads
        new = p["sgd"](cur, g, jnp.float32(self.lane["learning_rate"]))
        self.tail_layers = new["layers"]
        self.head["final_norm"] = new["final_norm"]
        self.head["unembed"] = new["unembed"]
        return 0.5 * (lp + lm)

    def tail_change_norms(self) -> dict:
        """Per BP-tail leaf (keyed as the program's), the norm of its
        change from the initial weights."""
        dims, ws = self.dims, jnp.uint32(self.weight_seed)

        @functools.partial(jax.jit, static_argnums=(1,))
        def layer_sq(w, layer):
            w0 = mla_moe.layer_weights(dims, ws, layer)
            return {k: jnp.sum((v.astype(jnp.float32)
                                - w0[k].astype(jnp.float32)) ** 2)
                    for k, v in w.items()}

        sq = {}
        for i, w in enumerate(self.tail_layers, self.zo_layers):
            for k, v in layer_sq(w, i).items():
                key = f"periods_bp/blk0/{k}"
                sq[key] = sq.get(key, 0.0) + float(v)

        @jax.jit
        def head_sq(h):
            h0 = mla_moe.head_weights(dims, ws)
            return {k: jnp.sum((h[k].astype(jnp.float32)
                                - h0[k].astype(jnp.float32)) ** 2)
                    for k in ("final_norm", "unembed")}

        sq.update({k: float(v) for k, v in head_sq(self.head).items()})
        return {k: v ** 0.5 for k, v in sq.items()}
