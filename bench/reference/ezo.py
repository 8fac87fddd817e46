"""The elastic-ZO step (one probe) followed from the seed, in float32.

Per step t (0-based) with probe seed s_t: the first L - K layers and the
embedding are perturbed to bf16(theta +/- eps * z), both passes run to a
loss, g = clip((L+ - L-) / 2 eps), every ZO leaf becomes
bf16(theta - lr * g * z), and the last K layers, the final norm and the
output head take one SGD step on the mean of their gradients at the two
perturbed points. The loss is the mean token cross-entropy over the
published vocabulary: the padded logits take no part in it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import weights
from . import model, noise
from .model import Dims

HEAD_ROWS = 512          # rows of logits made at a time in the head


def _zo_path(name: str) -> str:
    if name == "embed":
        return "['embed']"
    return "['periods_zo']" + "".join(f"['{p}']" for p in name.split("/"))


def programs(dims: Dims, eps: float, prec: str) -> dict:
    """The reference's jitted pieces, compiled once per run."""
    shapes = dims.leaf_shapes()

    def perturbed(w, seed, layer, sign):
        out = {}
        for k, v in w.items():
            shp = shapes[k]
            z = noise.normal(seed, noise.leaf_salt(_zo_path("blk0/" + k)),
                             shp, offset=layer * jnp.uint32(v.size))
            out[k] = weights.cast(v.astype(jnp.float32) + sign * eps * z,
                                  v.dtype)
        return out

    @jax.jit
    def zo_layer(w, x, seed, layer, sign):
        return model.layer(perturbed(w, seed, layer, sign), x, dims, prec)

    @jax.jit
    def zo_embed(emb, tokens, seed, sign):
        z = noise.normal(seed, noise.leaf_salt("['embed']"), emb.shape)
        e = weights.cast(emb.astype(jnp.float32) + sign * eps * z,
                         emb.dtype)
        return model.embed({"embed": e}, tokens)

    def tail_forward(tw, x):
        for w in tw["layers"]:
            x = model.layer(w, x, dims, prec)
        return model.rms_norm(x, tw["final_norm"], dims.norm_eps)

    @functools.partial(jax.jit, donate_argnums=(5,))
    def tail_grad(tw, unembed, x, labels, mask, acc):
        """Loss and the gradients of the tail at one perturbed point;
        the output head's gradient is added into `acc` (float32),
        block by block of rows, so no second head-sized buffer is
        made."""
        h, vjp = jax.vjp(lambda t: tail_forward(t, x), tw)
        loss, dh, acc = head_grad(unembed.astype(jnp.float32),
                                  h.reshape(-1, h.shape[-1]),
                                  labels.reshape(-1), mask.reshape(-1),
                                  acc)
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
    def zo_update(w, seed, layer, coeff):
        out = {}
        for k, v in w.items():
            z = noise.normal(seed, noise.leaf_salt(_zo_path("blk0/" + k)),
                             shapes[k],
                             offset=layer * jnp.uint32(v.size))
            out[k] = weights.cast(v.astype(jnp.float32) - coeff * z,
                                  v.dtype)
        return out

    @jax.jit
    def embed_update(emb, seed, coeff):
        z = noise.normal(seed, noise.leaf_salt("['embed']"), emb.shape)
        return weights.cast(emb.astype(jnp.float32) - coeff * z, emb.dtype)

    @jax.jit
    def sgd(p, g, lr):
        return jax.tree.map(
            lambda a, b: weights.cast(a.astype(jnp.float32) - lr * 0.5 * b,
                                      a.dtype), p, g)

    return {"zo_layer": zo_layer, "zo_embed": zo_embed,
            "tail_grad": tail_grad, "zo_update": zo_update,
            "embed_update": embed_update, "sgd": sgd}



class EzoReference:
    def __init__(self, dims: Dims, lane: dict, weight_seed: int,
                 train_seed: int, precision: str = "f32",
                 half_batch: bool = False):
        self.dims, self.lane = dims, lane
        self.weight_seed, self.train_seed = weight_seed, train_seed
        self.precision, self.half_batch = precision, half_batch
        self.tail = int(lane["bp_tail_layers"])
        self.zo_layers = dims.layers - self.tail
        ws = jnp.uint32(weight_seed)
        lw = jax.jit(functools.partial(model.layer_weights, dims))
        self.layers = [lw(ws, jnp.uint32(i)) for i in range(dims.layers)]
        self.head = jax.jit(functools.partial(model.head_weights, dims))(ws)
        p = programs(dims, float(lane["zo_eps"]), precision)
        self._zo_layer, self._zo_embed = p["zo_layer"], p["zo_embed"]
        self._tail_grad, self._zo_update = p["tail_grad"], p["zo_update"]
        self._embed_update, self._sgd = p["embed_update"], p["sgd"]

    def _tail_params(self):
        return jax.tree.map(lambda a: a.astype(jnp.float32),
                            {"layers": self.layers[self.zo_layers:],
                             "final_norm": self.head["final_norm"]})

    def step(self, t: int, batch: dict) -> float:
        """Run step t (0-based) on batch {tokens, labels, mask}; returns
        the step's loss 0.5 (L+ + L-)."""
        tokens = jnp.asarray(batch["tokens"])
        labels = jnp.asarray(batch["labels"])
        mask = jnp.asarray(batch["mask"], jnp.float32)
        if self.half_batch:
            mask = mask.at[mask.shape[0] // 2:].set(0.0)
        seed = noise.probe_seed(self.train_seed, t)
        tw = self._tail_params()
        acc = jnp.zeros(self.head["unembed"].shape, jnp.float32)
        losses, grads = [], []
        for sign in (1.0, -1.0):
            x = self._zo_embed(self.head["embed"], tokens, seed, sign)
            for i in range(self.zo_layers):
                x = self._zo_layer(self.layers[i], x, seed, jnp.uint32(i),
                                   sign)
            loss, dtw, acc = self._tail_grad(tw, self.head["unembed"], x,
                                             labels, mask, acc)
            losses.append(float(loss))
            grads.append(dtw)
        del tw, x
        lp, lm = losses
        eps, clip = float(self.lane["zo_eps"]), float(self.lane["zo_clip"])
        g = max(-clip, min(clip, (lp - lm) / (2.0 * eps)))
        self.last = (lp, lm, g)
        coeff = jnp.float32(float(self.lane["learning_rate"]) * g)
        self.head["embed"] = self._embed_update(self.head["embed"], seed,
                                                coeff)
        for i in range(self.zo_layers):
            self.layers[i] = self._zo_update(self.layers[i], seed,
                                             jnp.uint32(i), coeff)
        cur = {"layers": self.layers[self.zo_layers:],
               "final_norm": self.head["final_norm"],
               "unembed": self.head["unembed"]}
        g = jax.tree.map(jnp.add, grads[0], grads[1])
        g["unembed"] = acc
        del grads
        new = self._sgd(cur, g, jnp.float32(self.lane["learning_rate"]))
        self.layers[self.zo_layers:] = new["layers"]
        self.head["final_norm"] = new["final_norm"]
        self.head["unembed"] = new["unembed"]
        return 0.5 * (lp + lm)

    def tail_change_norms(self) -> dict:
        """Per BP-tail leaf (the last layers grouped as the lane groups
        them, the final norm, the output head), the norm of its change
        from the initial weights."""
        dims, ws = self.dims, jnp.uint32(self.weight_seed)

        @jax.jit
        def layer_sq(w, layer):
            w0 = model.layer_weights(dims, ws, layer)
            return {k: jnp.sum((v.astype(jnp.float32)
                                - w0[k].astype(jnp.float32)) ** 2)
                    for k, v in w.items()}

        sq = {}
        for i in range(self.zo_layers, dims.layers):
            for k, v in layer_sq(self.layers[i], jnp.uint32(i)).items():
                key = f"periods_bp/blk0/{k}"
                sq[key] = sq.get(key, 0.0) + float(v)

        @jax.jit
        def head_sq(h):
            h0 = model.head_weights(dims, ws)
            return {k: jnp.sum((h[k].astype(jnp.float32)
                                - h0[k].astype(jnp.float32)) ** 2)
                    for k in ("final_norm", "unembed")}

        sq.update({k: float(v) for k, v in head_sq(self.head).items()})
        return {k: v ** 0.5 for k, v in sq.items()}
