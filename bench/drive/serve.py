"""Serving cell: open-loop requests into ``serve.Engine``.

Set-up makes the weights from the seed on the device, builds the engine
with a page pool that holds every slot at full length, and compiles (or
loads from the cache) every program the cell's traffic can reach: each
(prompt bucket, wave size) prefill and its pool scatter, the first-token
samplers of every wave size, and the fused decode megastep of every
horizon (every request samples, so no greedy program is reachable). The window offers each request when it is
due, whether or not the engine keeps up, and steps the engine; a
request's time to first token counts from when it was due. After the
window no new load is offered and the requests in flight drain. Then the
engine is freed and the float32 reference judges a sample of the served
tokens, drawn from the seed with the longest request in it.
"""
from __future__ import annotations

import gc
import itertools
import math
import statistics
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .. import common, trace as trace_mod, weights

DRAIN_S = 120.0
COMPILE_THREADS = 8


def serve_config(tr: dict):
    from repro.configs.serve import ServeConfig
    sv = tr["serve"]
    per_seq = -(-(sv["max_seq_len"] + 1) // sv["page_size"])
    return ServeConfig(page_size=sv["page_size"],
                       num_pages=sv["max_batch_slots"] * per_seq + 1,
                       max_batch_slots=sv["max_batch_slots"],
                       max_seq_len=sv["max_seq_len"],
                       bucket_prompts=sv["bucket_prompts"])


def reachable(tr: dict, scfg) -> dict:
    """Every program shape the traffic can reach: prefill (bucket, wave
    size); first-token sampler wave sizes; megastep horizons; every split
    of a wave over buckets, whose logits the engine concatenates in a
    program of its own (PERF.md, Open questions: about 2,500 of them
    at the cell's size)."""
    gen = common.load_module("traffic", tr["generator"])
    buckets = gen.prompt_buckets(tr, scfg.max_seq_len)
    slots = scfg.max_batch_slots
    most = min(scfg.megastep, scfg.page_size,
               tr["output"]["max"])
    splits = set()
    for n in range(2, slots + 1):
        for k in range(2, min(len(buckets), n) + 1):
            for cut in itertools.combinations(range(1, n), k - 1):
                b = (0,) + cut + (n,)
                splits.add(tuple(b[j + 1] - b[j] for j in range(k)))
    return {"prefill": [(b, nb) for b in buckets
                        for nb in range(1, slots + 1)],
            "waves": list(range(1, slots + 1)),
            "horizons": list(range(1, most + 1)),
            "splits": sorted(splits)}


def warm(engine, shapes: dict, vocab: int) -> None:
    """Compile, or load from the cache, every reachable program, with
    arguments committed as the engine's own calls commit them."""
    import jax
    import jax.numpy as jnp
    from repro.serve import kv_pages, sampler
    s, cfg = engine.serve, engine.cfg

    def prefill(b, nb):
        m, fn = engine._get_prefill(b, nb)
        batch = {"tokens": jnp.asarray(np.zeros((nb, b), np.int32))}
        last = jnp.asarray(np.zeros(nb, np.int32))
        fn.lower(engine.params, batch, last).compile()
        return jax.eval_shape(fn, engine.params, batch, last)[1]

    def admit(dense_shape):
        nb = jax.tree.leaves(dense_shape)[0].shape[1]
        dense = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                             dense_shape)
        kv_pages._admit.lower(
            engine.caches, dense, jnp.asarray(np.zeros(nb, np.int32)),
            jnp.asarray(np.zeros((nb, s.max_pages_per_seq), np.int32)),
            pattern=cfg.pattern, page_size=s.page_size).compile()

    for b, nb in shapes["prefill"]:           # build the jit objects first
        engine._get_prefill(b, nb)
    with ThreadPoolExecutor(COMPILE_THREADS) as pool:
        dense_shapes = list(pool.map(lambda a: prefill(*a),
                                     shapes["prefill"]))
    for d in dense_shapes:                    # one dense cache at a time
        admit(d)
    Vp, P = cfg.padded_vocab, s.max_pages_per_seq
    for n in shapes["waves"]:
        # the engine uploads wave knobs, slots and page rows from lists
        jnp.asarray([0] * n, jnp.int32)
        jnp.asarray([[0] * P] * n, jnp.int32)
        lg = jnp.zeros((n, Vp), jnp.float32)
        np.asarray(sampler.sample_tokens(
            lg, jnp.asarray([0.8] * n, jnp.float32),
            jnp.asarray([50] * n, jnp.int32),
            jnp.asarray([0.9] * n, jnp.float32),
            jnp.asarray([np.uint32(0)] * n, jnp.uint32),
            jnp.asarray([0] * n, jnp.int32), vocab_size=vocab))
    for split in shapes["splits"]:
        jnp.concatenate([jnp.zeros((k, Vp), jnp.float32) for k in split],
                        axis=0).block_until_ready()
    n = s.max_batch_slots
    for h in shapes["horizons"]:
        plan = [np.zeros(n, np.int32), np.zeros((n, P), np.int32),
                np.zeros(n, np.int32), np.zeros(n, np.int32),
                np.zeros(n, np.float32), np.zeros(n, np.int32),
                np.ones(n, np.float32), np.zeros(n, np.uint32),
                np.zeros(n, np.int32)]
        toks, _, engine.caches, _, _ = engine._fused(
            engine.params, engine.caches,
            *[jnp.asarray(a) for a in plan], horizon=h, greedy=False)
        toks.block_until_ready()


def p90(values):
    return statistics.quantiles(values, n=10, method="inclusive")[8]


class ServeCell:
    """The cell's engine with every reachable program warm; `load` puts
    the weights of a seed in and empties the scheduler."""

    def __init__(self, ctx: dict):
        common.use_program()
        from repro.configs.base import LaneConfig, ShapeConfig
        from repro.core import api
        from repro.sharding.rules import ShardingRules
        self.c, self.tr = ctx["config"], ctx["traffic"]
        self.limits = ctx["workload"]["limits"]
        self.gen = common.load_module("traffic", self.tr["generator"])
        self.cfg = common.model_config(self.c)
        self.V = self.cfg.vocab_size
        self.scfg = serve_config(self.tr)
        self.lane = LaneConfig()
        dshape = ShapeConfig("serve_decode", seq_len=self.scfg.max_seq_len,
                             global_batch=self.scfg.max_batch_slots,
                             kind="decode")
        self.abstract = api.build(
            self.cfg, dshape, self.lane,
            ShardingRules(None, self.cfg, dshape)).abstract_params()
        self.first_bp = self.cfg.num_layers - api.tail_periods(
            self.cfg, self.lane) * len(self.cfg.pattern)
        self.engine = None

    def load(self, seed: int) -> None:
        from repro.serve.engine import Engine
        from repro.serve.scheduler import Scheduler
        params = weights.make_params(self.abstract,
                                     weights.sub_seed(seed, "weights"),
                                     self.first_bp, self.V)
        if self.engine is None:
            self.engine = Engine(self.cfg, self.scfg, lane=self.lane,
                                 params=params)
            warm(self.engine, reachable(self.tr, self.scfg), self.V)
        else:
            e = self.engine
            e.params = params
            e.sched = Scheduler(self.scfg, window=self.cfg.sliding_window)
            e._dev_plan, e._host_plan = None, {}

    def requests(self, seed: int, seconds: float) -> list:
        return self.gen.requests(self.tr, weights.sub_seed(seed, "requests"),
                                 seconds, self.V)

    def offer(self, reqs: list, seconds: float, on_close=None) -> dict:
        """Offer each request when it is due for `seconds`, then drain.
        `on_close(window_s)` runs the moment the window closes. Returns
        the per-request log and the window's length."""
        import jax
        from repro.serve.sampler import SamplingParams
        engine = self.engine
        log = [{"due": r["due_s"], "rid": None, "first": None, "last": None,
                "n": 0, "n_window": 0} for r in reqs]
        by_rid = {}
        pending = deque(sorted(range(len(reqs)),
                               key=lambda i: reqs[i]["due_s"]))

        def submit_due(t):
            while pending and reqs[pending[0]]["due_s"] <= t:
                i = pending.popleft()
                r = reqs[i]
                try:
                    rid = engine.submit(r["prompt"].tolist(),
                                        SamplingParams(**r["sampling"]),
                                        r["max_new"])
                except ValueError:
                    continue
                log[i]["rid"] = rid
                by_rid[rid] = log[i]

        def step():
            events = engine.step()
            t = common.now() - t0
            for ev in events:
                e = by_rid[ev.rid]
                if e["first"] is None:
                    e["first"] = t
                e["last"] = t
                e["n"] += 1

        t0 = common.now()
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW):
            while True:
                t = common.now() - t0
                if t >= seconds:
                    break
                with jax.profiler.TraceAnnotation("bench/submit"):
                    submit_due(t)
                if engine.sched.has_work():
                    with jax.profiler.TraceAnnotation("bench/engine_step"):
                        step()
                else:
                    nxt = reqs[pending[0]]["due_s"] if pending else seconds
                    with jax.profiler.TraceAnnotation("bench/wait_arrival"):
                        time.sleep(max(0.0, min(nxt, seconds) - t))
        window_s = common.now() - t0
        for e in log:
            e["n_window"] = e["n"]
        if on_close is not None:
            on_close(window_s)
        submit_due(math.inf)              # due in the window, not yet sent
        while engine.sched.has_work() and \
                common.now() - t0 < seconds + DRAIN_S:
            step()
        return {"log": log, "window_s": window_s}

    def served(self) -> dict:
        return {s.req.rid: {"prompt": list(s.req.prompt),
                            "served": list(s.generated),
                            "sampling": {
                                "temperature": s.req.sampling.temperature,
                                "top_k": s.req.sampling.top_k,
                                "top_p": s.req.sampling.top_p}}
                for s in self.engine.sched.finished}

    def free(self) -> None:
        self.engine = None
        gc.collect()

    def gaps(self, seed: int, served: dict, control: bool = False) -> dict:
        from ..reference import serve as ref_serve
        from ..reference.model import Dims
        sample = choose_sample(served, self.tr["check"], seed)
        return ref_serve.gaps(Dims.from_config(self.c),
                              weights.sub_seed(seed, "weights"), sample,
                              self.scfg.max_seq_len, control=control)

    def compare(self, g: dict) -> list:
        lim = self.limits
        return [common.Check("sampled_logit_gap", g["sampled_gap"],
                             lim["sampled_logit_gap"])]


def run(ctx: dict) -> dict:
    from repro import obs
    t_setup = common.now()
    seconds = ctx["seconds"]
    cell = ServeCell(ctx)
    cell.load(ctx["seed"])
    reqs = cell.requests(ctx["seed"], seconds)
    setup_s = common.now() - t_setup

    counter = common.CompileCounter()
    tracer = trace_mod.Tracer() if ctx["trace"] else None
    rec = obs.install() if ctx["trace"] else None
    layer = {"spans": []}

    def close(window_s):
        counter.armed = False
        if tracer:
            layer["trace"] = tracer.stop(window_s)
        if rec:
            layer["spans"] = list(rec.spans)
            obs.uninstall()

    if tracer:
        tracer.start()
    counter.armed = True
    res = cell.offer(reqs, seconds, on_close=close)
    peak = common.peak_bytes()
    log = res["log"]
    served = cell.served()
    done = [e for e in log if e["rid"] in served]
    ttft = [e["first"] - e["due"] for e in done]
    tpot = [(e["last"] - e["first"]) / (e["n"] - 1) for e in done
            if e["n"] > 1]
    layer.update({
        "window_s": res["window_s"], "compiles": counter.count,
        "config": cell.c, "traffic": cell.tr,
        "requests": [{"prompt_len": len(r["prompt"]),
                      "first_in_window": e["n_window"] > 0,
                      "decoded_in_window": max(e["n_window"] - 1, 0)}
                     for r, e in zip(reqs, log)],
    })
    cell.free()
    checks = cell.compare(cell.gaps(ctx["seed"], served))
    e2e = {"setup_s": setup_s}
    if ttft:
        e2e["ttft_p90_ms"] = 1e3 * p90(ttft)
    if tpot:
        e2e["tpot_p90_ms"] = 1e3 * p90(tpot)
    return {"end_to_end": e2e, "layer": layer, "attempted": len(log),
            "failed": len(log) - len(done), "memory_peak_bytes": peak,
            "checks": checks}


def choose_sample(served: dict, spec: dict, seed: int) -> list:
    """The longest finished request, then others drawn from the seed: a
    fixed number, so the reference's shapes never change."""
    rng = np.random.default_rng(weights.sub_seed(seed, "sample"))
    rids = sorted(served)
    longest = max(rids, key=lambda r: len(served[r]["prompt"])
                  + len(served[r]["served"]))
    pool = [r for r in rids if r != longest]
    return [served[longest]] + [served[int(r)] for r in
                                rng.permutation(pool)[:spec["sampled"]]]
