"""Fleet training launcher: simulated edge swarm with chaos injection.

``python -m repro.launch.fleet --arch llama3-8b --smoke --workers 8 \
      --dropout 0.2 --steps 20``

Runs N in-process workers against the seed-ledger protocol
(repro.fleet, docs/fleet.md): per-step scalar records for the ZO half,
error-feedback int8 payloads for the BP tail, deterministic
dropout/straggler chaos, optional crash/rejoin via ledger replay
(--crash worker:step:down). Exits non-zero if any worker's parameters
diverge from the coordinator's canon — the run is its own consistency
check.

``--lane int8`` runs the ElasticZO-INT8 lane (Alg. 2) instead: the
paper's LeNet-5 on deterministic glyphs, integer-only updates, 9-byte
ledger probes (record v2, docs/fleet.md), the same chaos matrix — and
additionally self-verifies the whole run bit-exact against the
single-process int8 reference (fleet/reference.py) before exiting.

``--byzantine 3:sign_flip,5:inflate:100`` puts deterministic attackers
on the named workers (fleet/adversary.py: inflate, sign_flip, freeload,
collude, seed_lie, stale_replay); ``--robust`` arms the Byzantine-robust
commit filter + quarantine (fleet/robust.py, commit v2 on the wire).
The int8 self-verification covers the Byzantine path too: the reference
re-derives every filter verdict from the realized arrival masks.

``--topology gossip`` removes the coordinator entirely: peers exchange
records epidemically (fleet/gossip.py, ``--gossip-fanout`` /
``--gossip-rounds``) and every peer closes each step independently via
the deterministic leaderless commit rule — the run exits non-zero
unless every surviving peer is bit-identical. ``--partition lo:hi:w+w``
schedules a temporary network split (the listed workers vs the rest);
the majority side keeps committing, the minority stalls and reconciles
at heal.
"""
from __future__ import annotations

import argparse
import sys


import jax
import jax.numpy as jnp

from .. import obs
from ..configs import (FleetConfig, GossipConfig, LaneConfig, RobustConfig,
                       ShapeConfig, get_arch, reduced)
from ..core import api
from ..data.synthetic import token_batch
from ..fleet import (make_int8_probe_fn, make_reference_step,
                     parse_byzantine, reference_state, run_fleet)
from ..sharding.rules import ShardingRules
from ..train.train_loop import LoopConfig, run
from .cache import enable_compile_cache


def _parse_partitions(ap, args):
    """'lo:hi:w+w+w,...' -> ((lo, hi, group_bitmask), ...)."""
    parts = []
    for p in args.partition.split(","):
        if not p:
            continue
        bits = p.split(":")
        if len(bits) != 3:
            ap.error(f"--partition entry {p!r} must be lo:hi:w+w+w")
        try:
            lo, hi = int(bits[0]), int(bits[1])
            group = 0
            for w in bits[2].split("+"):
                wi = int(w)
                if not 0 <= wi < args.workers:
                    ap.error(f"--partition worker {wi} out of range for "
                             f"--workers {args.workers}")
                group |= 1 << wi
        except ValueError:
            ap.error(f"--partition entry {p!r} must be lo:hi:w+w+w")
        parts.append((lo, hi, group))
    return tuple(parts)


def _parse_crashes(ap, args):
    crashes = []
    for c in args.crash.split(","):
        if not c:
            continue
        parts = c.split(":")
        if len(parts) != 3:
            ap.error(f"--crash entry {c!r} must be worker:step:down")
        w, cs, down = (int(x) for x in parts)
        if not 0 <= w < args.workers:
            ap.error(f"--crash worker {w} out of range for "
                     f"--workers {args.workers}")
        if cs < 0 or down < 1:
            ap.error(f"--crash entry {c!r}: step must be >= 0, down >= 1")
        crashes.append((w, cs, down))
    return tuple(crashes)


def lenet_int8_fleet_setup(bp_tail_layers: int = 1, probes: int = 1,
                           batch: int = 8, seed: int = 0):
    """LeNet-5 int8 fleet pieces: (params, lane, partition_fn, probe_fn,
    batch_fn). The one assembly of the paper's int8 deployment — the CLI
    below and benchmarks/bench_fleet.py share it. ``bp_tail_layers``
    counts trailing FC layers (paper: ZO-Feat-Cls1/2 = 1/2; 0 = Full-ZO
    INT8)."""
    from ..core.int8 import quant_from_float
    from ..data.synthetic import glyphs
    from ..models import lenet
    if not 0 <= bp_tail_layers <= 2:
        raise ValueError("int8 lane supports 0..2 tail FCs, got "
                         f"{bp_tail_layers}")
    c = 5 - bp_tail_layers
    tail_fcs = [("fc2", "fc2_in"), ("fc3", "fc3_in")][2 - bp_tail_layers:]
    lane = LaneConfig(lane="elastic_zo_int8", zo_num_probes=probes)
    partition_fn = lambda p, c=c: lenet.partition_at(p, c)  # noqa: E731
    probe_fn = make_int8_probe_fn(lenet.lenet5_forward_int8, lane,
                                  partition_fn, tail_fcs)
    params = lenet.init_lenet5_int8(jax.random.key(seed))

    def batch_fn(step):
        xs, ys = glyphs(batch, seed=seed + 1, start=step * batch)
        return {"x": quant_from_float(jnp.asarray(xs)),
                "y": jnp.asarray(ys)}

    return params, lane, partition_fn, probe_fn, batch_fn


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="LM arch (fp32 lanes; default llama3-8b)")
    ap.add_argument("--lane", default="elastic_zo",
                    choices=["elastic_zo", "full_zo", "int8"])
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-trainable)")
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--probes-per-worker", type=int, default=1)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--bp-tail-layers", type=int, default=1)
    ap.add_argument("--lr", type=float, default=None,
                    help="ZO learning rate (fp32 lanes; default 1e-2)")
    ap.add_argument("--eps", type=float, default=None,
                    help="SPSA eps (fp32 lanes; default 1e-3)")
    ap.add_argument("--dropout", type=float, default=0.0,
                    help="per-record transport loss probability")
    ap.add_argument("--max-delay", type=int, default=0,
                    help="max record delivery delay (virtual ticks)")
    ap.add_argument("--deadline", type=int, default=0,
                    help="coordinator per-step wait (virtual ticks)")
    ap.add_argument("--chaos-seed", type=int, default=0)
    ap.add_argument("--snapshot-every", type=int, default=10)
    ap.add_argument("--crash", default="",
                    help="worker:step:down triples, comma-separated, e.g. "
                         "'3:5:4' = worker 3 dies at step 5 for 4 steps")
    ap.add_argument("--byzantine", default="",
                    help="worker:attack[:amp] triples, comma-separated, "
                         "e.g. '3:sign_flip,5:inflate:100' "
                         "(fleet/adversary.py)")
    ap.add_argument("--robust", action="store_true",
                    help="arm the Byzantine-robust commit filter + "
                         "quarantine (fleet/robust.py; commit v2)")
    ap.add_argument("--robust-k-mad", type=float, default=6.0,
                    help="scalar filter band half-width, in MADs")
    ap.add_argument("--robust-mode", default="mask",
                    choices=["mask", "clip"],
                    help="reject out-of-band probes, or clip their "
                         "loss-diffs to the band")
    ap.add_argument("--topology", default="star",
                    choices=["star", "gossip"],
                    help="star: a coordinator closes every step; gossip: "
                         "leaderless — every peer closes independently "
                         "via the deterministic commit rule "
                         "(fleet/gossip.py)")
    ap.add_argument("--gossip-fanout", type=int, default=2,
                    help="peers contacted per epidemic push round")
    ap.add_argument("--gossip-rounds", type=int, default=2,
                    help="push rounds per step (anti-entropy then runs "
                         "the component to quiescence)")
    ap.add_argument("--partition", default="",
                    help="lo:hi:w+w+w windows, comma-separated: during "
                         "steps [lo,hi) the listed workers split from "
                         "the rest; the majority side keeps committing "
                         "(gossip topology only)")
    ap.add_argument("--no-verify-reference", action="store_true",
                    help="skip the single-process reference re-run "
                         "(int8 lane verifies it by default)")
    ap.add_argument("--seed", type=int, default=0)
    obs.add_observability_args(ap)
    args = ap.parse_args(argv)
    obs.configure_from_args(args)
    enable_compile_cache()

    crashes = _parse_crashes(ap, args)
    try:
        byzantine = parse_byzantine(args.byzantine)
    except ValueError as e:
        ap.error(str(e))
    robust = RobustConfig(mode=args.robust_mode,
                          k_mad=args.robust_k_mad) if args.robust else None
    partitions = _parse_partitions(ap, args)
    if partitions and args.topology != "gossip":
        ap.error("--partition needs --topology gossip (the star "
                 "coordinator cannot survive a split)")
    try:
        gossip = GossipConfig(fanout=args.gossip_fanout,
                              rounds=args.gossip_rounds,
                              partitions=partitions) \
            if args.topology == "gossip" else None
        fleet_cfg = FleetConfig(
            num_workers=args.workers,
            probes_per_worker=args.probes_per_worker,
            dropout=args.dropout, max_delay=args.max_delay,
            deadline=args.deadline, chaos_seed=args.chaos_seed,
            snapshot_every=args.snapshot_every, crashes=crashes,
            byzantine=byzantine, robust=robust,
            topology=args.topology, gossip=gossip)
    except ValueError as e:
        ap.error(str(e))

    loss_fn = None
    probe_fn = None
    if args.lane == "int8":
        # the int8 lane is integer-only LeNet-5 — reject fp32-lane flags
        # instead of silently ignoring them
        for flag, val in (("--lr", args.lr), ("--eps", args.eps),
                          ("--arch", args.arch)):
            if val is not None:
                ap.error(f"{flag} does not apply to --lane int8 "
                         "(integer-only LeNet-5; Alg. 2 knobs live in "
                         "LaneConfig.int8_*)")
        params, lane, partition_fn, probe_fn, batch_fn = \
            lenet_int8_fleet_setup(args.bp_tail_layers,
                                   args.probes_per_worker, args.batch,
                                   args.seed)
        desc = "lenet5-int8"
    else:
        if args.lr is None:
            args.lr = 1e-2
        if args.eps is None:
            args.eps = 1e-3
        cfg = get_arch(args.arch or "llama3-8b")
        if args.smoke:
            cfg = reduced(cfg)
        lane = LaneConfig(lane=args.lane, bp_tail_layers=args.bp_tail_layers,
                          zo_num_probes=args.probes_per_worker,
                          learning_rate=args.lr, zo_eps=args.eps)
        shape = ShapeConfig("fleet_cli", seq_len=args.seq,
                            global_batch=args.batch, kind="train")
        model = api.build(cfg, shape, lane, ShardingRules(None, cfg, shape))
        params = model.init(jax.random.key(args.seed))
        loss_fn = model.loss_fn
        partition_fn = None
        desc = cfg.name

        def batch_fn(step):
            x, y, m = token_batch(args.batch, args.seq, cfg.vocab_size,
                                  seed=args.seed + 1, step=step)
            return {"tokens": jnp.asarray(x), "labels": jnp.asarray(y),
                    "mask": jnp.asarray(m)}

    base_seed = jax.random.key_data(jax.random.key(args.seed + 1))
    obs.log("fleet", f"{desc}: {args.workers} workers x "
            f"{args.probes_per_worker} probes, lane={args.lane}, "
            f"topology={args.topology}, dropout={args.dropout}, "
            f"crashes={crashes or 'none'}, "
            f"partitions={args.partition or 'none'}, "
            f"byzantine={args.byzantine or 'none'}, "
            f"robust={'on' if robust else 'off'}")
    res = run_fleet(loss_fn, params, lane, fleet_cfg, batch_fn,
                    steps=args.steps, base_seed=base_seed,
                    partition_fn=partition_fn, probe_fn=probe_fn,
                    log_every=max(args.steps // 10, 1))
    for e in res.coordinator.events:
        obs.log("fleet", f"event: {e}")
    s = res.stats
    n_records = sum(len(t) for t in res.ledger.records.values())
    per_worker_step = s["ledger_bytes_zo"] / max(n_records, 1)
    # step 0 always holds >= 1 record: the coordinator force-accepts the
    # earliest arrival when everything misses the deadline ("a step is
    # never empty", fleet/coordinator.py)
    some_rec = next(iter(res.ledger.records[0].values()))
    obs.log("fleet", f"done: {s['steps']} steps, wall {s['wall_s']:.1f}s; "
          f"ZO wire {s['ledger_bytes_zo']}B "
          f"({per_worker_step:.1f}B/record, "
          f"{some_rec.zo_probe_nbytes}B/probe), tail wire "
          f"{s['ledger_bytes_tail']}B, catch-up {s['bytes_catchup']}B; "
          f"dropped {s['n_dropped']}, straggled {s['n_straggled']}, "
          f"redelivered {s['n_redelivered']}, "
          f"rejoins {s['n_catchups']}; rejected {s['n_rejected']}, "
          f"filtered probes {s['n_filtered_probes']}, "
          f"quarantines {s['n_quarantines']}"
          + (f"; gossip wire {s['bytes_gossip']}B, "
             f"reconciles {s['n_reconciles']}"
             if s["topology"] == "gossip" else ""))

    failed = False
    if args.lane == "int8" and some_rec.zo_probe_nbytes > 9:
        obs.log("fleet", "ERROR int8 ZO probe entry is "
                f"{some_rec.zo_probe_nbytes}B on the wire (> 9B budget)",
                level="error")
        failed = True

    n_exact = 0
    n_checked = 0
    canon_leaves = jax.tree.leaves(res.params)
    canon_struct = jax.tree.structure(res.params)
    for w in res.workers:
        if not w.alive:
            # crash scheduled past the end of the run: nothing to verify
            obs.log("fleet", f"note: worker {w.id} still down at end of run")
            continue
        ok = (jax.tree.structure(w.params) == canon_struct
              and all(jnp.array_equal(a, b) for a, b in
                      zip(jax.tree.leaves(w.params), canon_leaves)))
        if not ok:
            obs.log("fleet", f"ERROR worker {w.id} diverged from the canon",
                    level="error")
            failed = True
        n_exact += ok
        n_checked += 1
    who = "the coordinator" if args.topology == "star" \
        else "every other surviving peer (leaderless canon)"
    obs.log("fleet", f"{n_exact}/{n_checked} live workers bit-exact with "
            f"{who} at step {res.coordinator.step}")

    if args.lane == "int8" and not args.no_verify_reference:
        # replay the realized masks through the single-process reference
        # — the whole chaos run must reproduce bit-exactly. Byzantine
        # runs are driven by the ARRIVAL masks; the reference re-derives
        # validation, quarantine, and the filter itself.
        byz_path = byzantine or robust is not None
        drive = res.arrival_masks if byz_path else res.masks
        step_fn = make_reference_step(None, res.schema, probe_fn=probe_fn)
        state = reference_state(params, res.schema, base_seed)
        loop = LoopConfig(total_steps=args.steps, log_every=0,
                          n_probes=res.schema.n_probes,
                          mask_fn=lambda t: drive[t], jit=False)
        state, _ = run(step_fn, state, batch_fn, loop)
        ref_leaves = jax.tree.leaves(state.params["model"])
        ok = all(jnp.array_equal(a, b)
                 for a, b in zip(ref_leaves, canon_leaves))
        if ok:
            obs.log("fleet", "single-process int8 reference: bit-exact")
        else:
            obs.log("fleet", "ERROR fleet diverged from the "
                    "single-process int8 reference", level="error")
            failed = True

    obs.write_outputs(args)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
