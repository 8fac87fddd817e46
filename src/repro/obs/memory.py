"""The measured memory ledger: tagged live-bytes + JAX reconciliation.

The paper's headline claim is a *memory* tradeoff (ZO trains in nearly
inference memory; ElasticZO's BP tail adds 0.072-1.7%; INT8 cuts usage
1.46-1.60x), and until this module the repo only evaluated it
analytically (Eqs. 2-4 / 13-15 in benchmarks/paper_tables.py). This is
the instrument that turns those derivations into measurements. Three
layers:

  * **tagged registry** (``MemoryLedger``) — each subsystem registers
    the buffers it owns under a dotted tag (``train.params``,
    ``serve.kv_pages``, ``fleet.ledger.zo`` ... see
    docs/observability.md for the catalog) with O(1) alloc/free
    accounting, per-tag and total high-water marks, and optional *keys*
    for double-free / leak detection. ``region(name)`` brackets a code
    range and records its total-live high-water mark, the per-span
    analogue of a peak-RSS probe.
  * **sampling hook** (``sample``) — walks ``jax.live_arrays()`` (and
    device ``memory_stats()`` where the backend has them; CPU returns
    none) and reconciles what JAX actually holds against the tagged
    total, reporting the **untagged residual**. A residual that grows
    is a subsystem allocating outside its tag — exactly the silent
    regression the analytic tables can never see.
  * **compiled footprint** (``compiled_footprint``) — XLA's
    buffer-assignment stats (``Compiled.memory_analysis()``) for one
    jitted program: argument/output/temp bytes and their aliasing.
    ``jax.live_arrays()`` cannot see inside a jitted program, so this
    is the measured-peak instrument for a *step* — it is what puts
    measured numbers next to the paper's Eq. 2-4/13-15 analytic model
    in BENCH_paper.json (benchmarks/paper_tables.py).

Like every recorder primitive the ledger is numerics-inert (pinned by
tests/test_obs_inert.py with memory tracking armed): it only ever reads
host-visible metadata (``.nbytes`` — never a device sync) and the
NullRecorder carries a no-op ``NullMemoryLedger`` so untagged processes
pay one attribute check per call site.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, Hashable, Optional

__all__ = ["MemoryLedger", "NullMemoryLedger", "tree_nbytes",
           "compiled_footprint", "device_memory_stats", "sample"]


def tree_nbytes(tree) -> int:
    """Total bytes of a pytree's array leaves.

    Reads ``.nbytes`` metadata only — never forces a transfer or sync,
    so it is safe on the hot path. Leaves without ``.nbytes`` (python
    scalars, None) contribute 0. Works on jax Arrays, numpy arrays, and
    QTensor trees alike (QTensor is a pytree of arrays).
    """
    import jax
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        nb = getattr(leaf, "nbytes", None)
        if nb is not None:
            total += int(nb)
    return total


def device_memory_stats() -> Optional[Dict[str, int]]:
    """Byte-valued ``memory_stats()`` of device 0, or None off the TPU.

    The CPU backend has no allocator stats (returns None) — callers
    must treat this as best-effort there; ``jax.live_arrays()`` is the
    portable source of truth. A TPU always reports them, so there a
    failed or empty reading raises instead of hiding a missing peak.
    """
    import jax
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    try:
        st = dev.memory_stats()
    except Exception:
        if on_tpu:
            raise
        return None
    if not st:
        if on_tpu:
            raise RuntimeError(f"{dev.device_kind} reported no memory_stats()")
        return None
    return {k: int(v) for k, v in st.items()
            if "bytes" in k and isinstance(v, (int, float))}


def compiled_footprint(fn, *args, static_argnums=(), donate_argnums=()):
    """Measured XLA buffer-assignment footprint of ``fn(*args)``.

    Lowers and compiles (without executing) and reads
    ``Compiled.memory_analysis()``:

      * ``argument_bytes`` — live inputs (params, batch, masks);
      * ``output_bytes``  — live outputs (new state, metrics);
      * ``temp_bytes``    — XLA's temp allocation: the peak of all
        intermediates (activations, ZO perturbations, tail grads) under
        its buffer-assignment liveness analysis;
      * ``alias_bytes``   — input/output aliasing (donation) credit;
      * ``peak_bytes``    — argument + output + temp - alias: what the
        device must hold to run one step.

    ``fn`` may be a plain callable (it is jitted here) or an already
    ``jax.jit``-wrapped function. Returns None if the backend offers no
    memory analysis.
    """
    import jax
    jfn = fn if isinstance(fn, jax.stages.Wrapped) else jax.jit(
        fn, static_argnums=static_argnums, donate_argnums=donate_argnums)
    ma = jfn.lower(*args).compile().memory_analysis()
    if ma is None:
        return None

    def _get(attr):
        v = getattr(ma, attr, 0)
        return int(v) if v else 0

    arg = _get("argument_size_in_bytes")
    out = _get("output_size_in_bytes")
    tmp = _get("temp_size_in_bytes")
    alias = _get("alias_size_in_bytes")
    return {"argument_bytes": arg, "output_bytes": out, "temp_bytes": tmp,
            "generated_code_bytes": _get("generated_code_size_in_bytes"),
            "alias_bytes": alias,
            "peak_bytes": arg + out + tmp - alias}


class _Region:
    """An open total-live watermark bracket; ``with led.region("x"):``.

    Reads ``peak_bytes`` / ``floor_bytes`` after exit; the ledger also
    keeps a max-merged summary per region name in its snapshot.
    """

    __slots__ = ("ledger", "name", "floor_bytes", "peak_bytes")

    def __init__(self, ledger: "MemoryLedger", name: str):
        self.ledger = ledger
        self.name = name
        self.floor_bytes = 0
        self.peak_bytes = 0

    def __enter__(self):
        led = self.ledger
        with led._lock:
            self.floor_bytes = self.peak_bytes = led.total_live
            led._open_regions.append(self)
        return self

    def __exit__(self, *exc):
        led = self.ledger
        with led._lock:
            led._open_regions.remove(self)
            r = led.regions.setdefault(
                self.name, {"count": 0, "peak_bytes": 0, "hwm_delta_bytes": 0})
            r["count"] += 1
            r["peak_bytes"] = max(r["peak_bytes"], self.peak_bytes)
            r["hwm_delta_bytes"] = max(r["hwm_delta_bytes"],
                                       self.peak_bytes - self.floor_bytes)
        return False


class _NullRegion:
    __slots__ = ()
    floor_bytes = 0
    peak_bytes = 0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_REGION = _NullRegion()


class MemoryLedger:
    """Tagged live-bytes accounting with peaks, keys, and reconciliation.

    Two registration styles:

      * ``alloc(tag, nbytes, key=...)`` / ``free(tag, key=...)`` — paired
        lifetime tracking. A ``key`` (any hashable) arms double-alloc /
        double-free detection and lets ``free`` omit the size;
        ``leaks()`` lists whatever keyed allocations are still
        outstanding.
      * ``rebind(tag, nbytes, key)`` — idempotent registration for
        long-lived buffers that are *replaced*, not freed (params after
        an optimizer step): live bytes adjust by the delta.

    All mutation happens under one lock; reads used on hot paths
    (``total_live``) are plain attribute loads.
    """

    armed = True

    def __init__(self):
        self._lock = threading.Lock()
        self.live: Dict[str, int] = {}
        self.peak: Dict[str, int] = {}
        self.total_live = 0
        self.total_peak = 0
        self.n_allocs = 0
        self.n_frees = 0
        self.regions: Dict[str, Dict[str, int]] = {}
        self.last_sample: Optional[Dict[str, Any]] = None
        self._keyed: Dict[tuple, int] = {}
        self._open_regions: list = []

    # ---- registry ----------------------------------------------------- #
    def _bump(self, tag: str, delta: int):
        v = self.live.get(tag, 0) + delta
        self.live[tag] = v
        self.total_live += delta
        if v > self.peak.get(tag, 0):
            self.peak[tag] = v
        if self.total_live > self.total_peak:
            self.total_peak = self.total_live
        for r in self._open_regions:
            if self.total_live > r.peak_bytes:
                r.peak_bytes = self.total_live

    def alloc(self, tag: str, nbytes: int, key: Hashable = None) -> int:
        nbytes = int(nbytes)
        if nbytes < 0:
            raise ValueError(f"alloc({tag!r}) with negative size {nbytes}")
        with self._lock:
            if key is not None:
                k = (tag, key)
                if k in self._keyed:
                    raise KeyError(f"double alloc of {tag}:{key!r}")
                self._keyed[k] = nbytes
            self._bump(tag, nbytes)
            self.n_allocs += 1
        return nbytes

    def free(self, tag: str, nbytes: Optional[int] = None,
             key: Hashable = None):
        with self._lock:
            if key is not None:
                k = (tag, key)
                if k not in self._keyed:
                    raise KeyError(
                        f"double free / unknown allocation {tag}:{key!r}")
                bound = self._keyed.pop(k)
                if nbytes is None:
                    nbytes = bound
                elif int(nbytes) != bound:
                    raise ValueError(
                        f"free({tag}:{key!r}) size {nbytes} != "
                        f"allocated {bound}")
            if nbytes is None:
                raise ValueError("free() needs nbytes or key")
            nbytes = int(nbytes)
            if nbytes > self.live.get(tag, 0):
                raise ValueError(
                    f"free({tag!r}) of {nbytes} bytes exceeds live "
                    f"{self.live.get(tag, 0)}")
            self._bump(tag, -nbytes)
            self.n_frees += 1

    def rebind(self, tag: str, nbytes: int, key: Hashable) -> int:
        nbytes = int(nbytes)
        if nbytes < 0:
            raise ValueError(f"rebind({tag!r}) with negative size {nbytes}")
        with self._lock:
            k = (tag, key)
            old = self._keyed.get(k)
            if old is None:
                self.n_allocs += 1
                old = 0
            self._keyed[k] = nbytes
            self._bump(tag, nbytes - old)
        return nbytes

    def region(self, name: str) -> _Region:
        return _Region(self, name)

    def leaks(self) -> Dict[str, int]:
        """Outstanding keyed allocations as {"tag:key": nbytes}."""
        with self._lock:
            return {f"{tag}:{key}": nb
                    for (tag, key), nb in sorted(
                        self._keyed.items(), key=lambda kv: str(kv[0]))}

    # ---- reconciliation ----------------------------------------------- #
    def sample(self) -> Dict[str, Any]:
        """Reconcile tagged bytes against what JAX actually holds.

        ``untagged_bytes`` is the residual: device-resident arrays no
        subsystem has claimed. It can be negative when a tag registers
        logical bytes for host-side state (e.g. the fleet ledger's wire
        records live in numpy, outside jax.live_arrays()).
        """
        import jax
        live = 0
        n = 0
        for a in jax.live_arrays():
            nb = getattr(a, "nbytes", None)
            if nb is not None:
                live += int(nb)
                n += 1
        out: Dict[str, Any] = {
            "jax_live_bytes": live, "jax_live_arrays": n,
            "tagged_bytes": self.total_live,
            "untagged_bytes": live - self.total_live,
        }
        dstats = device_memory_stats()
        if dstats is not None:
            out["device"] = dstats
        with self._lock:
            self.last_sample = out
        return out

    # ---- readback ----------------------------------------------------- #
    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "live": dict(sorted(self.live.items())),
                "peak": dict(sorted(self.peak.items())),
                "total_live_bytes": self.total_live,
                "total_peak_bytes": self.total_peak,
                "n_allocs": self.n_allocs,
                "n_frees": self.n_frees,
                "n_outstanding": len(self._keyed),
                "regions": {k: dict(v)
                            for k, v in sorted(self.regions.items())},
                "sample": dict(self.last_sample) if self.last_sample else None,
            }

    def reset(self):
        with self._lock:
            self.live.clear()
            self.peak.clear()
            self.total_live = 0
            self.total_peak = 0
            self.n_allocs = 0
            self.n_frees = 0
            self.regions.clear()
            self.last_sample = None
            self._keyed.clear()
            self._open_regions.clear()


class NullMemoryLedger:
    """The no-op twin riding NullRecorder: every call disappears."""

    armed = False
    live: Dict[str, int] = {}
    peak: Dict[str, int] = {}
    total_live = 0
    total_peak = 0

    def alloc(self, tag, nbytes, key=None):
        return 0

    def free(self, tag, nbytes=None, key=None):
        pass

    def rebind(self, tag, nbytes, key):
        return 0

    def region(self, name):
        return _NULL_REGION

    def leaks(self):
        return {}

    def sample(self):
        return None

    def snapshot(self):
        return {}

    def reset(self):
        pass


def sample() -> Optional[Dict[str, Any]]:
    """Sample + reconcile via the installed recorder; sets memory.*
    gauges (memory.tagged_bytes / jax_live_bytes / untagged_bytes).
    No-op (returns None) when no recorder is armed.
    """
    from . import get
    rec = get()
    led = rec.memory
    if not led.armed:
        return None
    s = led.sample()
    rec.gauge("memory.tagged_bytes").set(s["tagged_bytes"])
    rec.gauge("memory.jax_live_bytes").set(s["jax_live_bytes"])
    rec.gauge("memory.untagged_bytes").set(s["untagged_bytes"])
    return s
