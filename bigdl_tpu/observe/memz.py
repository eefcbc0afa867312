"""Device-memory observability — the HBM ledger + /memz live plane.

HBM is the scarce resource on a TPU, and the platform now fills it from
four unmetered directions at once: trainer param/slot trees (ZeRO-1),
the decode path's persistent paged KV pools, the
data service's double-buffered H2D staging, and per-program XLA
workspace. The reference treats memory as a first-class managed
resource (MKL-DNN `MemoryData` + native allocation accounting, SURVEY
§L0); this module is that discipline rebuilt for the live telemetry
plane (PR 10/12 style):

  * **Buffer ledger** — every subsystem that pins long-lived device
    memory registers its trees under a named owner
    (:meth:`BufferLedger.register`): bytes are computed host-side from
    shapes/dtypes (NEVER a device sync), surface as `mem/<owner>/bytes`
    gauges, and are weakref-finalized against an anchor object so a
    GC'd engine/trainer frees its accounting too. Owners: the trainers'
    `trainer/{params,slots,model_state}` (optim/local.py +
    parallel/distri.py `_place_trees`), `serve/<model>/params` and
    `serve/<model>/kv_cache` (serve/registry.py + serve/decode.py), and
    the input service's `data/staging` double-buffer deltas
    (dataset/prefetch.py + dataset/service.py).

  * **Backend cross-check** — `device.memory_stats()` where the backend
    reports it (TPU/GPU), with a `jax.live_arrays()` census fallback
    (CPU — host metadata only, still zero syncs). Ledger-vs-backend
    drift is itself a gauge (`mem/unattributed_bytes`): bytes the
    backend holds that no owner claims, i.e. XLA workspace + leaks.
    A baseline captured at arm time keeps framework-startup arrays out
    of the drift.

  * **/memz** — the live plane endpoint (observe/statusz.py): per-owner
    table, per-device utilization + high-water marks, top-N buffers,
    and a headroom estimate (how many more decode slots / one more
    serve model fit). Host-side state only — a scrape adds zero device
    syncs, same discipline as /statusz.

  * **Memory watchdog** — a leg on the generalized Watchdog core
    (observe/doctor.py `observe_signal`, absolute-threshold mode):
    sustained utilization above BIGDL_TPU_MEM_WATCHDOG_PCT opens ONE
    incident attributed to the FASTEST-GROWING owner (each owner's
    bytes are a component compared against its own rolling baseline),
    riding the existing alert fan-out (observe/alerts.py). Armed only
    when a capacity limit is known (backend `bytes_limit` or
    BIGDL_TPU_MEM_LIMIT_BYTES).

  * **OOM forensics** — `is_oom()` recognizes RESOURCE_EXHAUSTED;
    the optimize() and serve dispatch seams route it into
    `dump_forensics`, which writes the full ledger (`memory.json` —
    names the top owner) plus `jax.profiler.save_device_memory_profile`
    (`memory.prof`) into the bundle; `observe doctor` renders both.
    `admission_check()` refuses a registration that cannot fit
    (CapacityError with a capacity report) instead of OOMing
    mid-traffic.

CLI: `python -m bigdl_tpu.observe memz` prints the ledger table
(`--json`; rc 1 when unattributed drift exceeds `--max-drift-pct`).
Knobs: BIGDL_TPU_MEM_LEDGER / _MEM_WATCHDOG_PCT / _MEM_LIMIT_BYTES /
_MEM_DRIFT_PCT (docs/configuration.md)."""

from __future__ import annotations

import json
import logging
import time
import weakref
from typing import Dict, List, Optional, Tuple

from bigdl_tpu.utils.threads import make_lock

log = logging.getLogger("bigdl_tpu")

_TOP_BUFFERS = 10


# ------------------------------------------------------------ byte math
def leaf_nbytes(a) -> int:
    """Bytes of one array-like leaf, from host-side metadata only:
    `.nbytes` when the leaf carries it (np/jax arrays — global logical
    bytes for sharded arrays), else shape x itemsize for specs
    (ShapeDtypeStruct). Non-arrays count zero."""
    nb = getattr(a, "nbytes", None)
    if nb is not None:
        return int(nb)
    shape = getattr(a, "shape", None)
    dtype = getattr(a, "dtype", None)
    if shape is None or dtype is None:
        return 0
    import numpy as np
    n = 1
    for d in shape:
        n *= int(d)
    return n * int(np.dtype(dtype).itemsize)


def tree_nbytes(tree) -> int:
    """Total bytes of a pytree of arrays/specs (host-side, no syncs)."""
    import jax
    return sum(leaf_nbytes(a) for a in jax.tree_util.tree_leaves(tree))


def tree_buffers(tree) -> List[Tuple[str, int]]:
    """(path, bytes) per leaf, largest first — the /memz top-buffers
    table's per-owner input."""
    import jax
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    rows = [(jax.tree_util.keystr(path), leaf_nbytes(a))
            for path, a in leaves]
    rows.sort(key=lambda kv: -kv[1])
    return rows


# ------------------------------------------------------- backend probes
def backend_device_stats() -> List[dict]:
    """Per-local-device memory_stats rows (TPU/GPU report bytes_in_use /
    peak / limit; CPU reports nothing and the census below takes over).
    Reading memory_stats is a local PJRT client query — no device sync."""
    import jax
    keep = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
            "largest_alloc_size", "num_allocs")
    rows = []
    for d in jax.local_devices():
        stats = getattr(d, "memory_stats", lambda: None)()
        row = {"id": int(d.id), "kind": str(d.device_kind),
               "platform": str(d.platform)}
        if stats:
            row.update({k: int(v) for k, v in stats.items() if k in keep})
        rows.append(row)
    return rows


def device_memory_summary(device=None) -> dict:
    """Per-device memory stats dict (bytes_in_use, peak_bytes_in_use,
    bytes_limit when the backend reports them — TPU/GPU do; host CPU
    returns {}). The single source of truth behind the historical
    `utils.profile.device_memory_summary` (now a thin shim over this)."""
    import jax
    dev = device or jax.devices()[0]
    stats = getattr(dev, "memory_stats", lambda: None)()
    if not stats:
        return {}
    keep = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
            "largest_alloc_size", "num_allocs")
    return {k: int(v) for k, v in stats.items() if k in keep}


def _census_bytes() -> int:
    """Fallback backend accounting: total bytes of every live jax array
    (`jax.live_arrays()` walks a host-side weakset — zero syncs). Used
    when the backend reports no memory_stats (the CPU test mesh)."""
    import jax
    total = 0
    for a in jax.live_arrays():
        try:
            total += int(a.nbytes)
        except Exception:               # noqa: BLE001 — deleted buffer
            pass
    return total


def backend_in_use() -> Tuple[int, Optional[int], str]:
    """(bytes_in_use, bytes_limit_or_None, source): summed memory_stats
    when any local device reports them, else the live-array census
    ('live_arrays'). The limit honors BIGDL_TPU_MEM_LIMIT_BYTES first —
    the operator override that also makes the watchdog/admission
    machinery testable on backends without a real limit."""
    from bigdl_tpu.utils import config
    rows = backend_device_stats()
    in_use = sum(r.get("bytes_in_use", 0) for r in rows)
    limit = sum(r.get("bytes_limit", 0) for r in rows) or None
    source = "memory_stats"
    if not any("bytes_in_use" in r for r in rows):
        in_use = _census_bytes()
        limit = None
        source = "live_arrays"
    knob = int(config.get("MEM_LIMIT_BYTES"))
    if knob > 0:
        limit = knob
    return in_use, limit, source


# --------------------------------------------------------------- ledger
class LedgerHandle:
    """One owner's registration handle: `update(tree)` re-measures after
    a re-shard, `add_bytes(delta)` tracks streaming staging buffers,
    `close()` unregisters (the weakref finalizer's explicit twin)."""

    __slots__ = ("_ledger", "owner", "closed")

    def __init__(self, ledger: "BufferLedger", owner: str):
        self._ledger = ledger
        self.owner = owner
        self.closed = False

    def update(self, tree) -> None:
        if not self.closed:
            self._ledger._set_owner_tree(self.owner, tree)

    def set_bytes(self, nbytes: int) -> None:
        if not self.closed:
            self._ledger._set_owner_bytes(self.owner, int(nbytes))

    def add_bytes(self, delta: int) -> None:
        if not self.closed:
            self._ledger._add_owner_bytes(self.owner, int(delta))

    def update_meta(self, **meta) -> None:
        """Merge keys into the owner's meta dict — live capacity facts
        (a paged KV pool's free-block count) ride this without
        re-measuring the tree."""
        if not self.closed:
            self._ledger._update_owner_meta(self.owner, meta)

    def close(self) -> None:
        if not self.closed:
            self.closed = True
            self._ledger.unregister(self.owner)


class _NoopHandle(LedgerHandle):
    """Returned when BIGDL_TPU_MEM_LEDGER=0 — registration is free and
    inert, so call sites never branch on the knob."""

    def __init__(self, owner: str):         # noqa: super — no ledger
        self._ledger = None
        self.owner = owner
        self.closed = True

    def update(self, tree) -> None:
        pass

    def set_bytes(self, nbytes: int) -> None:
        pass

    def add_bytes(self, delta: int) -> None:
        pass

    def update_meta(self, **meta) -> None:
        pass

    def close(self) -> None:
        pass


class _Owner:
    __slots__ = ("name", "bytes", "peak_bytes", "kind", "note", "meta",
                 "since", "updates", "buffers", "finalizer")

    def __init__(self, name: str, kind: str, note: str, meta: dict):
        self.name = name
        self.bytes = 0
        self.peak_bytes = 0
        self.kind = kind
        self.note = note
        self.meta = dict(meta or {})
        self.since = time.time()
        self.updates = 0
        self.buffers: List[Tuple[str, int]] = []
        self.finalizer = None


class BufferLedger:
    """The process-wide device-memory ledger: named owners -> bytes,
    cross-checked against the backend. One instance lives in this
    module (:func:`ledger`); tests may build private ones."""

    def __init__(self):
        self._lock = make_lock("memz.ledger")
        self._owners: Dict[str, _Owner] = {}
        self._baseline: Optional[int] = None
        self._peak_in_use = 0
        self._released_bytes = 0.0

    # ----------------------------------------------------- registration
    def register(self, owner: str, tree=None, *, nbytes: Optional[int] = None,
                 anchor=None, kind: str = "", note: str = "",
                 meta: Optional[dict] = None) -> LedgerHandle:
        """Register (or update) `owner` with the bytes of `tree` (or an
        explicit `nbytes`). `anchor` attaches a weakref finalizer: when
        the anchoring object (trainer, engine, scheduler) is GC'd the
        owner is unregistered automatically, so frees are accounted
        without an explicit close. Re-registering an existing owner
        replaces its bytes and re-anchors — the failover re-shard and
        repeat-optimize() paths ride this. Never syncs a device."""
        from bigdl_tpu.utils import config
        if not config.get("MEM_LEDGER"):
            return _NoopHandle(owner)
        if self._baseline is None:
            self.set_baseline()
        with self._lock:
            o = self._owners.get(owner)
            if o is None:
                o = _Owner(owner, kind, note, meta)
                self._owners[owner] = o
            else:
                if o.finalizer is not None:
                    o.finalizer.detach()
                    o.finalizer = None
                o.kind = kind or o.kind
                o.note = note or o.note
                if meta:
                    o.meta.update(meta)
            if anchor is not None:
                o.finalizer = weakref.finalize(
                    anchor, _finalize_owner, self, owner)
        if tree is not None:
            self._set_owner_tree(owner, tree)
        elif nbytes is not None:
            self._set_owner_bytes(owner, int(nbytes))
        else:
            self._set_owner_bytes(owner, 0)
        from bigdl_tpu.observe.metrics import counter
        counter("mem/ledger/registrations").inc()
        return LedgerHandle(self, owner)

    def tracker(self, owner: str, kind: str = "staging",
                note: str = "") -> LedgerHandle:
        """Get-or-create a shared delta-tracked owner (the staging
        pipelines' entry point: several generators add/subtract into one
        `data/staging` owner; no anchor — the owner outlives them)."""
        from bigdl_tpu.utils import config
        if not config.get("MEM_LEDGER"):
            return _NoopHandle(owner)
        with self._lock:
            if owner in self._owners:
                return LedgerHandle(self, owner)
        return self.register(owner, nbytes=0, kind=kind, note=note)

    def unregister(self, owner: str) -> None:
        from bigdl_tpu.observe.metrics import counter, gauge
        with self._lock:
            o = self._owners.pop(owner, None)
            if o is None:
                return
            if o.finalizer is not None:
                o.finalizer.detach()
                o.finalizer = None
            self._released_bytes += max(0, o.bytes)
        gauge(f"mem/{owner}/bytes").set(0.0)
        counter("mem/ledger/releases").inc()
        counter("mem/ledger/released_bytes").inc(max(0, o.bytes))
        self._refresh_totals()

    # ------------------------------------------------------- mutation
    def _set_owner_tree(self, owner: str, tree) -> None:
        bufs = tree_buffers(tree)
        self._set_owner_bytes(owner, sum(b for _, b in bufs),
                              buffers=bufs)

    def _set_owner_bytes(self, owner: str, nbytes: int,
                         buffers: Optional[List] = None) -> None:
        from bigdl_tpu.observe.metrics import gauge
        with self._lock:
            o = self._owners.get(owner)
            if o is None:
                return
            o.bytes = int(nbytes)
            o.peak_bytes = max(o.peak_bytes, o.bytes)
            o.updates += 1
            if buffers is not None:
                o.buffers = buffers[:_TOP_BUFFERS]
        gauge(f"mem/{owner}/bytes").set(float(nbytes))
        self._refresh_totals()

    def _update_owner_meta(self, owner: str, meta: dict) -> None:
        with self._lock:
            o = self._owners.get(owner)
            if o is not None:
                o.meta.update(meta)

    def _add_owner_bytes(self, owner: str, delta: int) -> None:
        from bigdl_tpu.observe.metrics import gauge
        with self._lock:
            o = self._owners.get(owner)
            if o is None:
                return
            o.bytes = max(0, o.bytes + int(delta))
            o.peak_bytes = max(o.peak_bytes, o.bytes)
            o.updates += 1
            nb = o.bytes
        gauge(f"mem/{owner}/bytes").set(float(nb))
        self._refresh_totals()

    def _refresh_totals(self) -> None:
        from bigdl_tpu.observe.metrics import gauge
        with self._lock:
            total = sum(o.bytes for o in self._owners.values())
            n = len(self._owners)
        gauge("mem/ledger/total_bytes").set(float(total))
        gauge("mem/ledger/owners").set(float(n))

    # --------------------------------------------------------- queries
    def total_bytes(self) -> int:
        with self._lock:
            return sum(o.bytes for o in self._owners.values())

    def owners(self) -> Dict[str, dict]:
        with self._lock:
            return {name: {"bytes": o.bytes, "peak_bytes": o.peak_bytes,
                           "kind": o.kind, "note": o.note,
                           "meta": dict(o.meta),
                           "since_unix": round(o.since, 3),
                           "updates": o.updates}
                    for name, o in sorted(self._owners.items())}

    def top_owner(self) -> Optional[Tuple[str, int]]:
        with self._lock:
            if not self._owners:
                return None
            name, o = max(self._owners.items(), key=lambda kv: kv[1].bytes)
            return (name, o.bytes)

    def top_buffers(self, n: int = _TOP_BUFFERS) -> List[dict]:
        rows: List[dict] = []
        with self._lock:
            for name, o in self._owners.items():
                for path, nb in o.buffers:
                    rows.append({"owner": name, "path": path, "bytes": nb})
        rows.sort(key=lambda r: -r["bytes"])
        return rows[:n]

    def set_baseline(self) -> int:
        """Capture the CURRENT backend in-use bytes (minus what the
        ledger already claims) as the drift baseline — framework startup
        arrays and test scaffolding stay out of `unattributed_bytes`."""
        in_use, _, _ = backend_in_use()
        base = max(0, in_use - self.total_bytes())
        with self._lock:
            self._baseline = base
        return base

    def utilization(self) -> dict:
        """The backend-vs-ledger headline (all host-side): in-use bytes,
        limit + percent when a limit is known, the drift gauge's inputs.
        Called by /memz, the /statusz memory section, and the watchdog
        poll — each call refreshes the `mem/...` cross-check gauges."""
        from bigdl_tpu.observe.metrics import gauge
        in_use, limit, source = backend_in_use()
        with self._lock:
            baseline = self._baseline or 0
            self._peak_in_use = max(self._peak_in_use, in_use)
            peak = self._peak_in_use
        ledger_total = self.total_bytes()
        unattributed = in_use - baseline - ledger_total
        util_pct = (100.0 * in_use / limit) if limit else None
        gauge("mem/backend/bytes_in_use").set(float(in_use))
        gauge("mem/backend/peak_bytes").set(float(peak))
        if limit:
            gauge("mem/backend/bytes_limit").set(float(limit))
            gauge("mem/utilization_pct").set(util_pct)
        gauge("mem/unattributed_bytes").set(float(unattributed))
        out = {
            "bytes_in_use": in_use,
            "peak_bytes": peak,
            "bytes_limit": limit,
            "utilization_pct": (round(util_pct, 2)
                                if util_pct is not None else None),
            "source": source,
            "ledger_bytes": ledger_total,
            "baseline_bytes": baseline,
            "unattributed_bytes": unattributed,
            "unattributed_pct": (
                round(100.0 * unattributed / in_use, 2) if in_use else 0.0),
        }
        return out

    def headroom(self) -> dict:
        """Capacity planning from the ledger: free bytes against the
        limit (None when no limit is known), each decode KV pool's live
        free-block count, and whether one more copy of the largest serve
        model's params fits."""
        util = self.utilization()
        limit = util["bytes_limit"]
        free = (limit - util["bytes_in_use"]) if limit else None
        kv_pools: Dict[str, dict] = {}
        largest_model = None
        with self._lock:
            for name, o in self._owners.items():
                if o.kind == "kv_pool":
                    # decode pools: headroom is the pool's own LIVE
                    # free-block count (serve/decode.py keeps the meta
                    # current), not a closed-form byte estimate
                    kv_pools[name] = {
                        "blocks": o.meta.get("blocks"),
                        "blocks_free": o.meta.get("blocks_free"),
                        "block_tokens": o.meta.get("block"),
                        "bytes_per_block": o.meta.get("bytes_per_block"),
                    }
                if o.kind == "params" and name.startswith("serve/"):
                    if largest_model is None or o.bytes > largest_model[1]:
                        largest_model = (name, o.bytes)
        out = {"free_bytes": free, "kv_pools": kv_pools or None}
        if largest_model is not None:
            out["one_more_model"] = {
                "model": largest_model[0], "bytes": largest_model[1],
                "fits": (free >= largest_model[1]
                         if free is not None else None),
            }
        return out

    # --------------------------------------------------------- payloads
    def payload(self) -> dict:
        """The /memz JSON: owner table + per-device stats + utilization
        + top buffers + headroom. Host-side only (zero device syncs)."""
        from bigdl_tpu.utils import config
        util = self.utilization()
        top = self.top_owner()
        wd = _mem_watchdog
        return {
            "ts": time.time(),
            "ledger_enabled": bool(config.get("MEM_LEDGER")),
            "owners": self.owners(),
            "total_bytes": util["ledger_bytes"],
            "utilization": util,
            "devices": backend_device_stats(),
            "top_owner": (
                {"owner": top[0], "bytes": top[1]} if top else None),
            "top_buffers": self.top_buffers(),
            "headroom": self.headroom(),
            "watchdog": wd.summary() if wd is not None else None,
        }

    def status_section(self) -> dict:
        """The compact `memory` section of /statusz — the per-peer rows
        /fleetz merges (observe/fleet.py)."""
        util = self.utilization()
        top = self.top_owner()
        head = self.headroom()
        return {
            "ledger_bytes": util["ledger_bytes"],
            "owners": len(self._owners),
            "bytes_in_use": util["bytes_in_use"],
            "bytes_limit": util["bytes_limit"],
            "utilization_pct": util["utilization_pct"],
            "unattributed_bytes": util["unattributed_bytes"],
            "top_owner": top[0] if top else None,
            "top_owner_bytes": top[1] if top else 0,
            "headroom_bytes": head["free_bytes"],
        }

    def reset(self) -> None:
        """Drop every owner + the baseline (tests)."""
        with self._lock:
            for o in self._owners.values():
                if o.finalizer is not None:
                    o.finalizer.detach()
            self._owners.clear()
            self._baseline = None
            self._peak_in_use = 0
            self._released_bytes = 0.0


def _finalize_owner(ledger: BufferLedger, owner: str) -> None:
    # weakref.finalize callback: the anchoring object died — its device
    # trees are (about to be) freed, so the accounting follows
    ledger.unregister(owner)


_LEDGER = BufferLedger()


def ledger() -> BufferLedger:
    return _LEDGER


def reset() -> None:
    """Drop ledger owners + the memory watchdog (tests)."""
    stop_memory_watchdog()
    _LEDGER.reset()


# ------------------------------------------------------ memory watchdog
class MemoryWatchdog:
    """Sustained-high-utilization detector on the generalized Watchdog
    core (observe/doctor.py, absolute-threshold mode): each poll feeds
    utilization-% as the signal and every owner's bytes (MB) — plus the
    unattributed remainder — as attribution components. Utilization
    held above BIGDL_TPU_MEM_WATCHDOG_PCT for `sustain` polls opens ONE
    incident naming the FASTEST-GROWING owner (the component that grew
    the most over its own rolling baseline), fanned out through
    observe/alerts.py like every other incident. Polls are skipped
    entirely when no capacity limit is known."""

    def __init__(self, pct: Optional[float] = None,
                 window: Optional[int] = None,
                 sustain: Optional[int] = None):
        from bigdl_tpu.observe.doctor import Watchdog
        from bigdl_tpu.utils import config
        self.pct = (float(config.get("MEM_WATCHDOG_PCT")) if pct is None
                    else pct)
        self._dog = Watchdog(self.pct, window, sustain,
                             prefix="watchdog/memory",
                             signal="mem_utilization_pct",
                             gauge_names=("utilization_pct",
                                          "baseline_pct"),
                             default_blame="unattributed",
                             absolute=True)
        self._polls = 0

    @property
    def enabled(self) -> bool:
        return self.pct > 0

    def poll(self) -> Optional[dict]:
        """One watchdog observation (the PeriodicWorker drives it on the
        fleet/export cadence; tests call it directly). Returns the
        incident when THIS poll opened one."""
        if not self.enabled:
            return None
        util = _LEDGER.utilization()
        if util["utilization_pct"] is None:
            return None                  # no limit -> no signal
        self._polls += 1
        comps = {name: o["bytes"] / 1e6
                 for name, o in _LEDGER.owners().items()}
        comps["unattributed"] = max(0, util["unattributed_bytes"]) / 1e6
        top = _LEDGER.top_owner()
        return self._dog.observe_signal(
            self._polls, util["utilization_pct"], comps,
            extra={"bytes_in_use": util["bytes_in_use"],
                   "bytes_limit": util["bytes_limit"],
                   "top_owner": top[0] if top else None})

    def active_alert(self) -> Optional[dict]:
        return self._dog.active_alert()

    def alerts(self) -> List[dict]:
        return self._dog.alerts()

    def summary(self) -> dict:
        totals = self._dog.incident_totals()
        active = self._dog.active_alert()
        out = {"enabled": self.enabled, "threshold_pct": self.pct,
               "polls": self._polls,
               "alert_active": active is not None,
               "incidents_total": totals["total"],
               "incidents_dropped": totals["dropped"]}
        if active:
            out["owner"] = active.get("phase")
            out["utilization_pct"] = active.get("value")
        return out


_mem_watchdog: Optional[MemoryWatchdog] = None
_mem_poller = None
_mem_lock = make_lock("memz.watchdog")


def memory_watchdog() -> MemoryWatchdog:
    """The process-wide memory watchdog (knobs read at first use)."""
    global _mem_watchdog
    if _mem_watchdog is None:
        with _mem_lock:
            if _mem_watchdog is None:
                _mem_watchdog = MemoryWatchdog()
    return _mem_watchdog


def watchdog_active() -> bool:
    wd = _mem_watchdog
    return bool(wd is not None and wd.active_alert() is not None)


def arm_memory_watchdog() -> bool:
    """Start the memory-watchdog poller (idempotent;
    observe.ensure_started() calls this). Armed only when the knob is
    on AND a capacity limit is resolvable — on a limit-less backend
    (the CPU test mesh without BIGDL_TPU_MEM_LIMIT_BYTES) no thread is
    spawned at all."""
    global _mem_poller
    from bigdl_tpu.utils import config
    wd = memory_watchdog()
    if not wd.enabled:
        return False
    _, limit, _ = backend_in_use()
    if not limit:
        return False
    with _mem_lock:
        if _mem_poller is None:
            from bigdl_tpu.utils.threads import PeriodicWorker
            interval = (config.get("FLEET_POLL_S")
                        or config.get("METRICS_FLUSH_S"))
            _mem_poller = PeriodicWorker(
                lambda: memory_watchdog().poll(),
                interval, name="memory-watchdog")
    return True


def stop_memory_watchdog() -> None:
    """Join the poller and drop the singleton (shutdown path + tests;
    swap under the lock, join outside it — docs/concurrency.md)."""
    global _mem_poller, _mem_watchdog
    with _mem_lock:
        poller, _mem_poller = _mem_poller, None
        _mem_watchdog = None
    if poller is not None:
        poller.stop()


def ensure_started() -> None:
    """Arm the memory plane from the knobs (observe.ensure_started()
    calls this once per optimize()/engine): capture the drift baseline
    on first use and start the watchdog poller when it can run."""
    from bigdl_tpu.utils import config
    if not config.get("MEM_LEDGER"):
        return
    if _LEDGER._baseline is None:
        _LEDGER.set_baseline()
    arm_memory_watchdog()


# --------------------------------------------------------- OOM handling
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Resource exhausted",
                "Out of memory", "out of memory", "OOM")


def is_oom(exc: Optional[BaseException]) -> bool:
    """Does this exception smell like a device allocation failure? XLA
    surfaces RESOURCE_EXHAUSTED through XlaRuntimeError (and sometimes
    plain RuntimeError) — matched on the message, so the seams need no
    jaxlib-version-specific exception imports."""
    if exc is None:
        return False
    msg = f"{type(exc).__name__}: {exc}"
    return any(m in msg for m in _OOM_MARKERS)


def oom_report() -> dict:
    """The forensics `memory.json` payload: the full /memz ledger plus
    the top-owner headline a post-mortem reads first."""
    p = _LEDGER.payload()
    top = p.get("top_owner")
    p["headline"] = (
        f"top owner {top['owner']} holds {top['bytes']:,} bytes of "
        f"{p['total_bytes']:,} ledgered "
        f"({p['utilization']['bytes_in_use']:,} in use on the backend)"
        if top else "ledger empty — nothing registered an owner")
    return p


def save_memory_profile(path: str) -> Optional[str]:
    """Best-effort `jax.profiler.save_device_memory_profile` (the pprof
    the OOM post-mortem opens); returns the path or None."""
    try:
        import jax.profiler as _prof
        _prof.save_device_memory_profile(path)
        from bigdl_tpu.observe.metrics import counter
        counter("mem/profiles_saved").inc()
        return path
    except Exception as e:               # noqa: BLE001 — forensics
        log.warning("memz: device memory profile failed: %s", e)
        return None


class CapacityError(RuntimeError):
    """Admission refusal: a registration asked for more device memory
    than the remaining headroom. Raised BEFORE allocation with a
    capacity report — the loud alternative to OOMing mid-traffic."""


def admission_check(need_bytes: int, what: str) -> None:
    """Refuse `what` when `need_bytes` exceeds the free headroom
    (limit - in_use). A no-op when no capacity limit is known (the
    default CPU test mesh) or the ledger is off — real chips and
    BIGDL_TPU_MEM_LIMIT_BYTES arm it."""
    from bigdl_tpu.utils import config
    if not config.get("MEM_LEDGER"):
        return
    util = _LEDGER.utilization()
    limit = util["bytes_limit"]
    if not limit:
        return
    free = limit - util["bytes_in_use"]
    if need_bytes <= free:
        return
    from bigdl_tpu.observe.metrics import counter
    counter("mem/admission_refused").inc()
    top = _LEDGER.top_owner()
    raise CapacityError(
        f"{what} needs {need_bytes:,} bytes but only {max(0, free):,} of "
        f"the {limit:,}-byte device budget remain "
        f"({util['bytes_in_use']:,} in use; ledger claims "
        f"{util['ledger_bytes']:,}"
        + (f", top owner {top[0]} = {top[1]:,}" if top else "")
        + f"; unattributed {util['unattributed_bytes']:,}). "
        f"Free capacity (unregister a model, shrink num_slots/"
        f"max_seq_len) or raise the budget — see /memz for the "
        f"full per-owner table")


# -------------------------------------------------------------- the CLI
def _fmt_bytes(n) -> str:
    if n is None:
        return "-"
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0 or unit == "TiB":
            return (f"{n:,.0f} {unit}" if unit == "B"
                    else f"{n:,.1f} {unit}")
        n /= 1024.0
    return f"{n:,.1f} TiB"


def render_table(payload: dict) -> str:
    """The human form of the /memz payload (CLI + doctor)."""
    util = payload["utilization"]
    lines = [
        f"device memory · ledger "
        f"{'on' if payload['ledger_enabled'] else 'OFF'} · backend "
        f"{util['source']}",
        f"in use {_fmt_bytes(util['bytes_in_use'])}"
        + (f" of {_fmt_bytes(util['bytes_limit'])} "
           f"({util['utilization_pct']}%)" if util["bytes_limit"]
           else " (no capacity limit reported)")
        + f" · peak {_fmt_bytes(util['peak_bytes'])}",
        f"ledger {_fmt_bytes(util['ledger_bytes'])} across "
        f"{len(payload['owners'])} owner(s) · baseline "
        f"{_fmt_bytes(util['baseline_bytes'])} · unattributed "
        f"{_fmt_bytes(util['unattributed_bytes'])} "
        f"({util['unattributed_pct']}% of in-use)",
        "",
        f"{'owner':<36} {'bytes':>12} {'peak':>12} {'kind':<12} updates",
    ]
    lines.append("-" * len(lines[-1]))
    for name, o in payload["owners"].items():
        lines.append(f"{name:<36} {_fmt_bytes(o['bytes']):>12} "
                     f"{_fmt_bytes(o['peak_bytes']):>12} "
                     f"{o['kind'] or '-':<12} {o['updates']}")
    if not payload["owners"]:
        lines.append("(no owners registered)")
    top = payload.get("top_buffers") or []
    if top:
        lines.append("\ntop buffers:")
        for r in top[:5]:
            lines.append(f"  {r['owner']}{r['path']:<32} "
                         f"{_fmt_bytes(r['bytes'])}")
    head = payload.get("headroom") or {}
    if head.get("free_bytes") is not None:
        lines.append(f"\nheadroom: {_fmt_bytes(head['free_bytes'])} free")
        for name, d in (head.get("kv_pools") or {}).items():
            lines.append(
                f"  {name}: {d['blocks_free']} of {d['blocks']} KV blocks "
                f"free")
        om = head.get("one_more_model")
        if om:
            lines.append(f"  one more {om['model']} "
                         f"({_fmt_bytes(om['bytes'])}): "
                         f"{'fits' if om['fits'] else 'does NOT fit'}")
    return "\n".join(lines)


def memz_main(argv: Optional[List[str]] = None) -> int:
    """`python -m bigdl_tpu.observe memz [--json] [--smoke]
    [--max-drift-pct X]` — print this process's ledger table; rc 1 when
    the unattributed drift exceeds the threshold (default
    BIGDL_TPU_MEM_DRIFT_PCT). `--smoke` stands up a demo ledger (a
    trainer-shaped tree + a decode-shaped KV bucket of real device
    arrays) first — the tier-1 CI canary for the whole accounting
    path."""
    import argparse
    from bigdl_tpu.utils import config
    ap = argparse.ArgumentParser(
        prog="bigdl_tpu.observe memz",
        description="Device-memory ledger: per-owner table, backend "
                    "cross-check, headroom (the CLI twin of /memz)")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="register demo owners (real arrays) before "
                         "printing — exercises ledger + drift end to end")
    ap.add_argument("--max-drift-pct", type=float, default=None,
                    help="rc 1 when |unattributed| exceeds this percent "
                         "of backend in-use (default "
                         "BIGDL_TPU_MEM_DRIFT_PCT)")
    args = ap.parse_args(argv)
    threshold = (float(config.get("MEM_DRIFT_PCT"))
                 if args.max_drift_pct is None else args.max_drift_pct)
    keepalive = []
    if args.smoke:
        import jax.numpy as jnp
        _LEDGER.set_baseline()
        params = {"w": jnp.zeros((256, 256), jnp.float32),
                  "b": jnp.zeros((256,), jnp.float32)}
        kv = tuple(jnp.zeros((4, 64, 4, 8), jnp.float32)
                   for _ in range(4))
        keepalive.extend([params, kv])
        ledger().register("trainer/params", params, kind="params",
                          note="memz smoke")
        ledger().register("serve/demo/kv_cache", kv, kind="kv_cache",
                          meta={"slots": 4, "max_seq_len": 64},
                          note="memz smoke")
    p = _LEDGER.payload()
    drift_pct = abs(p["utilization"]["unattributed_pct"])
    ok = drift_pct <= threshold
    if args.smoke:
        # the smoke also asserts the owners actually landed
        ok = ok and "trainer/params" in p["owners"] \
            and "serve/demo/kv_cache" in p["owners"] \
            and p["owners"]["serve/demo/kv_cache"]["bytes"] == \
            4 * 4 * 64 * 4 * 8 * 4
    if args.json:
        print(json.dumps({"ok": ok, "drift_pct": drift_pct,
                          "threshold_pct": threshold, **p},
                         default=str))
    else:
        print(render_table(p))
        print(f"\ndrift check: {drift_pct}% unattributed vs "
              f"{threshold}% threshold -> {'OK' if ok else 'FAIL'}")
    return 0 if ok else 1
