"""Runtime concurrency sanitizer — lock-order, lockset, host-sync checks.

PRs 7-10 made the process genuinely multithreaded: serve scheduler
threads, input-service read-ahead, the statusz HTTP server, the async
checkpoint writer, export flush and autotune publisher all touch shared
registries behind hand-rolled locks. The AST lint (rules.py
TPU-LINT10x) catches the static half of that risk; this module is the
dynamic half — a TSan-flavoured, pure-Python, opt-in sanitizer:

  * **Lock-order graph** — :class:`TrackedLock` / :class:`TrackedRLock`
    (installed by the ``utils.threads`` factories when
    ``BIGDL_TPU_SANITIZE`` enables the ``locks`` mode) record, per
    thread, the stack of currently-held locks; acquiring B while
    holding A adds the edge A→B with the acquiring ``module:line``. A
    new edge that closes a cycle is a lock-order inversion — the
    classic potential deadlock — reported once per cycle with every
    edge's acquisition site.
  * **Hold times** — releasing a lock held longer than
    ``BIGDL_TPU_SANITIZE_HOLD_MS`` reports a long-hold (a lock held
    across sleeps/IO serializes every other participant).
  * **Lockset race check** — shared structures register their owning
    lock; mutation sites call :func:`check_owned`, and a mutation while
    the lock is demonstrably not held is an unlocked-write report with
    the mutating site attributed. Seeded at the observe metrics
    registry, the serve batcher queue, the statusz engine list and the
    autotune table.
  * **Host-sync sanitizer** (``sync`` mode) — wraps ``jax.device_get``
    so an un-sanctioned device→host fetch inside an instrumented span
    (``observe.span`` / ``observe.phase``) is reported and attributed to
    the innermost one.
    The legitimate fetch points (the trainer's flush fetch, the serve
    dispatch fetch, checkpoint gather, bench timing) are marked with
    :func:`sanctioned_sync` — everything else inside the hot loop is a
    silent serializer some refactor smuggled in. This turns the ad-hoc
    "monkeypatch device_get and count" test trick into a reusable
    checked mode.

Reports are plain dicts, deduplicated, capped, and surfaced three ways:
`python -m bigdl_tpu.analysis threads`, the /statusz payload, and crash
forensics bundles (observe/doctor.py writes ``sanitizer.json`` and the
doctor CLI prints the findings).

Everything here is deliberately observe-free at record time: a report
only appends to an in-process list (no locks of ours, no counters), so
the sanitizer can fire from inside any lock without deadlocking the
instrumentation it rides.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Dict, List, Optional

from bigdl_tpu.utils.threads import sanitize_modes

__all__ = ["TrackedLock", "TrackedRLock", "enable", "disable", "refresh",
           "check_owned", "register_shared", "sanctioned_sync",
           "reports", "report_payload", "reset", "LOCKS_ON", "SYNC_ON"]

# mode flags, refreshed from BIGDL_TPU_SANITIZE by refresh()/enable():
# call sites gate on `if sancov.LOCKS_ON:` — one module-attribute load
# when off, nothing else
LOCKS_ON = "locks" in sanitize_modes()
SYNC_ON = False          # set only once the device_get wrapper is installed

_MAX_REPORTS = 256
_reports: List[dict] = []
_report_keys: set = set()
_reports_lock = threading.Lock()       # raw: reporting must never recurse

_tls = threading.local()               # .held: list, .phases: list, .sanc: int

# ----------------------------------------------------- lock-order graph
_graph_lock = threading.Lock()         # raw on purpose (see module doc)
_edges: Dict[int, Dict[int, str]] = {}     # src uid -> {dst uid: site}
_uid_names: Dict[int, str] = {}
_cycles_seen: set = set()
_next_uid = [0]


def _hold_threshold_s() -> float:
    raw = os.environ.get("BIGDL_TPU_SANITIZE_HOLD_MS")
    try:
        return float(raw) / 1e3 if raw else 0.25
    except ValueError:
        return 0.25


def _site(depth: int) -> str:
    """`module:line` of the first caller frame outside this module."""
    try:
        frame = sys._getframe(depth)
        while frame is not None and \
                frame.f_globals.get("__name__", "").endswith("sancov"):
            frame = frame.f_back
        if frame is None:
            return "?"
        mod = frame.f_globals.get("__name__", "?")
        return f"{mod}:{frame.f_lineno}"
    except Exception:                      # noqa: BLE001 — attribution only
        return "?"


def _report(kind: str, key: tuple, **fields) -> bool:
    """Append one deduplicated report; returns True when it was new."""
    with _reports_lock:
        if key in _report_keys or len(_reports) >= _MAX_REPORTS:
            return False
        _report_keys.add(key)
        _reports.append({"kind": kind, "thread": threading.current_thread().name,
                         "t": time.time(), **fields})
    return True


def _held_stack() -> list:
    held = getattr(_tls, "held", None)
    if held is None:
        held = _tls.held = []
    return held


def _add_edge(src_uid: int, dst_uid: int, site: str) -> None:
    """Record src→dst; a path dst→…→src means the new edge closes a
    lock-order cycle (two threads interleaving those acquisitions can
    deadlock). Reported once per distinct lock set."""
    with _graph_lock:
        outs = _edges.setdefault(src_uid, {})
        if dst_uid in outs:
            return
        outs[dst_uid] = site
        # DFS: src reachable from dst == the new edge closes a cycle
        path = _find_path(dst_uid, src_uid)    # [dst, …, last→src]
        if path is None:
            return
        cyc_key = frozenset([src_uid] + path)
        if cyc_key in _cycles_seen:
            return
        _cycles_seen.add(cyc_key)
        edges = [{"from": _uid_names.get(src_uid, "?"),
                  "to": _uid_names.get(dst_uid, "?"), "site": site}]
        for a, b in zip(path, path[1:]):
            edges.append({"from": _uid_names.get(a, "?"),
                          "to": _uid_names.get(b, "?"),
                          "site": _edges.get(a, {}).get(b, "?")})
        edges.append({"from": _uid_names.get(path[-1], "?"),
                      "to": _uid_names.get(src_uid, "?"),
                      "site": _edges.get(path[-1], {}).get(src_uid, "?")})
    _report("lock-order-cycle",
            ("lock-order-cycle", cyc_key),
            locks=sorted(_uid_names.get(u, "?") for u in cyc_key),
            edges=edges, where=site)


def _find_path(start: int, goal: int) -> Optional[List[int]]:
    """DFS path start→goal in the edge graph (callers hold _graph_lock).
    Returns the node list [start, …] EXCLUDING goal, or None."""
    seen = set()
    stack = [(start, [start])]
    while stack:
        node, path = stack.pop()
        if node == goal:
            return path[:-1]
        if node in seen:
            continue
        seen.add(node)
        for nxt in _edges.get(node, {}):
            if nxt == goal:
                return path
            if nxt not in seen:
                stack.append((nxt, path + [nxt]))
    return None


class TrackedLock:
    """Instrumented mutex: records acquisition order, owner, and hold
    time. Drop-in for ``threading.Lock`` including use as the mutex of
    a ``threading.Condition`` (supplies ``_is_owned`` so wait/notify
    ownership checks are O(1) and allocation-free)."""

    _reentrant = False

    def __init__(self, name: str):
        self._lock = self._make()
        self.name = name
        with _graph_lock:
            self.uid = _next_uid[0]
            _next_uid[0] += 1
            _uid_names[self.uid] = name
        self._owner: Optional[int] = None
        self._count = 0
        self._acquired_at = 0.0
        self._acquisitions = 0

    @staticmethod
    def _make():
        return threading.Lock()

    # --------------------------------------------------------- lock API
    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        ok = self._lock.acquire(blocking, timeout)
        if not ok:
            return False
        me = threading.get_ident()
        if self._reentrant and self._owner == me:
            self._count += 1
            return True
        self._owner = me
        self._count = 1
        self._acquired_at = time.perf_counter()
        self._acquisitions += 1
        held = _held_stack()
        if held:
            _add_edge(held[-1].uid, self.uid, _site(2))
        held.append(self)
        return True

    def release(self) -> None:
        me = threading.get_ident()
        if self._reentrant and self._owner == me and self._count > 1:
            self._count -= 1
            self._lock.release()
            return
        held_s = time.perf_counter() - self._acquired_at
        self._owner = None
        self._count = 0
        held = _held_stack()
        if self in held:
            held.remove(self)
        if held_s > _hold_threshold_s():
            _report("long-hold", ("long-hold", self.name, _site(2)),
                    lock=self.name, held_ms=round(held_s * 1e3, 1),
                    where=_site(2))
        self._lock.release()

    def locked(self) -> bool:
        return self._lock.locked()

    def _is_owned(self) -> bool:            # threading.Condition protocol
        return self._owner == threading.get_ident()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc) -> bool:
        self.release()
        return False

    def stats(self) -> dict:
        return {"acquisitions": self._acquisitions,
                "held_now": self._owner is not None}


class TrackedRLock(TrackedLock):
    """Reentrant variant: nested acquires by the owner count instead of
    re-recording; order edges and hold time span the outermost pair."""

    _reentrant = True

    @staticmethod
    def _make():
        return threading.RLock()

    def locked(self) -> bool:
        return self._owner is not None


# --------------------------------------------------------- lockset checks
_shared: Dict[str, object] = {}        # registered structure name -> lock
_shared_lock = threading.Lock()        # raw: registration must not recurse


def register_shared(name: str, lock) -> None:
    """Declare `lock` as the owner of shared structure `name` (the
    thread-inventory CLI lists these; guards reference the lock they
    were seeded with directly)."""
    with _shared_lock:
        _shared[name] = lock


def _lock_free(lock) -> bool:
    """True when `lock` is PROVABLY not protecting the caller: a tracked
    lock not owned by this thread, or any lock nobody holds at all.
    Plain (untracked) locks held by another thread pass — conservative,
    no false positives."""
    target = getattr(lock, "_lock", lock)       # Condition -> mutex
    if isinstance(target, TrackedLock):
        return not target._is_owned()
    try:
        return not target.locked()
    except AttributeError:
        return False


def check_owned(lock, what: str) -> None:
    """Lockset race check: call at a mutation site of `what`, which the
    design says is guarded by `lock`. Reports an unlocked-write when the
    lock demonstrably is not held. Call sites gate on ``sancov.LOCKS_ON``
    so the disabled path costs one attribute load."""
    if not LOCKS_ON or not _lock_free(lock):
        return
    where = _site(2)
    _report("unlocked-write", ("unlocked-write", what, where),
            shared=what, where=where,
            lock=getattr(lock, "name", type(lock).__name__))


# -------------------------------------------------------- host-sync mode
_real_device_get = None
_phase_hook_installed = False


def _phase_stack() -> list:
    ph = getattr(_tls, "phases", None)
    if ph is None:
        ph = _tls.phases = []
    return ph


def _on_phase(name: str, entering: bool) -> None:
    ph = _phase_stack()
    if entering:
        ph.append(name)
    elif ph and ph[-1] == name:
        ph.pop()


class _Sanction:
    __slots__ = ("reason",)

    def __init__(self, reason: str):
        self.reason = reason

    def __enter__(self):
        _tls.sanc = getattr(_tls, "sanc", 0) + 1
        return self

    def __exit__(self, *exc) -> bool:
        _tls.sanc -= 1
        return False


class _NoopCtx:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NOOP = _NoopCtx()


def sanctioned_sync(reason: str = ""):
    """Mark a scope whose device→host fetches are intentional (the ONE
    fetch a subsystem is designed around). No-op singleton when the sync
    sanitizer is off."""
    if not SYNC_ON:
        return _NOOP
    return _Sanction(reason)


def _guarded_device_get(*args, **kwargs):
    if SYNC_ON and not getattr(_tls, "sanc", 0):
        ph = _phase_stack()
        if ph:
            where = _site(2)
            _report("hostsync", ("hostsync", ph[-1], where),
                    phase=ph[-1], where=where)
    return _real_device_get(*args, **kwargs)


def _install_sync_guard() -> bool:
    """Patch jax.device_get + hook observe phase spans. Requires jax;
    returns False (mode stays off) when it is not importable."""
    global _real_device_get, SYNC_ON, _phase_hook_installed
    try:
        import jax
    except Exception:                      # noqa: BLE001 — no jax, no mode
        return False
    if _real_device_get is None:
        _real_device_get = jax.device_get
    if jax.device_get is not _guarded_device_get:
        jax.device_get = _guarded_device_get
    if not _phase_hook_installed:
        from bigdl_tpu.observe import trace as _trace
        _trace.set_phase_hook(_on_phase)
        _phase_hook_installed = True
    SYNC_ON = True
    return True


def _uninstall_sync_guard() -> None:
    # _real_device_get is kept (not reset to None): a thread racing the
    # uninstall inside the wrapper must still resolve the original
    global SYNC_ON, _phase_hook_installed
    SYNC_ON = False
    if _real_device_get is not None:
        import jax
        jax.device_get = _real_device_get
    if _phase_hook_installed:
        from bigdl_tpu.observe import trace as _trace
        _trace.set_phase_hook(None)
        _phase_hook_installed = False


# ------------------------------------------------------------- lifecycle
def refresh() -> frozenset:
    """Re-read BIGDL_TPU_SANITIZE and (de)activate modes accordingly.
    Locks built BEFORE enabling stay untracked (the factories choose at
    construction) — production use sets the knob at process start."""
    global LOCKS_ON
    modes = sanitize_modes()
    LOCKS_ON = "locks" in modes
    if "sync" in modes:
        _install_sync_guard()
    elif SYNC_ON:
        _uninstall_sync_guard()
    return modes


def enable(modes: str = "1") -> frozenset:
    """Programmatic opt-in (tests): sets the env knob then refreshes."""
    os.environ["BIGDL_TPU_SANITIZE"] = modes
    return refresh()


def disable() -> None:
    os.environ.pop("BIGDL_TPU_SANITIZE", None)
    refresh()


def reports(kind: Optional[str] = None) -> List[dict]:
    with _reports_lock:
        out = [dict(r) for r in _reports]
    return [r for r in out if r["kind"] == kind] if kind else out


def report_payload() -> dict:
    """The sanitizer section statusz/forensics embed: active modes,
    per-kind counts, and the deduplicated findings."""
    all_reports = reports()
    counts: Dict[str, int] = {}
    for r in all_reports:
        counts[r["kind"]] = counts.get(r["kind"], 0) + 1
    return {"modes": sorted(sanitize_modes()), "counts": counts,
            "reports": all_reports, "shared": sorted(_shared)}


def reset() -> None:
    """Drop findings and the order graph (tests)."""
    global _edges, _cycles_seen
    with _reports_lock:
        _reports.clear()
        _report_keys.clear()
    with _graph_lock:
        _edges = {}
        _cycles_seen = set()
