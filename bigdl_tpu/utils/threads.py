"""Sanctioned thread/lock construction — the one place bigdl_tpu spawns.

Seventeen modules grew hand-rolled ``threading`` usage across PRs 7-10
(serve scheduler, input-service read-ahead, statusz HTTP, async
checkpoint writer, export flush, autotune publisher). This module is the
single sanctioned doorway for all of them, enforced by lint rule
TPU-LINT101 (raw ``threading.Thread`` outside this file is an error):

  * :func:`spawn` — create-and-start a named thread, registered in a
    process-wide inventory (``python -m bigdl_tpu.analysis threads``
    dumps it) with the spawning module recorded. Threads are daemonic by
    default — the repo-wide discipline is daemon=True PLUS an explicit
    join on the owner's clean-shutdown path, so an abrupt interpreter
    exit never hangs and a graceful one never leaks work.
  * :func:`make_lock` / :func:`make_rlock` / :func:`make_condition` —
    lock factories that return plain ``threading`` primitives normally
    and sanitizer-instrumented wrappers when ``BIGDL_TPU_SANITIZE`` is
    set (analysis/sancov.py: lock-order graph, hold times, lockset race
    checks). The default path constructs the stock primitive directly —
    zero added cost when the knob is off.

The inventory holds weak references only — it never keeps a thread or
lock alive — and is itself guarded by a raw ``threading.Lock`` (the
guard below every guard has to be unwrapped, or instrumenting would
recurse).
"""

from __future__ import annotations

import os
import sys
import threading
import time
import weakref
from typing import Callable, List, Optional

__all__ = ["spawn", "make_lock", "make_rlock", "make_condition",
           "thread_inventory", "lock_inventory", "sanitize_modes",
           "PeriodicWorker"]

# raw primitives on purpose: the inventory must never route through the
# instrumented path it implements
_registry_lock = threading.Lock()
_threads: List[dict] = []        # {"ref": weakref, "meta": {...}}
_locks: List[dict] = []
_MAX_DEAD_SCAN = 512             # compact the lists opportunistically


def sanitize_modes() -> frozenset:
    """The active sanitizer modes from BIGDL_TPU_SANITIZE: empty set
    (off, the default), {'locks','sync'} for '1'/'true'/'all', or the
    comma-separated subset named by the knob. Read from the environment
    every call — tests toggle it — but callers on hot paths cache the
    result at construction time."""
    raw = (os.environ.get("BIGDL_TPU_SANITIZE") or "").strip().lower()
    if raw in ("", "0", "false", "no", "off"):
        return frozenset()
    if raw in ("1", "true", "yes", "on", "all"):
        return frozenset(("locks", "sync"))
    return frozenset(m.strip() for m in raw.split(",") if m.strip())


def _caller_module(depth: int = 2) -> str:
    try:
        frame = sys._getframe(depth)
        return frame.f_globals.get("__name__", "?")
    except Exception:                       # noqa: BLE001 — inventory only
        return "?"


def _compact(entries: List[dict]) -> None:
    if len(entries) > _MAX_DEAD_SCAN:
        entries[:] = [e for e in entries if e["ref"]() is not None]


# ------------------------------------------------------------------ threads
def spawn(target: Callable, *, name: str, daemon: bool = True,
          args: tuple = (), kwargs: Optional[dict] = None,
          start: bool = True) -> threading.Thread:
    """Create (and by default start) a background thread.

    `name` is mandatory — an anonymous thread in a stack dump is a
    debugging dead end. The spawning module and purpose land in the
    inventory `python -m bigdl_tpu.analysis threads` prints. Pass
    ``daemon=False`` only for threads the caller joins immediately
    (e.g. the autotune trace-state hop)."""
    t = threading.Thread(target=target, name=name, args=args,
                         kwargs=kwargs or {}, daemon=daemon)
    meta = {"name": name, "daemon": daemon, "owner": _caller_module(),
            "created": time.time()}
    with _registry_lock:
        _compact(_threads)
        _threads.append({"ref": weakref.ref(t), "meta": meta})
    if start:
        t.start()
    return t


class PeriodicWorker:
    """A sanctioned periodic background caller: `fn()` every
    `interval_s` seconds on a named daemon thread until :meth:`stop`.

    This is the shared shape of every telemetry-plane poller (export
    flush, fleet aggregation, serve-SLO watchdog): an ``Event.wait``
    cadence (interruptible, never a bare ``sleep``), exceptions logged
    and swallowed (a poller must not die of one bad poll), and an
    explicit join on the owner's clean-shutdown path
    (docs/concurrency.md)."""

    def __init__(self, fn: Callable[[], None], interval_s: float, *,
                 name: str, start: bool = True):
        self._fn = fn
        self.interval_s = max(0.05, float(interval_s))
        self.name = name
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        if start:
            self.start()

    def start(self) -> "PeriodicWorker":
        if self._thread is None:
            self._thread = spawn(self._run, name=self.name)
        return self

    def _run(self) -> None:
        import logging
        log = logging.getLogger("bigdl_tpu")
        while not self._stop.wait(self.interval_s):
            try:
                self._fn()
            except Exception as e:       # noqa: BLE001 — poller survives
                log.warning("%s: periodic poll failed: %s", self.name, e)

    def tick(self) -> None:
        """Run one poll inline (tests / CLI smokes drive the cadence
        synchronously instead of waiting on the thread)."""
        self._fn()

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=timeout)
        self._thread = None

    @property
    def alive(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive()


def thread_inventory() -> List[dict]:
    """Every live thread spawned through :func:`spawn`: name, owner
    module, daemon flag, liveness, age."""
    now = time.time()
    out = []
    with _registry_lock:
        entries = list(_threads)
    for e in entries:
        t = e["ref"]()
        if t is None:
            continue
        out.append({**e["meta"], "alive": t.is_alive(),
                    "ident": t.ident,
                    "age_s": round(now - e["meta"]["created"], 3)})
    return out


# -------------------------------------------------------------------- locks
def _register_lock(obj, kind: str, name: str) -> None:
    meta = {"name": name, "kind": kind, "owner": _caller_module(3),
            "tracked": type(obj).__module__.endswith("sancov")}
    with _registry_lock:
        _compact(_locks)
        _locks.append({"ref": weakref.ref(obj), "meta": meta})


def make_lock(name: str) -> threading.Lock:
    """A named mutex: stock ``threading.Lock`` normally, the sanitizer's
    TrackedLock when BIGDL_TPU_SANITIZE enables the 'locks' mode."""
    if "locks" in sanitize_modes():
        from bigdl_tpu.analysis import sancov
        lock = sancov.TrackedLock(name)
    else:
        lock = threading.Lock()
    _register_lock(lock, "lock", name)
    return lock


def make_rlock(name: str) -> threading.RLock:
    if "locks" in sanitize_modes():
        from bigdl_tpu.analysis import sancov
        lock = sancov.TrackedRLock(name)
    else:
        lock = threading.RLock()
    _register_lock(lock, "rlock", name)
    return lock


def make_condition(name: str) -> threading.Condition:
    """A named condition variable. Under the sanitizer the underlying
    mutex is a TrackedLock, so wait/notify cycles feed the same
    acquisition-order graph as plain ``with lock:`` scopes."""
    if "locks" in sanitize_modes():
        from bigdl_tpu.analysis import sancov
        cv = threading.Condition(sancov.TrackedLock(name))
    else:
        cv = threading.Condition()
    _register_lock(cv, "condition", name)
    return cv


def lock_inventory() -> List[dict]:
    """Every live lock built through the factories, with live sanitizer
    state (holder, acquisition count) when tracked."""
    out = []
    with _registry_lock:
        entries = list(_locks)
    for e in entries:
        obj = e["ref"]()
        if obj is None:
            continue
        row = dict(e["meta"])
        target = getattr(obj, "_lock", obj)    # Condition -> its mutex
        if hasattr(target, "stats"):           # sancov.TrackedLock
            row.update(target.stats())
        out.append(row)
    return out
