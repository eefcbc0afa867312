"""Host-side span tracer — the flight recorder's timeline.

The reference logs per-phase wall times through `optim/Metrics.scala`
accumulators and leaves the timeline to the driver log; with fused
dispatch (PR 2) and async checkpointing (PR 3) the train loop has five
asynchronous moving parts (host batch assembly, H2D placement, K-step
scan dispatch, metric flush, background snapshot writer) and a log line
cannot show which one a slow step waited on. This tracer records spans
from EVERY thread into one lock-free ring buffer and emits standard
Chrome/Perfetto `trace_event` JSON, so `chrome://tracing` / ui.perfetto.dev
renders the actual interleaving.

Design constraints, in order:

  * **Always on, always bounded.** A flight recorder that is off when the
    incident happens records nothing, so every span lands in the ring.
    Events append to a `collections.deque(maxlen=ring)` — atomic under
    the GIL, and the bounded ring means the recorder can never eat the
    heap (the oldest spans fall off). `BIGDL_TPU_TRACE` only names where
    `observe.finish()` dumps the ring as Chrome JSON.
  * **One primitive, one clock read per edge.** A span (`span()`, or
    `phase()` for one that also feeds the `phase/<name>` histogram)
    appends one complete event to the ring, enters a
    `jax.profiler.TraceAnnotation` (kept by the profiler only while a
    capture is live, so a `jax.profiler.trace` capture shows these host
    spans aligned with the XLA device timeline), and records its seconds
    into its histogram where it has one.
  * **Monotonic clocks.** Timestamps are `time.perf_counter_ns()`; the
    Chrome export writes them as deltas from the tracer's start, and the
    wall-clock anchor rides the metadata so traces from different hosts
    can still be lined up.
  * **Collections are spans too.** A `gc.callbacks` hook (installed when
    `bigdl_tpu.observe` is imported) records each collection as the span
    `process/gc` and adds its pause to the counter `process/gc_pause_s`
    and the histogram `phase/process/gc`: a collection stops every
    thread, whichever one ran it.
"""

from __future__ import annotations

import gc
import json
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Iterable, List, Optional

from bigdl_tpu.observe import metrics as _metrics

# concurrency-sanitizer hook (analysis/sancov.py): when the sync mode is
# on it installs a fn(name, entering) here so device->host fetches can
# be attributed to the innermost live span; None costs one load
_phase_hook: Optional[Callable[[str, bool], None]] = None


def set_phase_hook(fn: Optional[Callable[[str, bool], None]]) -> None:
    global _phase_hook
    _phase_hook = fn


_Annotation = None      # jax.profiler.TraceAnnotation, resolved at first use


def _annotation_class():
    global _Annotation
    from jax.profiler import TraceAnnotation as _Annotation
    return _Annotation


class _Span:
    """One host phase. `t0_ns` and `dur_ns` (perf_counter nanoseconds)
    are readable once the span has closed; `args` may be filled in while
    it is open (it is read when it closes)."""

    __slots__ = ("_tracer", "name", "cat", "args", "_hist", "_ann",
                 "t0_ns", "dur_ns")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args,
                 hist=None):
        self._tracer = tracer
        self.name, self.cat, self.args = name, cat, args
        self._hist = hist

    def __enter__(self):
        # the annotation's cost is inside the span, so that the leaves of
        # a span tile it but for the few bytecodes between them
        self.t0_ns = time.perf_counter_ns()
        if _phase_hook is not None:
            _phase_hook(self.name, True)
        ann = self._ann = (_Annotation or _annotation_class())(self.name)
        ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        if _phase_hook is not None:
            _phase_hook(self.name, False)
        dur = self.dur_ns = time.perf_counter_ns() - self.t0_ns
        if self._hist is not None:
            self._hist.record(dur * 1e-9)
        self._tracer.record(self.name, self.cat, self.t0_ns, dur,
                            self.args)
        return False


class Tracer:
    """Ring-buffered span recorder. One process-wide instance lives in
    this module (`get_tracer()`); tests may build private ones."""

    def __init__(self, ring: int = 100_000):
        self.trace_dir: Optional[str] = None   # where dump() writes
        self._ring = ring
        self._events: deque = deque(maxlen=ring)
        self._thread_names: Dict[int, str] = {}
        self._t0_ns = time.perf_counter_ns()
        self._wall0 = time.time()

    # ------------------------------------------------------------- control
    def configure(self, trace_dir: Optional[str] = None,
                  ring: Optional[int] = None) -> None:
        """Name the directory `dump()` writes to (None: no dump) and,
        where given, resize the ring (its newest events are kept)."""
        if ring and ring != self._ring:
            self._ring = ring
            self._events = deque(self._events, maxlen=ring)
        self.trace_dir = trace_dir

    def clear(self) -> None:
        self._events.clear()

    # ------------------------------------------------------------ recording
    def span(self, name: str, cat: str = "host",
             args: Optional[dict] = None) -> _Span:
        """Context manager timing a host phase into this ring."""
        return _Span(self, name, cat, args)

    def record(self, name: str, cat: str, t0_ns: int, dur_ns: int,
               args: Optional[dict] = None) -> None:
        """Append one complete ('X') event; called by _Span.__exit__ and
        by instrumentation that timed a phase itself."""
        tid = threading.get_ident()
        if tid not in self._thread_names:
            self._thread_names[tid] = threading.current_thread().name
        self._events.append(("X", name, cat, tid, t0_ns, dur_ns, args))

    def instant(self, name: str, cat: str = "host",
                args: Optional[dict] = None) -> None:
        """Zero-duration marker (fault injected, retry, preemption...)."""
        tid = threading.get_ident()
        if tid not in self._thread_names:
            self._thread_names[tid] = threading.current_thread().name
        self._events.append(("i", name, cat, tid,
                             time.perf_counter_ns(), 0, args))

    # ------------------------------------------------------------- export
    def _ts_us(self, t_ns: int) -> float:
        return (t_ns - self._t0_ns) / 1e3

    def chrome_trace(self) -> dict:
        """The ring buffer as a Chrome/Perfetto `trace_event` JSON object
        (object form so metadata rides along)."""
        from bigdl_tpu.utils.runtime import process_index, run_id
        pid = process_index()
        events: List[dict] = [
            {"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
             "args": {"name": f"bigdl_tpu p{pid} {run_id()}"}},
        ]
        for tid, tname in sorted(self._thread_names.items()):
            events.append({"name": "thread_name", "ph": "M", "pid": pid,
                           "tid": tid, "args": {"name": tname}})
        for ph, name, cat, tid, t0, dur, args in list(self._events):
            ev = {"name": name, "cat": cat, "ph": ph, "pid": pid,
                  "tid": tid, "ts": self._ts_us(t0)}
            if ph == "X":
                ev["dur"] = dur / 1e3
            else:
                ev["s"] = "t"
            if args:
                ev["args"] = dict(args)
            events.append(ev)
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "run_id": run_id(),
                "process_index": pid,
                "wall_time_origin": self._wall0,
            },
        }

    def dump(self, path: Optional[str] = None) -> Optional[str]:
        """Write the Chrome trace JSON. `path=None` uses
        `<trace_dir>/trace.p<index>.json`; no dir configured → no-op.
        Returns the written path."""
        if path is None:
            if not self.trace_dir:
                return None
            from bigdl_tpu.utils.runtime import process_index
            os.makedirs(self.trace_dir, exist_ok=True)
            path = os.path.join(self.trace_dir,
                                f"trace.p{process_index()}.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(self.chrome_trace(), fh)
        os.replace(tmp, path)
        return path

    def events(self) -> Iterable[tuple]:
        """Raw ring contents, oldest first: (ph, name, cat, tid, t0_ns,
        dur_ns, args) with perf_counter nanoseconds."""
        return list(self._events)


_TRACER = Tracer()


def get_tracer() -> Tracer:
    return _TRACER


def span(name: str, cat: str = "host", args: Optional[dict] = None) -> _Span:
    """Module-level hot-path entry: `with trace.span("serve/decode/fetch"):`"""
    return _Span(_TRACER, name, cat, args)


_phase_cache: Dict[str, "_metrics.Histogram"] = {}


def phase(name: str, cat: str = "train", args: Optional[dict] = None
          ) -> _Span:
    """`with phase("train/dispatch"): ...` — a span that also records its
    seconds into the `phase/<name>` histogram. The histogram lookup is
    cached by name, so the steady-state cost is a span's."""
    h = _phase_cache.get(name)
    if h is None:
        h = _phase_cache[name] = _metrics.histogram(f"phase/{name}")
    return _Span(_TRACER, name, cat, args, h)


def instant(name: str, cat: str = "host",
            args: Optional[dict] = None) -> None:
    _TRACER.instant(name, cat, args)


# ------------------------------------------------------------ collections
GC_SPAN = "process/gc"
GC_PAUSE = "process/gc_pause_s"
GC_HIST = f"phase/{GC_SPAN}"
_gc_t0 = 0


def _on_gc(event: str, info: dict) -> None:
    """`gc.callbacks` hook. Collections never overlap (the interpreter
    runs one at a time, callbacks included), so one start time serves.
    It may run at any allocation of any thread, also one made while that
    thread holds the metrics registry's lock, which is reentrant for it."""
    global _gc_t0
    if event == "start":
        _gc_t0 = time.perf_counter_ns()
        return
    t0 = _gc_t0
    dur = time.perf_counter_ns() - t0
    # registered by hand: a collection may come while a module that the
    # registry's accessors import is half imported
    reg = _metrics.registry()._metrics
    pause = reg.get(GC_PAUSE) or reg.setdefault(
        GC_PAUSE, _metrics.Counter(GC_PAUSE))
    pause.inc(dur * 1e-9)
    hist = reg.get(GC_HIST) or reg.setdefault(
        GC_HIST, _metrics.Histogram(GC_HIST))
    hist.record(dur * 1e-9)
    _TRACER.record(GC_SPAN, "process", t0, dur,
                   {"generation": info.get("generation"),
                    "collected": info.get("collected")})


def install_gc_hook() -> None:
    """Record every collection of this process (idempotent)."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def validate_chrome_trace(doc: Any) -> List[str]:
    """Schema check for Chrome/Perfetto trace JSON — the report CLI and
    tests use it; returns a list of problems (empty = valid)."""
    problems: List[str] = []
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        return ["missing traceEvents"]
    for i, ev in enumerate(doc["traceEvents"]):
        for key in ("name", "ph", "pid", "tid"):
            if key not in ev:
                problems.append(f"event {i}: missing {key!r}")
        ph = ev.get("ph")
        if ph not in ("X", "i", "M", "B", "E", "C"):
            problems.append(f"event {i}: unknown ph {ph!r}")
        if ph == "X":
            if "ts" not in ev or "dur" not in ev:
                problems.append(f"event {i}: X event needs ts+dur")
            elif ev["dur"] < 0:
                problems.append(f"event {i}: negative dur")
    return problems
