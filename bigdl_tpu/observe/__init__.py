"""bigdl_tpu.observe — the flight recorder.

Unified observability for the training stack (reference analogues:
`optim/Metrics.scala` phase timers, `AbstractModule` nanosecond timers,
`visualization/TrainSummary` events — SURVEY §2.10):

  * **trace**   — thread-safe, always-on, bounded ring of host spans
                  (and of the interpreter's collections, `process/gc`)
                  emitting Chrome/Perfetto `trace_event` JSON, with
                  matching `jax.profiler.TraceAnnotation` scopes so a
                  profiler capture holds the host spans on the device's
                  timeline;
  * **metrics** — process-wide registry of counters, gauges, and
                  log-bucket histograms (bounded memory for any run
                  length) fed only host-side values — no added syncs;
  * **export**  — TensorBoard / JSONL / Prometheus-textfile exporters
                  flushed by one background thread;
  * **report**  — `python -m bigdl_tpu.observe run.jsonl` phase table;
  * **statusz** — live telemetry plane: in-process HTTP /healthz,
                  /metrics (live Prometheus), /statusz, /tracez,
                  /profilez endpoints (BIGDL_TPU_STATUSZ_PORT);
  * **doctor**  — step-time anomaly watchdog riding the flush cadence
                  (BIGDL_TPU_WATCHDOG_PCT), the serve-SLO watchdog
                  (per-model p99, BIGDL_TPU_SERVE_WATCHDOG_PCT), crash
                  forensics bundles (BIGDL_TPU_FORENSICS, with
                  capture-on-crash when an incident is live), and the
                  `python -m bigdl_tpu.observe doctor` post-mortem CLI;
  * **memz**    — device-memory observability: the HBM buffer ledger
                  (every long-lived device tree registered under a
                  named owner, `mem/<owner>/bytes` gauges,
                  backend cross-check + unattributed drift), the /memz
                  live plane, the memory watchdog
                  (BIGDL_TPU_MEM_WATCHDOG_PCT), serve admission
                  checks, and OOM forensics (memory.json +
                  memory.prof in every crash bundle);
  * **fleet**   — cross-process aggregation: process 0 polls every
                  peer's plane and serves merged /fleetz +
                  peer-labeled /fleetz/metrics (BIGDL_TPU_FLEET /
                  BIGDL_TPU_FLEET_PEERS);
  * **alerts**  — incident fan-out to BIGDL_TPU_ALERT_CMD /
                  BIGDL_TPU_ALERT_WEBHOOK with bounded retry, off the
                  flush path.

The span ring is always on and bounded (BIGDL_TPU_TRACE_RING events; a
span costs a clock read at each edge, a ring append and an annotation
the profiler drops unless a capture is live). Knobs (utils/config.py):
BIGDL_TPU_TRACE=<dir> dumps the ring as Chrome JSON at each `finish()`;
BIGDL_TPU_METRICS_JSONL / _PROM / _TB attach exporters. The trainers
call `ensure_started()` once per optimize() and `finish()` at the end.

Span catalogue (docs/observability.md): training spans (`train/*`,
`data/*`, `checkpoint/*`, `jit/compile`), resilience markers
(`fault/*`, `preempt/*`, `retry`), the collections (`process/gc`, with
the counter `process/gc_pause_s`), and — since the serving subsystem —
the serve family: `serve/pack` and `serve/dispatch` spans around each
continuous-batching dispatch; the decode iteration
`serve/decode/iteration`, tiled by its leaves `serve/decode/admit`,
`await_taker`, `prefill`, `dispatch`, `fetch` (the host waiting on the
device) and `deliver`; the `serve/drain` span on graceful shutdown, and
the `serve/shed` instant for admission-control rejections, all riding
the same flush cadence (ONE host fetch per dispatched batch or decode
step, no per-request syncs — bigdl_tpu/serve/).
"""

from __future__ import annotations

import atexit
import threading
from typing import Optional

from bigdl_tpu.observe import metrics as metrics  # noqa: F401 — re-export
from bigdl_tpu.observe import trace as trace      # noqa: F401 — re-export
from bigdl_tpu.observe.metrics import counter, gauge, histogram, registry
from bigdl_tpu.observe.trace import get_tracer, instant, phase, span
from bigdl_tpu.utils.runtime import (install_log_prefix, process_index,
                                     run_id)
from bigdl_tpu.utils.threads import make_lock

__all__ = [
    "counter", "gauge", "histogram", "phase", "registry",
    "get_tracer", "instant", "span",
    "process_index", "run_id",
    "ensure_started", "finish", "shutdown", "export_manager",
    "statusz_server",
]

trace.install_gc_hook()     # every collection of the process, from import on

_lock = make_lock("observe.lifecycle")
_exports = None            # ExportManager when any exporter is configured
_started = False
_atexit_registered = False
_compile_listener = None
_compile_event_listener = None
_tls = threading.local()   # per-thread cache-hit marker (see below)

# event-key suffixes the DURATION listener owns: the plain-event listener
# must skip these, because some jax versions fire BOTH
# record_event_duration_secs AND record_event with the same key for one
# compilation — counting both double-counted jit/compiles (regression
# test: tests/test_observe.py::test_jit_compile_counter_dedupes...)
_DURATION_OWNED = ("backend_compile_duration", "cache_retrieval_time_sec")


def _on_jax_duration(event: str, duration: float, **kw):
    if event.endswith("backend_compile_duration"):
        # a persistent-cache hit goes through the same backend_compile
        # monitoring path (the "compile" is a deserialization) — the
        # retrieval event that immediately precedes it on this thread
        # tells the two apart
        hit = getattr(_tls, "cache_hit", False)
        _tls.cache_hit = False
        counter("jit/compiles").inc()
        counter("jit/compile_seconds").inc(duration)
        if hit:
            counter("jit/cache_hit_compiles").inc()
        trace.instant("jit/compile", cat="jit",
                      args={"seconds": round(duration, 4),
                            "cache_hit": hit})
    elif event.endswith("cache_retrieval_time_sec"):
        _tls.cache_hit = True
        counter("jit/cache_retrieval_seconds").inc(duration)


def _on_jax_event(event: str, **kw):
    # dedupe by event key: anything the duration listener counts must
    # not be re-counted here when jax also fires it as a plain event
    if any(event.endswith(s) for s in _DURATION_OWNED):
        return
    if event.endswith("cache_hits"):
        counter("jit/cache_hits").inc()
    elif event.endswith("cache_misses"):
        counter("jit/cache_misses").inc()


def _install_jax_compile_listener() -> None:
    """Count XLA compiles + seconds (and persistent-cache hits/misses)
    through jax.monitoring — the flight-recorder view of "why was this
    step 40s": recompilation. Registered once per process; survives
    jax's clear_event_listeners in tests by re-registering on the next
    ensure_started."""
    global _compile_listener, _compile_event_listener
    try:
        from jax import monitoring
        from jax._src import monitoring as _impl
    except Exception:
        return
    live = getattr(_impl, "get_event_duration_listeners", lambda: [])()
    if _compile_listener is None or _compile_listener not in live:
        monitoring.register_event_duration_secs_listener(_on_jax_duration)
        _compile_listener = _on_jax_duration
    live_ev = getattr(_impl, "get_event_listeners", lambda: [])()
    if _compile_event_listener is None \
            or _compile_event_listener not in live_ev:
        try:
            monitoring.register_event_listener(_on_jax_event)
            _compile_event_listener = _on_jax_event
        except Exception:
            pass


def ensure_started() -> bool:
    """Configure the flight recorder from the env knobs (idempotent; the
    trainers call this at the top of optimize()). Returns True when any
    observability sink (trace dir or exporter) is active."""
    global _exports, _started
    from bigdl_tpu.utils import config
    with _lock:
        install_log_prefix()
        _install_jax_compile_listener()
        # concurrency sanitizer (analysis/sancov.py): the locks mode
        # arms at lock construction, but the sync guard (device_get
        # wrapper + phase hook) installs here — the knob set at process
        # start is enough, no explicit sancov call needed
        from bigdl_tpu.analysis import sancov
        if sancov.sanitize_modes():
            sancov.refresh()
        trace_dir = config.get("TRACE") or None
        if trace_dir in ("1", "true", "yes", "on"):
            trace_dir = "/tmp/bigdl_tpu_trace"
        t = get_tracer()
        t.configure(trace_dir, ring=config.get("TRACE_RING"))
        if _exports is None:
            exporters = []
            jsonl = config.get("METRICS_JSONL")
            prom = config.get("METRICS_PROM")
            tb = config.get("METRICS_TB")
            from bigdl_tpu.observe.export import (ExportManager,
                                                  JsonlExporter,
                                                  PrometheusExporter,
                                                  TensorBoardExporter)
            if jsonl:
                exporters.append(JsonlExporter(jsonl))
            if prom:
                exporters.append(PrometheusExporter(prom))
            if tb and process_index() == 0:
                exporters.append(TensorBoardExporter(tb))
            if exporters:
                _exports = ExportManager(
                    exporters, flush_s=config.get("METRICS_FLUSH_S")).start()
        # live telemetry plane (observe/statusz.py): the in-process
        # /healthz /metrics /statusz /tracez /profilez HTTP endpoints,
        # knob-gated (BIGDL_TPU_STATUSZ_PORT, 0 = off, process 0 only)
        from bigdl_tpu.observe import statusz as _statusz
        sz = _statusz.start()
        # fleet brain (observe/fleet.py): process 0 aggregates every
        # peer's plane into /fleetz when BIGDL_TPU_FLEET /
        # BIGDL_TPU_FLEET_PEERS arm it — no-op otherwise
        from bigdl_tpu.observe import fleet as _fleet
        _fleet.ensure_started()
        # device-memory plane (observe/memz.py): capture the drift
        # baseline and arm the memory watchdog when a capacity limit is
        # known (backend bytes_limit or BIGDL_TPU_MEM_LIMIT_BYTES)
        from bigdl_tpu.observe import memz as _memz
        _memz.ensure_started()
        _started = True
        # thread-shutdown audit (docs/concurrency.md): a process that
        # merely turned the plane on must exit cleanly — join the export
        # flusher and close the statusz server BEFORE interpreter
        # teardown starts reclaiming the modules those threads touch
        global _atexit_registered
        if not _atexit_registered:
            atexit.register(shutdown)
            _atexit_registered = True
        return bool(t.trace_dir or _exports or sz)


def export_manager():
    """The live ExportManager (None when no exporter knob is set)."""
    return _exports


def statusz_server():
    """The live StatuszServer (None when the plane is off)."""
    from bigdl_tpu.observe import statusz as _statusz
    return _statusz.server()


def finish() -> Optional[str]:
    """End-of-optimize flush: dump the ring where BIGDL_TPU_TRACE names a
    directory (returns the path) and push one final exporter snapshot.
    The ring keeps recording — a process training twice dumps both runs
    in one flight record."""
    path = get_tracer().dump()
    if _exports is not None:
        _exports.flush()
    return path


def shutdown() -> None:
    """Tear down fleet poller + serve-SLO watchdog + exporters +
    statusz server, and forget the trace dump directory (tests / process
    exit). Pollers stop before the HTTP server they scrape through."""
    global _exports, _started
    with _lock:
        from bigdl_tpu.observe import fleet as _fleet
        _fleet.stop()
        from bigdl_tpu.observe import doctor as _doctor
        _doctor.stop_serve_watchdog()
        from bigdl_tpu.observe import memz as _memz
        _memz.stop_memory_watchdog()
        if _exports is not None:
            _exports.close()
            _exports = None
        from bigdl_tpu.observe import statusz as _statusz
        _statusz.stop()
        get_tracer().configure(None)
        _started = False
