"""Live telemetry plane — the in-process /statusz HTTP endpoints.

PR 4's flight recorder is write-only: spans and metrics land in files
you read after the run. This module is the pull-based half (the
reference's `TrainSummary`/validation dashboards were live), delivered
TPU-natively: a stdlib `http.server` thread serving the CURRENT state
of the process — no new deps, no agent, no sidecar.

Endpoints (all GET, all JSON unless noted):

  * `/healthz`   — liveness + last-step age: is the trainer stalled?
  * `/metrics`   — the metrics registry rendered LIVE in Prometheus
                   exposition format (text/plain) through the same
                   `render_prometheus` the textfile exporter uses — a
                   scraper no longer waits for the flush cadence.
  * `/statusz`   — the operator headline: run id, epoch/step/K,
                   data-wait fraction, failover live/lost slices, serve
                   per-model p50/p99/shed/queue-depth, checkpoint
                   in-flight, watchdog alerts, fault-injection state.
  * `/varz`      — the raw registry snapshot as JSON (the fleet
                   aggregator's machine-readable scrape).
  * `/fleetz`    — the MERGED fleet view when this process aggregates
                   peers (observe/fleet.py; `?full=1` embeds raw peer
                   snapshots); `/fleetz/metrics` is the peer-labeled
                   Prometheus form.
  * `/memz`      — the device-memory plane (observe/memz.py): buffer
                   ledger per-owner table, per-device utilization +
                   high-water marks, top buffers, unattributed drift,
                   headroom estimates. Bytes come from shapes/dtypes
                   and local allocator stats — zero device syncs.
  * `/tracez?n=N` — the newest N spans from the tracer ring buffer.
  * `/profilez?seconds=S` — arms a `jax.profiler` capture window on
                   demand; the TensorBoard-loadable capture lands under
                   the trace dir.

Cadence contract: every handler reads host-side registry/ring state
only — a scrape NEVER touches a device value, so polling /statusz under
load adds zero host syncs to the train loop (asserted by
tests/test_statusz.py).

Enable with BIGDL_TPU_STATUSZ_PORT (0 = off; process 0 only — the
other hosts of a multihost job export files with `.p<i>` suffixes and
can run their own plane if wanted). `ensure_started()` (observe/
__init__.py) starts it; `shutdown()` stops it. Binds
BIGDL_TPU_STATUSZ_HOST (loopback by default — widening the bind is a
deliberate operator choice).
"""

from __future__ import annotations

import json
import logging
import os
import time
from typing import Dict, List, Optional
from urllib.parse import parse_qs, urlparse

from bigdl_tpu.analysis import sancov
from bigdl_tpu.utils.httpd import HTTPServerThread, JSONHandler, ServerSlot
from bigdl_tpu.utils.threads import make_lock, spawn

log = logging.getLogger("bigdl_tpu")

_t0 = time.time()

# serve engines announce themselves here so /statusz can read their
# per-model stats() without observe depending on serve at import time
_engines: List = []
_engines_lock = make_lock("statusz.engines")
sancov.register_shared("statusz.engines", _engines_lock)


def register_engine(engine) -> None:
    """Called by ServeEngine.__init__ (weakly held via liveness checks:
    a shut-down engine reports itself closed and is dropped)."""
    import weakref
    with _engines_lock:
        if sancov.LOCKS_ON:
            sancov.check_owned(_engines_lock, "statusz.engines")
        _engines.append(weakref.ref(engine))


def _live_engines() -> List:
    with _engines_lock:
        live, keep = [], []
        for ref in _engines:
            e = ref()
            if e is not None and not getattr(e, "_closed", False):
                live.append(e)
                keep.append(ref)
        _engines[:] = keep
        return live


# ------------------------------------------------------------- payloads
def health_payload() -> dict:
    """Liveness + staleness: `last_step_age_s` is the seconds since the
    trainer's last metrics flush (the loop's heartbeat) — a live server
    with a growing age means the train loop is stalled, which is
    exactly the failure a file-based exporter cannot show."""
    from bigdl_tpu.observe import metrics as _metrics
    from bigdl_tpu.utils.runtime import process_index, run_id
    g = _metrics.registry().snapshot().get("gauges", {})
    last = g.get("train/last_flush_unix", 0.0)
    return {
        "ok": True,
        "run_id": run_id(),
        "process_index": process_index(),
        "uptime_s": round(time.time() - _t0, 3),
        "neval": int(g.get("train/neval", 0)),
        "last_step_age_s": (round(time.time() - last, 3)
                            if last else None),
    }


def status_payload() -> dict:
    """The /statusz JSON — also snapshotted verbatim into every crash
    forensics bundle (observe/doctor.py), so the post-mortem view and
    the live view are the same document."""
    from bigdl_tpu.observe import doctor as _doctor
    from bigdl_tpu.observe import metrics as _metrics
    snap = _metrics.registry().snapshot()
    g, c = snap.get("gauges", {}), snap.get("counters", {})
    serve: Dict[str, dict] = {}
    for engine in _live_engines():
        try:
            serve.update(engine.stats())
        except Exception as e:          # noqa: BLE001 — telemetry
            serve["_error"] = {"error": str(e)}
    if not serve:
        # no live engine in-process (or a post-mortem reader): fall
        # back to the registry-derived SLO view so a run log still
        # answers the same questions
        slo = _metrics.serve_slo(snap)
        if slo:
            serve = {"_from_registry": slo}
    # iteration-level decode (serve/decode.py): per-model slot/token
    # state lifted out of the serve stats into its own pane — the fleet
    # plane mirrors these per peer (observe/fleet.py)
    decode = {m: s["decode"] for m, s in serve.items()
              if isinstance(s, dict) and isinstance(s.get("decode"),
                                                    dict)}
    wd = _doctor.watchdog()
    payload = {
        **health_payload(),
        "train": {
            "epoch": int(g.get("train/epoch", 0)),
            "step": int(g.get("train/neval", 0)),
            "steps_per_call": int(g.get("train/steps_per_call", 1)) or 1,
            "loss": g.get("train/loss"),
            "lr": g.get("train/lr"),
            "throughput_rec_s": g.get("train/throughput"),
            "records": c.get("train/records", 0),
            "nonfinite_steps": c.get("train/nonfinite_steps", 0),
        },
        "data_wait": _metrics.data_wait_fraction(snap),
        "jit": {
            "compiles": c.get("jit/compiles", 0),
            "compile_seconds": round(c.get("jit/compile_seconds", 0.0), 3),
            "cache_hit_compiles": c.get("jit/cache_hit_compiles", 0),
        },
        "checkpoint": {
            "in_flight": bool(g.get("checkpoint/in_flight", 0)),
            "saves": c.get("checkpoint/saves", 0),
            "failures": c.get("checkpoint/failures", 0),
        },
        "serve": serve or None,
        "decode": decode or None,
        "alerts": wd.alerts(),
        "watchdog": {
            "enabled": wd.enabled,
            "alert_active": wd.active_alert() is not None,
            "anomalies": c.get("watchdog/anomalies", 0),
            "incidents": c.get("watchdog/incidents", 0),
            "alerts": wd.alerts(),
            # incident-history accounting: the alerts list retains the
            # newest 16 — total/dropped make a flapping regression's
            # full history visible even after truncation
            **{f"incidents_{k}": v
               for k, v in wd.incident_totals().items()},
            "serve": (_doctor._serve_watchdog.summary()
                      if _doctor._serve_watchdog is not None else None),
        },
    }
    try:
        # device-memory headline (observe/memz.py): the compact per-peer
        # rows /fleetz merges; the full table lives on /memz
        from bigdl_tpu.observe import memz as _memz
        payload["memory"] = _memz.ledger().status_section()
    except Exception:                    # noqa: BLE001 — telemetry
        pass
    san = sancov.report_payload()
    if san["modes"]:
        # concurrency sanitizer live (BIGDL_TPU_SANITIZE): findings
        # belong on the same pane as everything else
        payload["sanitizer"] = san
    if "exchange/window" in g:
        # DCN-tier exchange (parallel/dcn.py): where this process is
        # inside its T-window, plus the per-slice loss spread — the
        # fleet plane mirrors these per peer (observe/fleet.py)
        payload["exchange"] = {
            "window": int(g.get("exchange/window", 1)),
            "pending_steps": int(g.get("exchange/pending_steps", 0)),
            "count": c.get("exchange/count", 0),
            "skipped_steps": c.get("exchange/skipped_steps", 0),
            "wire_bytes": c.get("exchange/wire_bytes", 0),
            "residual_norm": g.get("exchange/residual_norm"),
            "loss_spread": g.get("exchange/loss_spread"),
            "dropped_contributions": c.get(
                "exchange/dropped_contributions", 0),
        }
    if "failover/live_slices" in g:
        payload["failover"] = {
            "live_slices": int(g["failover/live_slices"]),
            "lost_slices": int(g.get("failover/lost_slices", 0)),
            "live_devices": int(g.get("failover/live_devices", 0)),
            "last_reshard_s": g.get("failover/last_reshard_s"),
            "slice_losses": c.get("failover/slice_losses", 0),
            "grow_backs": c.get("failover/grow_backs", 0),
        }
    if "train/mesh_devices" in g:
        payload["train"]["mesh_devices"] = int(g["train/mesh_devices"])
    try:
        from bigdl_tpu.resilience import faults
        payload["faults"] = faults.status()
    except Exception:                    # noqa: BLE001 — telemetry
        pass
    return payload


def tracez_payload(n: int = 100) -> dict:
    """The newest `n` ring-buffer spans (host timeline post-mortem
    without waiting for the end-of-run trace dump)."""
    from bigdl_tpu.observe.trace import get_tracer
    t = get_tracer()
    evs = list(t.events())[-max(1, n):]
    spans = []
    for ph, name, cat, tid, t0, dur, args in evs:
        spans.append({"ph": ph, "name": name, "cat": cat, "tid": tid,
                      "ts_us": round(t._ts_us(t0), 1),
                      "dur_us": round(dur / 1e3, 1),
                      "args": args})
    return {"enabled": t.enabled, "ring": t._ring,
            "count": len(spans), "spans": spans}


# ------------------------------------------------------------- profiler
_profile_lock = make_lock("statusz.profile")
_profile_until = 0.0


def arm_profiler(seconds: float) -> dict:
    """Start a `jax.profiler` capture for `seconds` (clamped 0.1..600);
    a background timer stops it. One window at a time. The capture dir
    lands under the trace dir (or /tmp) — TensorBoard-loadable, with
    the host spans' TraceAnnotations aligned to the device timeline."""
    global _profile_until
    seconds = min(600.0, max(0.1, float(seconds)))
    try:
        import jax.profiler as _prof
    except Exception as e:               # noqa: BLE001 — optional dep
        return {"ok": False, "error": f"jax.profiler unavailable: {e}"}
    with _profile_lock:
        now = time.time()
        if _profile_until > now:
            return {"ok": False, "error": "capture already in flight",
                    "remaining_s": round(_profile_until - now, 1)}
        from bigdl_tpu.observe.trace import get_tracer
        root = get_tracer().trace_dir or "/tmp/bigdl_tpu_trace"
        out = os.path.join(root, f"profilez-{int(now)}")
        try:
            _prof.start_trace(out)
        except Exception as e:           # noqa: BLE001 — profiler state
            return {"ok": False, "error": str(e)}
        _profile_until = now + seconds

    def _stop():
        global _profile_until
        time.sleep(seconds)
        with _profile_lock:
            try:
                _prof.stop_trace()
            except Exception as e:       # noqa: BLE001 — profiler state
                log.warning("profilez: stop_trace failed: %s", e)
            _profile_until = 0.0
        log.info("profilez: %.1fs capture -> %s", seconds, out)

    spawn(_stop, name="profilez-stop")
    from bigdl_tpu.observe.metrics import counter
    counter("statusz/profile_captures").inc()
    return {"ok": True, "seconds": seconds, "dir": out}


# --------------------------------------------------------------- server
class _Handler(JSONHandler):
    # server core (bind/threading/shutdown discipline) lives in
    # utils/httpd.py, shared with the serving network front
    server_version = "bigdl-tpu-statusz/1"
    log_prefix = "statusz"

    def do_GET(self):                    # noqa: N802 — http.server API
        url = urlparse(self.path)
        q = parse_qs(url.query)
        try:
            if url.path == "/healthz":
                self._send(200, json.dumps(health_payload()))
            elif url.path == "/metrics":
                from bigdl_tpu.observe import metrics as _metrics
                from bigdl_tpu.observe.export import render_prometheus
                self._send(200, render_prometheus(
                    _metrics.registry().snapshot()), ctype="text/plain")
            elif url.path in ("/statusz", "/", "/statusz/"):
                payload = status_payload()
                if q.get("varz", ["0"])[0] not in ("0", ""):
                    # one-round-trip form for the fleet poller: the raw
                    # registry snapshot rides the same response, so a
                    # peer scrape costs ONE request, not two
                    from bigdl_tpu.observe import metrics as _metrics
                    payload["varz"] = _metrics.registry().snapshot()
                self._send(200, json.dumps(payload, default=str))
            elif url.path == "/varz":
                # raw registry snapshot as JSON — the fleet poller's
                # machine-readable twin of /metrics (observe/fleet.py)
                from bigdl_tpu.observe import metrics as _metrics
                self._send(200, json.dumps(
                    _metrics.registry().snapshot(), default=str))
            elif url.path in ("/fleetz", "/fleetz/", "/fleetz/metrics"):
                from bigdl_tpu.observe import fleet as _fleet
                agg = _fleet.aggregator()
                if agg is None:
                    self._send(404, json.dumps({
                        "error": "fleet aggregation is off — set "
                                 "BIGDL_TPU_FLEET=1 or "
                                 "BIGDL_TPU_FLEET_PEERS (process 0 "
                                 "aggregates)"}))
                elif url.path.endswith("/metrics"):
                    self._send(200, agg.fleet_metrics(),
                               ctype="text/plain")
                else:
                    full = q.get("full", ["0"])[0] not in ("0", "")
                    self._send(200, json.dumps(
                        agg.fleet_payload(full=full), default=str))
            elif url.path == "/memz":
                from bigdl_tpu.observe import memz as _memz
                self._send(200, json.dumps(_memz.ledger().payload(),
                                           default=str))
            elif url.path == "/tracez":
                n = int(q.get("n", ["100"])[0])
                self._send(200, json.dumps(tracez_payload(n),
                                           default=str))
            elif url.path == "/profilez":
                sec = float(q.get("seconds", ["5"])[0])
                out = arm_profiler(sec)
                self._send(200 if out.get("ok") else 409,
                           json.dumps(out))
            else:
                self._send(404, json.dumps({"error": "unknown endpoint",
                                            "endpoints": [
                                                "/healthz", "/metrics",
                                                "/varz", "/statusz",
                                                "/memz", "/fleetz",
                                                "/fleetz/metrics",
                                                "/tracez",
                                                "/profilez"]}))
        except BrokenPipeError:
            pass
        except Exception as e:           # noqa: BLE001 — telemetry
            log.warning("statusz handler %s failed: %s", url.path, e)
            try:
                self._send(500, json.dumps({"error": str(e)}))
            except Exception:            # noqa: BLE001 — socket gone
                pass


class StatuszServer(HTTPServerThread):
    """The HTTP thread (utils/httpd.py core). `port=0` binds an
    ephemeral port (tests); the knob path never passes 0 (0 = off)."""

    def __init__(self, port: int, host: str = "127.0.0.1"):
        super().__init__(_Handler, port, host, thread_name="statusz-http")
        log.info("statusz: live telemetry plane on http://%s:%d "
                 "(/healthz /metrics /statusz /memz /tracez /profilez)",
                 host, self.port)


_slot = ServerSlot("statusz.server")


def start(port: Optional[int] = None,
          host: Optional[str] = None) -> Optional[StatuszServer]:
    """Start (or return) the process-wide server. With `port=None` the
    knobs decide: BIGDL_TPU_STATUSZ_PORT=0 -> None (off), and only
    process 0 serves. An explicit `port` (0 = ephemeral) always starts."""
    from bigdl_tpu.utils import config

    def _factory() -> Optional[StatuszServer]:
        h, p = host, port
        if h is None:
            h = config.get("STATUSZ_HOST")
        if p is None:
            p = config.get("STATUSZ_PORT")
            if not p:
                return None
            from bigdl_tpu.utils.runtime import process_index
            idx = process_index()
            if idx != 0:
                # fleet mode (observe/fleet.py): every process serves a
                # plane at STATUSZ_PORT + process_index so process 0's
                # aggregator can reach it; otherwise process 0 only
                from bigdl_tpu.observe import fleet as _fleet
                if not _fleet.enabled():
                    log.debug("statusz: not process 0 — skipping")
                    return None
                p = int(p) + idx
        try:
            return StatuszServer(int(p), h)
        except OSError as e:
            log.warning("statusz: cannot bind %s:%s (%s) — telemetry "
                        "plane disabled", h, p, e)
            return None

    return _slot.start(_factory)


def server() -> Optional[StatuszServer]:
    return _slot.get()


def stop() -> None:
    # ServerSlot swaps under its lock and joins OUTSIDE it: close()
    # waits on the HTTP thread (hundreds of ms), and holding the lock
    # across that join is exactly the long-hold the sanitizer flags
    _slot.stop()
