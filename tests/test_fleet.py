"""Fleet-brain tests (observe/fleet.py + the generalized watchdog +
alert fan-out): cross-process /fleetz aggregation (2-subprocess run with
a SIGKILLed peer going STALE, not dropped), the serve-SLO watchdog
opening exactly ONE attributed incident under a fake-clock p99
regression that fires the alert hook once, peer-labeled Prometheus
rendering, incident-history accounting, capture-on-crash, and the
`observe fleet` / `observe report --fleet` / `observe doctor --fleet`
CLIs."""

import json
import os
import pathlib
import signal
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

from bigdl_tpu import observe
from bigdl_tpu.observe import alerts as obs_alerts
from bigdl_tpu.observe import doctor as obs_doctor
from bigdl_tpu.observe import fleet as obs_fleet
from bigdl_tpu.observe import metrics as obs_metrics
from bigdl_tpu.observe import statusz as obs_statusz
from bigdl_tpu.observe import trace as obs_trace
from bigdl_tpu.observe.export import render_prometheus

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture
def clean_plane():
    """Fresh registry/tracer/watchdogs/servers/aggregator per test."""
    observe.shutdown()
    obs_metrics.registry().reset()
    obs_trace.get_tracer().clear()
    obs_doctor.reset_watchdog()
    yield
    observe.shutdown()          # stops fleet poller + serve watchdog too
    obs_metrics.registry().reset()
    obs_trace.get_tracer().clear()
    obs_doctor.reset_watchdog()


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=30) as r:
        return r.status, r.read().decode()


# ------------------------------------------------------------ discovery
def test_fleet_peer_candidates_derivation(monkeypatch):
    from bigdl_tpu.utils import runtime
    monkeypatch.setattr(runtime, "process_count", lambda: 3)
    monkeypatch.setattr(runtime, "coordinator_host",
                        lambda: "10.0.0.7")
    assert runtime.fleet_peer_candidates(8300) == [
        "10.0.0.7:8300", "10.0.0.7:8301", "10.0.0.7:8302"]
    assert runtime.fleet_peer_candidates(0) == []
    monkeypatch.setattr(runtime, "process_count", lambda: 1)
    assert runtime.fleet_peer_candidates(8300) == []


def test_resolve_peers_prefers_explicit_knob(monkeypatch, clean_plane):
    monkeypatch.setenv("BIGDL_TPU_FLEET_PEERS",
                       "a:1, b:2 ,c:3")
    assert obs_fleet.resolve_peers() == ["a:1", "b:2", "c:3"]
    assert obs_fleet.enabled()


# ---------------------------------------------------- prometheus labels
def test_render_prometheus_peer_labels(clean_plane):
    h = obs_metrics.Histogram("t", bounds=(1.0, 2.0))
    h.record(1.5)
    snap = {"counters": {"a/b": 3.0}, "gauges": {"c/d": 1.5},
            "histograms": {"e/f": h.snapshot()}}
    text = render_prometheus(snap, labels={"peer": "2"})
    assert 'bigdl_tpu_a_b{peer="2"} 3.0' in text
    assert 'bigdl_tpu_c_d{peer="2"} 1.5' in text
    assert ',peer="2"}' in text               # histogram buckets labeled
    assert 'bigdl_tpu_e_f_count{peer="2"} 1' in text
    # unlabeled render unchanged (the /metrics endpoint's form)
    assert "bigdl_tpu_a_b 3.0" in render_prometheus(snap)


# ------------------------------------------------- aggregator (no HTTP)
def _peer_doc(i, *, step=None, alerts=()):
    return {
        "statusz": {
            "run_id": "r", "process_index": i,
            "last_step_age_s": 0.1,
            "train": {"step": 100 + i * 5 if step is None else step,
                      "epoch": 2, "loss": 0.5 + i,
                      "throughput_rec_s": 1000.0 * (i + 1),
                      "nonfinite_steps": 0},
            "data_wait": {"fraction": 0.05 * (i + 1)},
            "watchdog": {"alert_active": bool(alerts),
                         "alerts": list(alerts)},
            "serve": {"m1": {"requests": 3 + i, "p99_ms": 8.0 + i,
                             "queued_rows": i,
                             "decode": {
                                 "tokens": 100 * (i + 1),
                                 "tokens_per_s": 50.0 * (i + 1),
                                 "active_slots": i, "slots": 4,
                                 "slot_occupancy_mean": 0.25 * (i + 1),
                             }}},
            "decode": {"m1": {"tokens_per_s": 50.0 * (i + 1)}},
            "failover": {"live_slices": 2 - i, "slice_losses": i},
            "exchange": {"window": 8, "pending_steps": 3 + i,
                         "loss_spread": 0.01 * (i + 1)},
            "memory": {"ledger_bytes": 1000 * (i + 1),
                       "utilization_pct": 10.0 * (i + 1),
                       "headroom_bytes": 9000 - 1000 * i,
                       "unattributed_bytes": 8,
                       "top_owner": "serve/lm/kv_cache",
                       "top_owner_bytes": 800 * (i + 1)},
            "sanitizer": {"reports": [{"kind": "hostsync"}] * i,
                          "modes": ["locks"]},
        },
        "varz": {"counters": {"train/records": 10.0 * (i + 1)},
                 "gauges": {"train/neval": 100.0 + i * 5},
                 "histograms": {}},
    }


def _fake_fetch(docs, down):
    def fetch(addr, path, timeout):
        if addr in down:
            raise OSError(f"{addr} down")
        d = docs[addr]
        if path.startswith("/statusz"):
            # the ?varz=1 embedded form the poller asks for first
            return {**d["statusz"], "varz": dict(d["varz"])}
        return d["varz"]
    return fetch


def test_aggregator_merges_and_marks_stale_not_dropped(clean_plane):
    docs = {"h:1": _peer_doc(0), "h:2": _peer_doc(
        1, alerts=[{"opened_at": 5.0, "phase": "train/data_wait",
                    "slowdown_x": 3.0, "resolved": False}])}
    down = set()
    agg = obs_fleet.FleetAggregator(
        ["h:1", "h:2"], poll_s=1.0, stale_after=2,
        fetch=_fake_fetch(docs, down), start_thread=False)
    agg.poll_once()
    p = agg.fleet_payload()
    f = p["fleet"]
    assert f["peers_total"] == 2 and f["peers_live"] == 2
    assert f["step"] == {"min": 100, "max": 105, "skew": 5}
    assert f["loss"]["spread"] == pytest.approx(1.0)
    assert f["alerts_active"] == 1
    assert p["serve"]["m1"]["requests"] == 7
    assert p["serve"]["m1"]["p99_ms_max"] == 9.0
    # per-model decode aggregates: tokens/s additive, occupancy averaged
    dec = p["serve"]["m1"]["decode"]
    assert dec["tokens"] == 300
    assert dec["tokens_per_s"] == pytest.approx(150.0)
    assert dec["slots"] == 8 and dec["active_slots"] == 1
    assert dec["slot_occupancy_mean"] == pytest.approx(0.375)
    assert dec["peers"] == 2
    assert p["peers"][1]["decode_tokens_per_s"] == pytest.approx(100.0)
    assert p["failover"]["slice_losses"] == 1
    assert p["failover"]["min_live_slices"] == 1
    assert p["sanitizer"]["reports"] == 1
    assert p["alerts"][0]["peer"] == 1
    assert p["peers"][1]["data_wait"] == pytest.approx(0.10)
    # DCN-exchange window position + per-slice loss spread per peer
    assert p["peers"][1]["exchange_pending"] == 4
    assert p["peers"][1]["slice_loss_spread"] == pytest.approx(0.02)
    # device-memory rows (statusz `memory` section, observe/memz.py):
    # per-peer utilization/headroom/top-owner + the fleet worst-case
    # rollup (max utilization, min headroom)
    assert p["peers"][1]["mem_utilization_pct"] == pytest.approx(20.0)
    assert p["peers"][1]["mem_ledger_bytes"] == 2000
    assert p["peers"][1]["mem_headroom_bytes"] == 8000
    assert p["peers"][1]["mem_top_owner"] == "serve/lm/kv_cache"
    assert f["mem_utilization_max"] == pytest.approx(20.0)
    assert f["mem_headroom_min_bytes"] == 8000
    # full form embeds the raw snapshots for the report CLI
    full = agg.fleet_payload(full=True)
    assert full["snapshots"]["0"]["gauges"]["train/neval"] == 100.0
    # peer death: unreachable counted, stale after N consecutive
    # misses, NEVER dropped from the pane
    down.add("h:2")
    agg.poll_once()
    p = agg.fleet_payload()
    assert p["peers"][1]["ok"] is False
    assert p["peers"][1]["stale"] is False        # 1 miss < stale_after
    agg.poll_once()
    p = agg.fleet_payload()
    assert len(p["peers"]) == 2                   # kept, not dropped
    assert p["peers"][1]["stale"] is True
    assert p["peers"][1]["step"] == 105           # last-known state
    # memory rows ride the same STALE-not-dropped contract
    assert p["peers"][1]["mem_ledger_bytes"] == 2000
    assert p["fleet"]["peers_live"] == 1
    assert p["fleet"]["peers_stale"] == 1
    assert p["fleet"]["unreachable_polls"] == 2
    assert observe.counter("fleet/peer_unreachable").value == 2
    # recovery clears the stale flag
    down.clear()
    agg.poll_once()
    p = agg.fleet_payload()
    assert p["peers"][1]["stale"] is False and p["peers"][1]["ok"]
    agg.close()


def test_fleet_metrics_peer_labeled_and_type_deduped(clean_plane):
    docs = {"h:1": _peer_doc(0), "h:2": _peer_doc(1)}
    agg = obs_fleet.FleetAggregator(
        ["h:1", "h:2"], poll_s=1.0, fetch=_fake_fetch(docs, set()),
        start_thread=False)
    agg.poll_once()
    text = agg.fleet_metrics()
    assert 'bigdl_tpu_train_neval{peer="0"} 100.0' in text
    assert 'bigdl_tpu_train_neval{peer="1"} 105.0' in text
    assert 'bigdl_tpu_fleet_peer_up{peer="0",addr="h:1"} 1' in text
    # one TYPE header per family even with two peers
    assert text.count("# TYPE bigdl_tpu_train_neval gauge") == 1
    agg.close()


# ------------------------------------------- live HTTP, single process
def test_fleetz_endpoints_over_http(monkeypatch, clean_plane):
    srv = obs_statusz.start(port=0)
    peer = obs_statusz.StatuszServer(0)
    monkeypatch.setenv(
        "BIGDL_TPU_FLEET_PEERS",
        f"127.0.0.1:{srv.port},127.0.0.1:{peer.port}")
    monkeypatch.setenv("BIGDL_TPU_FLEET_POLL_S", "0.5")
    observe.gauge("train/neval").set(7)
    observe.gauge("train/last_flush_unix").set(time.time())
    agg = obs_fleet.ensure_started()
    assert agg is not None and obs_fleet.aggregator() is agg
    agg.poll_once()
    # /varz: the raw registry snapshot the poller scrapes
    code, body = _get(srv.port, "/varz")
    assert code == 200
    assert json.loads(body)["gauges"]["train/neval"] == 7
    code, body = _get(srv.port, "/fleetz")
    assert code == 200
    doc = json.loads(body)
    assert doc["fleet"]["peers_live"] == 2
    assert all(p["step"] == 7 for p in doc["peers"])
    code, body = _get(srv.port, "/fleetz/metrics")
    assert code == 200
    assert 'bigdl_tpu_train_neval{peer="1"} 7.0' in body
    # a killed peer goes stale while /fleetz keeps serving
    peer.close()
    for _ in range(agg.stale_after):
        agg.poll_once()
    doc = json.loads(_get(srv.port, "/fleetz")[1])
    assert doc["peers"][1]["stale"] is True
    assert doc["fleet"]["peers_live"] == 1


def test_fleetz_404_when_aggregation_off(clean_plane):
    srv = obs_statusz.start(port=0)
    try:
        urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/fleetz", timeout=30)
        assert False, "expected 404"
    except urllib.error.HTTPError as e:
        assert e.code == 404
        assert "BIGDL_TPU_FLEET" in e.read().decode()


# -------------------------------------------------- 2-subprocess fleet
def _scrape_fleetz(port, pred, deadline_s=30):
    last = None
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        try:
            _, body = _get(port, "/fleetz")
            last = json.loads(body)
            if pred(last):
                return last
        except Exception:
            pass
        time.sleep(0.2)
    raise AssertionError(f"fleetz condition never met; last={last}")


def test_two_process_fleet_survives_sigkilled_peer(tmp_path):
    """ISSUE 12 acceptance: a 2-subprocess run's merged /fleetz shows
    both peers; SIGKILLing one mid-scrape marks it stale (never a
    crash, never dropped) while the aggregator keeps serving."""
    import socket
    ports = []
    socks = []
    for _ in range(2):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        socks.append(s)
    for s in socks:
        s.close()
    peers = f"127.0.0.1:{ports[0]},127.0.0.1:{ports[1]}"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = (str(REPO) + os.pathsep
                         + env.get("PYTHONPATH", ""))
    procs = []
    try:
        for idx in (0, 1):
            procs.append(subprocess.Popen(
                [sys.executable, str(REPO / "tests" / "fleet_worker.py"),
                 str(idx), str(ports[idx]), peers],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True, env=env))
        for i, p in enumerate(procs):
            ready = json.loads(p.stdout.readline())
            assert ready["ready"] and ready["port"] == ports[i]
            assert ready["aggregating"] == (i == 0)
        # merged view shows BOTH peers with their skewed states
        doc = _scrape_fleetz(
            ports[0], lambda d: d["fleet"]["peers_live"] == 2)
        assert [p["step"] for p in doc["peers"]] == [100, 105]
        assert doc["fleet"]["step"]["skew"] == 5
        assert doc["peers"][1]["loss"] == pytest.approx(1.5)
        # per-model decode aggregates ride the merged serve table
        dec = doc["serve"]["lm"]["decode"]
        assert dec["tokens"] == 300
        assert dec["tokens_per_s"] == pytest.approx(150.0)
        assert dec["slot_occupancy_mean"] == pytest.approx(0.375)
        assert doc["peers"][1]["decode_tokens_per_s"] == pytest.approx(
            100.0)
        # per-peer memory rows (ISSUE 15 satellite): each worker grew a
        # registered decode KV bucket, so peer KV/ledger bytes are
        # NONZERO in the merged view — 2 layers x (4, 64, 2, 8) fp32
        kv_bytes = 2 * 4 * 64 * 2 * 8 * 4
        for row in doc["peers"]:
            assert row["mem_ledger_bytes"] >= kv_bytes
            assert row["mem_top_owner"] == "serve/lm/kv_cache"
        _, text = _get(ports[0], "/fleetz/metrics")
        assert 'bigdl_tpu_train_neval{peer="1"} 105.0' in text
        assert 'bigdl_tpu_mem_serve_lm_kv_cache_bytes{peer="1"} ' \
               f'{float(kv_bytes)}' in text
        # SIGKILL peer 1 mid-scrape: stale, not a crash
        procs[1].send_signal(signal.SIGKILL)
        procs[1].wait(timeout=10)
        doc = _scrape_fleetz(
            ports[0], lambda d: d["peers"][1]["stale"])
        assert len(doc["peers"]) == 2             # never dropped
        assert doc["peers"][1]["step"] == 105     # last-known state
        assert doc["fleet"]["peers_live"] == 1
        assert doc["fleet"]["unreachable_polls"] >= 1
        # aggregator process exits CLEANLY through observe.shutdown()
        out, err = procs[0].communicate(timeout=30)
        assert procs[0].returncode == 0, err[-2000:]
        assert "Traceback" not in err
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


# ------------------------------------------------- serve-SLO watchdog
class _Clock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def test_serve_p99_regression_opens_exactly_one_incident_and_alerts_once(
        tmp_path, monkeypatch, clean_plane):
    """ISSUE 12 acceptance: latency inflation injected through the
    batcher's clock-injectable seam -> the serve-SLO watchdog opens ONE
    incident attributed to queue-wait, and the alert hook fires once."""
    from bigdl_tpu.serve.batcher import ContinuousBatcher
    hook = tmp_path / "pages.jsonl"
    monkeypatch.setenv("BIGDL_TPU_ALERT_CMD", f"cat >> {hook}")
    clk = _Clock()
    b = ContinuousBatcher(lambda xs, n: xs, [8], name="m1",
                          clock=clk, start=False)
    swd = obs_doctor.ServeWatchdog(pct=50.0, window=8, sustain=2)
    obs_doctor._serve_watchdog = swd      # /statusz must see THIS one

    def window(wait_s):
        for _ in range(3):
            b.submit(np.ones((2, 3), np.float32))
        clk.t += wait_s                   # time "passes" in the queue
        b._run_batch(b._take())
        return swd.observe_snapshot()

    for i in range(8):                    # healthy baseline: 5 ms p99
        assert window(0.005) == []
    assert observe.counter("watchdog/serve/m1/incidents").value == 0
    # sustained 20x p99 inflation through the fake clock
    assert window(0.100) == []            # 1st bad window: anomaly only
    assert observe.counter("watchdog/serve/m1/anomalies").value == 1
    opened = window(0.100)                # 2nd: sustained -> incident
    assert len(opened) == 1
    inc = opened[0]
    assert inc["model"] == "m1"
    assert inc["signal"] == "serve_p99_ms"
    assert inc["phase"] == "queue_wait_ms"          # attributed
    assert inc["slowdown_x"] > 2
    assert set(inc["deltas"]) == {"queue_wait_ms", "dispatch_ms",
                                  "batch_fill_ms"}
    # further sustained windows must NOT open a second incident
    assert window(0.100) == []
    assert window(0.100) == []
    assert observe.counter("watchdog/serve/m1/incidents").value == 1
    # surfaced on /statusz
    payload = obs_statusz.status_payload()
    sv = payload["watchdog"]["serve"]
    assert sv["models"]["m1"]["alert_active"] is True
    assert sv["models"]["m1"]["phase"] == "queue_wait_ms"
    assert sv["alerts"][-1]["model"] == "m1"
    # the alert hook fired EXACTLY once (fan-out is per incident open,
    # not per bad window)
    deadline = time.time() + 10
    while time.time() < deadline and not hook.exists():
        time.sleep(0.05)
    time.sleep(0.3)                       # let any extra fire land
    lines = hook.read_text().strip().splitlines()
    assert len(lines) == 1, lines
    event = json.loads(lines[0])
    assert event["model"] == "m1" and event["phase"] == "queue_wait_ms"
    assert event["run_id"]
    assert observe.counter("alerts/fired").value == 1
    # recovery closes it; a fresh regression may open a new incident
    assert window(0.005) == []
    assert swd.active_alerts() == []


def test_serve_watchdog_attributes_dispatch_regression(clean_plane):
    """Fed straight from registry histograms: a p99 regression whose
    growth sits in dispatch_ms blames the dispatch, not the queue."""
    from bigdl_tpu.serve.batcher import LATENCY_MS_BOUNDS
    lat = observe.histogram("serve/m2/latency_ms", LATENCY_MS_BOUNDS)
    qw = observe.histogram("serve/m2/queue_wait_ms", LATENCY_MS_BOUNDS)
    disp = observe.histogram("serve/m2/dispatch_ms", LATENCY_MS_BOUNDS)
    swd = obs_doctor.ServeWatchdog(pct=50.0, window=8, sustain=1)

    def window(lat_ms, qw_ms, disp_ms):
        for _ in range(3):
            lat.record(lat_ms)
            qw.record(qw_ms)
        disp.record(disp_ms)
        return swd.observe_snapshot()

    for _ in range(6):
        assert window(5.0, 1.0, 4.0) == []
    opened = window(100.0, 1.0, 99.0)
    assert len(opened) == 1 and opened[0]["phase"] == "dispatch_ms"


def test_serve_watchdog_skips_no_traffic_windows(clean_plane):
    from bigdl_tpu.serve.batcher import LATENCY_MS_BOUNDS
    lat = observe.histogram("serve/m3/latency_ms", LATENCY_MS_BOUNDS)
    swd = obs_doctor.ServeWatchdog(pct=50.0, window=8, sustain=1)
    lat.record(5.0)
    swd.observe_snapshot()
    before = observe.gauge("watchdog/serve/m3/p99_ms").value
    for _ in range(5):                    # idle polls: no new requests
        assert swd.observe_snapshot() == []
    assert observe.gauge("watchdog/serve/m3/p99_ms").value == before
    assert observe.counter("watchdog/serve/m3/anomalies").value == 0


def test_serve_watchdog_disabled_by_knob(monkeypatch, clean_plane):
    monkeypatch.setenv("BIGDL_TPU_SERVE_WATCHDOG_PCT", "0")
    swd = obs_doctor.ServeWatchdog()
    assert not swd.enabled and swd.observe_snapshot() == []
    assert obs_doctor.arm_serve_watchdog() is False


# -------------------------------------------- incident history (ISSUE)
def test_incident_history_truncation_is_accounted(clean_plane):
    wd = obs_doctor.Watchdog(pct=50.0, window=8, sustain=1)
    obs_doctor._watchdog = wd
    for i in range(6):                    # warm the baseline at 1.0
        wd.observe_signal(i, 1.0, {"c": 1.0})
    for i in range(20):                   # 20 open/close flaps
        assert wd.observe_signal(100 + i, 5.0, {"c": 5.0}) is not None
        wd.observe_signal(200 + i, 1.0, {"c": 1.0})
    totals = wd.incident_totals()
    assert totals == {"total": 20, "retained": 16, "dropped": 4}
    assert len(wd.alerts()) == 16
    assert observe.counter("watchdog/incidents_dropped").value == 4
    assert observe.counter("watchdog/incidents").value == 20
    payload = obs_statusz.status_payload()
    assert payload["watchdog"]["incidents_total"] == 20
    assert payload["watchdog"]["incidents_retained"] == 16
    assert payload["watchdog"]["incidents_dropped"] == 4


# ------------------------------------------------------- alert fan-out
class _Hook:
    """Local webhook endpoint recording POST bodies; `fail_n` first
    requests answer 500."""

    def __init__(self, fail_n=0):
        from http.server import (BaseHTTPRequestHandler,
                                 ThreadingHTTPServer)
        hook = self

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_POST(self):          # noqa: N802 — http.server API
                n = int(self.headers.get("Content-Length", 0))
                hook.bodies.append(self.rfile.read(n).decode())
                code = 500 if len(hook.bodies) <= hook.fail_n else 200
                self.send_response(code)
                self.send_header("Content-Length", "0")
                self.end_headers()

        self.bodies = []
        self.fail_n = fail_n
        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        from bigdl_tpu.utils.threads import spawn
        self.port = self.httpd.server_address[1]
        self._t = spawn(self.httpd.serve_forever, name="test-hook")

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()
        self._t.join(timeout=5)


def test_alert_webhook_delivers_incident_json(monkeypatch, clean_plane):
    hook = _Hook()
    try:
        ok = obs_alerts.deliver({"kind": "incident", "phase": "x",
                                 "slowdown_x": 3.0},
                                cmd="", hook=f"http://127.0.0.1:{hook.port}/")
        assert ok is True
        assert len(hook.bodies) == 1
        doc = json.loads(hook.bodies[0])
        assert doc["phase"] == "x" and doc["source"] == "bigdl_tpu"
        assert observe.counter("alerts/fired").value == 1
    finally:
        hook.close()


def test_alert_webhook_bounded_retry_then_gives_up(monkeypatch,
                                                   clean_plane):
    monkeypatch.setenv("BIGDL_TPU_ALERT_RETRIES", "2")
    monkeypatch.setenv("BIGDL_TPU_ALERT_BACKOFF_S", "0.01")
    hook = _Hook(fail_n=99)               # never succeeds
    try:
        ok = obs_alerts.deliver({"kind": "incident"}, cmd="",
                                hook=f"http://127.0.0.1:{hook.port}/")
        assert ok is False                # never raises, only reports
        assert len(hook.bodies) == 3      # 1 try + 2 bounded retries
        assert observe.counter("alerts/retries").value == 2
        assert observe.counter("alerts/failed").value == 1
    finally:
        hook.close()
    # retry backoff follows the shared resilience curve
    from bigdl_tpu.resilience.retry import backoff_delay
    assert backoff_delay(0.5, 0) == 0.5
    assert backoff_delay(0.5, 3) == 4.0
    assert backoff_delay(0.5, 99) == 8.0  # 16x cap
    assert backoff_delay(0.0, 5) == 0.0


def test_alert_cmd_failure_counts_failed(monkeypatch, clean_plane):
    monkeypatch.setenv("BIGDL_TPU_ALERT_RETRIES", "0")
    ok = obs_alerts.deliver({"kind": "incident"}, cmd="exit 3", hook="")
    assert ok is False
    assert observe.counter("alerts/failed").value == 1
    assert obs_alerts.fanout({"kind": "x"}) is None or True  # no sinks?


def test_fanout_noop_without_sinks(clean_plane):
    assert not obs_alerts.enabled()
    assert obs_alerts.fanout({"kind": "incident"}) is None


# --------------------------------------------------- capture-on-crash
def test_forensics_profile_capture_when_incident_live(tmp_path,
                                                      monkeypatch,
                                                      clean_plane):
    monkeypatch.setenv("BIGDL_TPU_FORENSICS", str(tmp_path))
    monkeypatch.setenv("BIGDL_TPU_FORENSICS_PROFILE_S", "0.2")
    # no incident -> capture skipped, noted in the bundle
    p = obs_doctor.dump_forensics("no-incident")
    note = json.loads((pathlib.Path(p) / "profile.json").read_text())
    assert note["ok"] is False and "no live incident" in note["skipped"]
    # live incident -> a profiler capture lands INSIDE the bundle
    wd = obs_doctor.Watchdog(pct=50.0, window=8, sustain=1)
    obs_doctor._watchdog = wd
    for i in range(6):
        wd.observe_signal(i, 1.0, {"c": 1.0})
    assert wd.observe_signal(50, 5.0, {"c": 5.0}) is not None
    assert obs_doctor.incident_active()
    p = obs_doctor.dump_forensics("crash-during-incident",
                                  exc=RuntimeError("boom"))
    note = json.loads((pathlib.Path(p) / "profile.json").read_text())
    assert note["ok"] is True, note
    assert os.path.isdir(note["dir"])
    assert note["dir"].startswith(p)
    assert observe.counter("forensics/profile_captures").value == 1


# ---------------------------------------------------------------- CLIs
def test_observe_fleet_cli_smoke():
    """Tier-1 wiring of the fleet smoke subcommand: two in-process
    planes, merged payload asserted, rc 0."""
    r = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.observe", "fleet", "--json"],
        capture_output=True, text=True, timeout=100,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stdout + r.stderr
    doc = json.loads(r.stdout.strip().splitlines()[-1])
    assert doc["ok"] is True and doc["peers"] == 2
    assert doc["stale"] == 1              # the killed-peer leg ran


def _hist_snap(*vals):
    h = obs_metrics.Histogram("t")
    for v in vals:
        h.record(v)
    return h.snapshot()


def test_report_fleet_from_jsonl_dir(tmp_path, clean_plane, capsys):
    for i, name in enumerate(("run.jsonl", "run.jsonl.p1")):
        rec = {"ts": 1.0, "step": 100 + i * 5, "run_id": "r",
               "process_index": i,
               "counters": {"watchdog/incidents": float(i)},
               "gauges": {"train/neval": 100.0 + i * 5,
                          "train/loss": 0.5 + i,
                          "train/throughput": 10.0},
               "histograms": {
                   "phase/train/dispatch": _hist_snap(0.01, 0.02)}}
        (tmp_path / name).write_text(json.dumps(rec) + "\n")
    from bigdl_tpu.observe import report as obs_report
    src = obs_report.load_fleet_sources(str(tmp_path))
    assert src["kind"] == "jsonl-dir" and len(src["peers"]) == 2
    assert src["peers"][1]["step"] == 105
    out = obs_report.render_fleet_report(src)
    assert "2 peers" in out and "step skew 5" in out
    assert "p0" in out and "p1" in out
    # merged phase table sums both peers' histograms
    assert "train/dispatch" in out
    doc = obs_report.fleet_report_json(src)
    assert doc["merged_phases"][0]["count"] == 4
    # CLI entry points
    assert obs_report.main([str(tmp_path), "--fleet"]) == 0
    assert "step skew 5" in capsys.readouterr().out
    assert obs_doctor.doctor_main([str(tmp_path), "--fleet"]) == 0
    out = capsys.readouterr().out
    assert "per-peer anomalies" in out and "incidents=1" in out


def test_report_fleet_from_fleetz_snapshot(tmp_path, clean_plane,
                                           capsys):
    docs = {"h:1": _peer_doc(0), "h:2": _peer_doc(
        1, alerts=[{"opened_at": 5.0, "phase": "train/data_wait",
                    "slowdown_x": 3.0, "resolved": True,
                    "signal": "step_s"}])}
    agg = obs_fleet.FleetAggregator(
        ["h:1", "h:2"], poll_s=1.0, fetch=_fake_fetch(docs, set()),
        start_thread=False)
    agg.poll_once()
    path = tmp_path / "fleet.json"
    path.write_text(json.dumps(agg.fleet_payload(full=True),
                               default=str))
    agg.close()
    from bigdl_tpu.observe import report as obs_report
    src = obs_report.load_fleet_sources(str(path))
    assert src["kind"] == "fleetz" and len(src["peers"]) == 2
    out = obs_report.render_fleet_report(src)
    assert "incident timeline:" in out
    assert "3.0x -> train/data_wait (resolved)" in out
    assert obs_report.main([str(path), "--fleet", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["fleet"]["peers_live"] == 2
    # a non-fleet file is a loud error, not a confusing table
    bad = tmp_path / "x.json"
    bad.write_text("{}")
    with pytest.raises(ValueError, match="peers"):
        obs_report.load_fleet_sources(str(bad))
