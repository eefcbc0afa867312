"""Live telemetry plane tests (observe/statusz.py + observe/doctor.py):
the /healthz /metrics /statusz /tracez endpoints served DURING a live
optimize(), the step-time anomaly watchdog (baseline, sustained-regression
incident, phase attribution, recovery), crash forensics bundles + the
doctor CLI, percentile error bars of the log-bucket histograms, and the
span-catalogue doc-rot check."""

import json
import math
import os
import pathlib
import re
import socket
import urllib.request

import jax
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu import observe
from bigdl_tpu.observe import doctor as obs_doctor
from bigdl_tpu.observe import metrics as obs_metrics
from bigdl_tpu.observe import statusz as obs_statusz
from bigdl_tpu.observe import trace as obs_trace
from bigdl_tpu.observe.metrics import Histogram

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture
def clean_plane():
    """Fresh registry/tracer/watchdog/server around each test."""
    observe.shutdown()
    obs_metrics.registry().reset()
    obs_trace.get_tracer().clear()
    obs_doctor.reset_watchdog()
    yield
    observe.shutdown()
    obs_metrics.registry().reset()
    obs_trace.get_tracer().clear()
    obs_doctor.reset_watchdog()


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _get(port, path):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=30) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:       # non-2xx still has a body
        return e.code, e.read().decode()


# ------------------------------------------------------- live endpoints
class _ScrapingDataSet:
    """Wraps a dataset; at batch `at` of an epoch it scrapes every
    statusz endpoint — i.e. the HTTP client runs while optimize() is
    mid-flight, which is exactly the acceptance criterion."""

    def __init__(self, ds, port, at=3):
        self.ds, self.port, self.at = ds, port, at
        self.results = {}

    def __iter__(self):
        import time
        for i, batch in enumerate(iter(self.ds)):
            if i == self.at and not self.results:
                # the read-ahead thread can run ahead of the train loop;
                # poll /healthz until the trainer's first flush landed so
                # the scrape observes a mid-flight, non-trivial state
                # (training keeps consuming the already-queued batches
                # while we hold this one back)
                deadline = time.time() + 60
                while time.time() < deadline:
                    code, body = _get(self.port, "/healthz")
                    if json.loads(body).get("neval", 0) >= 2:
                        break
                    time.sleep(0.02)
                for ep in ("/healthz", "/metrics", "/statusz",
                           "/tracez?n=50"):
                    self.results[ep] = _get(self.port, ep)
            yield batch


def test_statusz_endpoints_live_during_optimize(tmp_path, monkeypatch,
                                                clean_plane):
    from bigdl_tpu.dataset import ArrayDataSet
    from bigdl_tpu.optim.local import Optimizer
    from bigdl_tpu.optim.method import SGD
    from bigdl_tpu.optim.trigger import Trigger
    port = _free_port()
    monkeypatch.setenv("BIGDL_TPU_STATUSZ_PORT", str(port))
    monkeypatch.setenv("BIGDL_TPU_TRACE", str(tmp_path / "trace"))
    r = np.random.RandomState(0)
    x = r.randn(160, 6).astype(np.float32)
    y = r.randint(0, 3, 160).astype(np.int32)
    model = nn.Sequential(nn.Linear(6, 3), nn.LogSoftMax())
    ds = _ScrapingDataSet(
        ArrayDataSet(x, y, 16, drop_last=True, shuffle=False), port)
    opt = Optimizer(model, ds, nn.ClassNLLCriterion(), SGD(0.1), seed=0)
    opt._log_every = 2
    opt.set_end_when(Trigger.max_iteration(10))
    opt.optimize()
    res = ds.results
    assert set(res) == {"/healthz", "/metrics", "/statusz", "/tracez?n=50"}
    assert all(code == 200 for code, _ in res.values())
    health = json.loads(res["/healthz"][1])
    assert health["ok"] is True and health["neval"] >= 2
    assert health["last_step_age_s"] is not None
    # /metrics is LIVE prometheus text straight from the registry
    prom = res["/metrics"][1]
    assert "# TYPE bigdl_tpu_train_neval gauge" in prom
    assert "bigdl_tpu_phase_train_dispatch" in prom
    statusz = json.loads(res["/statusz"][1])
    assert statusz["train"]["step"] >= 2
    assert statusz["train"]["steps_per_call"] == 1
    assert statusz["run_id"]
    assert statusz["watchdog"]["enabled"] is True
    assert statusz["checkpoint"]["in_flight"] is False
    # DCN exchange off -> no exchange section (armed form asserted in
    # tests/test_dcn_exchange.py::test_statusz_exchange_section...)
    assert "exchange" not in statusz
    tracez = json.loads(res["/tracez?n=50"][1])
    assert tracez["enabled"] is True and tracez["count"] > 0
    assert any(s["name"] == "train/dispatch" for s in tracez["spans"])
    # shutdown tears the plane down: the port must stop answering
    observe.shutdown()
    with pytest.raises(Exception):
        _get(port, "/healthz")


def test_statusz_unknown_endpoint_404_and_ephemeral_port(clean_plane):
    srv = obs_statusz.start(port=0)         # explicit 0 = ephemeral
    assert srv is not None and srv.port > 0
    code, body = _get(srv.port, "/nope")
    assert code == 404 and "/statusz" in body
    obs_statusz.stop()


def test_statusz_knob_zero_means_off(monkeypatch, clean_plane):
    monkeypatch.setenv("BIGDL_TPU_STATUSZ_PORT", "0")
    assert obs_statusz.start() is None
    assert observe.statusz_server() is None


def test_statusz_serves_engine_stats(clean_plane):
    from bigdl_tpu.serve.engine import ServeEngine
    model = nn.Sequential(nn.Linear(4, 2))
    params, state = model.init(jax.random.PRNGKey(0))
    with ServeEngine() as engine:
        engine.register("m1", model, params, state, max_batch=8)
        engine.predict("m1", np.zeros((3, 4), np.float32))
        payload = obs_statusz.status_payload()
        assert "m1" in payload["serve"]
        assert payload["serve"]["m1"]["requests"] >= 1
        assert "p99_ms" in payload["serve"]["m1"]
    # engine closed -> dropped from the payload; registry-derived SLO
    # fallback still answers (the run's flushed serve metrics remain)
    payload = obs_statusz.status_payload()
    assert "m1" not in (payload["serve"] or {}) \
        or "_from_registry" in payload["serve"]


# -------------------------------------------------------------- watchdog
def _feed_window(wd, neval, wait_s, disp_s):
    """One flush window: record the phase seconds, then observe a
    1-step window whose wall is their sum."""
    observe.histogram("phase/train/data_wait").record(wait_s)
    observe.histogram("phase/train/dispatch").record(disp_s)
    return wd.observe(neval, wait_s + disp_s, 1)


def test_watchdog_flags_3x_slowdown_and_attributes_data_wait(clean_plane):
    wd = obs_doctor.Watchdog(pct=50.0, window=16, sustain=2)
    obs_doctor._watchdog = wd          # /statusz must see THIS watchdog
    for i in range(10):                     # healthy baseline: 100 ms
        assert _feed_window(wd, i, 0.01, 0.09) is None
    assert observe.counter("watchdog/incidents").value == 0
    # injected 3x regression, all of it data-wait
    assert _feed_window(wd, 100, 0.21, 0.09) is None   # 1st bad: counted
    assert observe.counter("watchdog/anomalies").value == 1
    incident = _feed_window(wd, 101, 0.21, 0.09)       # 2nd bad: sustained
    assert incident is not None
    assert incident["phase"] == "train/data_wait"
    assert incident["slowdown_x"] == pytest.approx(3.0, rel=0.05)
    assert observe.counter("watchdog/incidents").value == 1
    assert observe.gauge("watchdog/alert_active").value == 1.0
    assert wd.active_alert() is not None
    # statusz alerts field carries it
    assert obs_statusz.status_payload()["alerts"][-1]["phase"] \
        == "train/data_wait"
    # a second sustained window must NOT open a second incident
    assert _feed_window(wd, 102, 0.21, 0.09) is None
    assert observe.counter("watchdog/incidents").value == 1
    # recovery closes it
    _feed_window(wd, 103, 0.01, 0.09)
    assert wd.active_alert() is None
    assert observe.gauge("watchdog/alert_active").value == 0.0
    assert wd.alerts()[-1]["resolved"] is True


def test_watchdog_attributes_dispatch_regression(clean_plane):
    wd = obs_doctor.Watchdog(pct=50.0, window=16, sustain=1)
    for i in range(8):
        _feed_window(wd, i, 0.01, 0.09)
    incident = _feed_window(wd, 50, 0.01, 0.29)
    assert incident is not None and incident["phase"] == "train/dispatch"


def test_watchdog_baseline_does_not_absorb_slowdown(clean_plane):
    """Anomalous windows stay OUT of the baseline: a persistent 3x
    slowdown keeps the alert active instead of normalizing itself."""
    wd = obs_doctor.Watchdog(pct=50.0, window=8, sustain=1)
    for i in range(8):
        _feed_window(wd, i, 0.01, 0.09)
    for i in range(20):                     # 20 slow windows > window=8
        _feed_window(wd, 100 + i, 0.21, 0.09)
    assert wd.active_alert() is not None    # still alerting


def test_watchdog_disabled_by_knob(clean_plane):
    wd = obs_doctor.Watchdog(pct=0.0)
    for i in range(20):
        assert wd.observe(i, 1.0, 1) is None
    assert not wd.enabled
    assert observe.counter("watchdog/anomalies").value == 0


# ------------------------------------------------------------- forensics
def test_nan_abort_writes_forensics_bundle_and_doctor_parses(
        tmp_path, monkeypatch, clean_plane, capsys):
    from bigdl_tpu.dataset import ArrayDataSet
    from bigdl_tpu.optim.local import Optimizer, NonFiniteLossError
    from bigdl_tpu.optim.method import SGD
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.resilience import faults
    froot = tmp_path / "forensics"
    monkeypatch.setenv("BIGDL_TPU_FORENSICS", str(froot))
    monkeypatch.setenv("BIGDL_TPU_MAX_NONFINITE", "1")
    monkeypatch.setenv("BIGDL_TPU_FAULT", "nan@step:4")
    faults.configure()
    try:
        r = np.random.RandomState(0)
        x = r.randn(160, 6).astype(np.float32)
        y = r.randint(0, 3, 160).astype(np.int32)
        model = nn.Sequential(nn.Linear(6, 3), nn.LogSoftMax())
        ds = ArrayDataSet(x, y, 16, drop_last=True, shuffle=False)
        opt = Optimizer(model, ds, nn.ClassNLLCriterion(), SGD(0.1),
                        seed=0)
        opt._log_every = 2
        opt.set_end_when(Trigger.max_iteration(10))
        with pytest.raises(NonFiniteLossError):
            opt.optimize()
    finally:
        faults.configure("")
    bundles = sorted(froot.glob("forensics-*"))
    assert len(bundles) == 1
    bundle = bundles[0]
    for name in ("meta.json", "metrics.json", "spans.json",
                 "config.json", "statusz.json", "error.txt"):
        assert (bundle / name).exists(), name
    meta = json.loads((bundle / "meta.json").read_text())
    assert meta["reason"] == "nonfinite-loss"
    assert "NonFiniteLossError" in meta["error"]
    assert meta["state"]["neval"] >= 4
    assert "data_state" in meta                  # resume/pipeline state
    cfg = json.loads((bundle / "config.json").read_text())
    assert cfg["BIGDL_TPU_MAX_NONFINITE"] == 1
    m = json.loads((bundle / "metrics.json").read_text())
    assert m["counters"]["train/nonfinite_steps"] >= 1
    sz = json.loads((bundle / "statusz.json").read_text())
    assert sz["train"]["nonfinite_steps"] >= 1
    assert sz["faults"]["events"][0]["kind"] == "nan"
    # the doctor reads it back: phase attribution + top anomalies
    d = obs_doctor.render_doctor(str(bundle))
    assert d["kind"] == "bundle"
    assert d["anomalies"]["nonfinite_steps"] >= 1
    assert any(r["phase"] == "train/dispatch" for r in d["phases"])
    from bigdl_tpu.observe.doctor import doctor_main
    assert doctor_main([str(bundle)]) == 0
    out = capsys.readouterr().out
    assert "nonfinite" in out and "train/dispatch" in out
    assert "NonFiniteLossError" in out


def test_forensics_disabled_by_knob(monkeypatch, clean_plane):
    monkeypatch.setenv("BIGDL_TPU_FORENSICS", "0")
    assert obs_doctor.dump_forensics("test", exc=RuntimeError("x")) is None


def test_forensics_rotation_keeps_newest(tmp_path, monkeypatch,
                                         clean_plane):
    monkeypatch.setenv("BIGDL_TPU_FORENSICS", str(tmp_path))
    for i in range(10):
        p = obs_doctor.dump_forensics(f"r{i}")
        assert p is not None
    left = sorted(tmp_path.glob("forensics-*"))
    assert len(left) == obs_doctor._KEEP_BUNDLES


def test_doctor_reads_jsonl_run_log(tmp_path, clean_plane):
    from bigdl_tpu.observe import export as obs_export
    observe.gauge("train/neval").set(12)
    observe.histogram("phase/train/dispatch").record(0.05)
    jsonl = str(tmp_path / "run.jsonl")
    mgr = obs_export.ExportManager(
        [obs_export.JsonlExporter(jsonl)], flush_s=3600)
    mgr.flush()
    mgr.close()
    d = obs_doctor.render_doctor(jsonl)
    assert d["kind"] == "jsonl" and d["last_step"] == 12
    assert any(r["phase"] == "train/dispatch" for r in d["phases"])


# --------------------------------------------- percentile accuracy (SLO)
def test_histogram_percentile_error_bar_bounded_by_grid(clean_plane):
    """/statusz and the watchdog quote log-bucket percentiles as SLOs:
    the quoted value must BRACKET the true order statistic and the
    bracket must be no wider than the x2 geometric grid ratio —
    documented in docs/observability.md 'Percentile accuracy'."""
    h = Histogram("t")
    samples = np.random.RandomState(7).lognormal(mean=-4.0, sigma=1.5,
                                                 size=1001)
    for v in samples:
        h.record(v)
    s = np.sort(samples)
    for q in (0.5, 0.9, 0.99):
        lo, hi = h.quantile_bounds(q)
        true = s[math.ceil(q * len(s)) - 1]      # exact order statistic
        assert lo <= true <= hi, (q, lo, true, hi)
        assert hi <= 2.0 * lo * (1 + 1e-12), (q, lo, hi)
        assert h.quantile(q) == hi               # quoted = conservative edge
    # serialized (JSONL) form brackets identically
    snap = h.snapshot()
    assert obs_metrics.quantile_from_snapshot(snap, 0.99) \
        == h.quantile(0.99)


def test_serve_slo_from_snapshot(clean_plane):
    from bigdl_tpu.serve.batcher import LATENCY_MS_BOUNDS
    lat = observe.histogram("serve/m1/latency_ms", LATENCY_MS_BOUNDS)
    for v in (1.0, 2.0, 50.0):
        lat.record(v)
    observe.counter("serve/requests").inc(3)
    observe.counter("serve/shed").inc(1)
    observe.histogram("serve/batch_fill").record(0.75)
    slo = obs_metrics.serve_slo(obs_metrics.registry().snapshot())
    assert slo["models"]["m1"]["requests"] == 3
    assert slo["models"]["m1"]["p99_ms"] >= slo["models"]["m1"]["p50_ms"]
    assert slo["totals"]["shed"] == 1
    assert slo["totals"]["mean_batch_fill"] == 0.75
    # report CLI renders the serve section from the same snapshot
    from bigdl_tpu.observe.report import render_report
    rec = {"run_id": "r", "step": 1, **obs_metrics.registry().snapshot()}
    out = render_report([rec])
    assert "serve:" in out and "m1" in out and "shed 1" in out


# ------------------------------------------------- span-catalogue doc rot
_NAME_CALL = re.compile(
    r'(?:counter|gauge|histogram|phase|span|instant)\(\s*(f?)"([^"]+)"')


def _emitted_names():
    names = set()
    for p in (REPO / "bigdl_tpu").rglob("*.py"):
        for m in _NAME_CALL.finditer(p.read_text()):
            is_f, name = m.groups()
            if "/" not in name:
                continue                 # ad-hoc/user names are not catalogued
            if is_f:
                name = re.sub(r"\{[^}]*\}", "*", name)
                name = re.sub(r"\*+", "*", name)
            names.add(name)
    return names


def test_span_catalogue_documented():
    """Every span/counter/gauge/histogram name emitted anywhere in the
    codebase must appear in docs/observability.md — the catalogue table
    cannot silently rot. F-string name segments are wildcarded
    (serve/<model>/latency_ms appears as serve/*/latency_ms)."""
    names = _emitted_names()
    assert len(names) > 40               # the scraper actually scraped
    doc = (REPO / "docs" / "observability.md").read_text()
    missing = sorted(n for n in names if n not in doc)
    assert not missing, (
        f"metric/span names emitted but undocumented in "
        f"docs/observability.md: {missing}")
