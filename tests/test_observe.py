"""Flight-recorder tests (observe/): span tracer + Perfetto JSON schema,
metrics registry + log-bucket histograms, exporters (TensorBoard event-file
round-trip, JSONL, Prometheus textfile), report CLI, the no-extra-host-sync
contract on the instrumented train loops, and bit-identical training with
observability on vs off (reference analogues: Metrics accumulator specs +
TrainSummary/FileReader round-trip specs)."""

import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu import observe
from bigdl_tpu.observe import export as obs_export
from bigdl_tpu.observe import metrics as obs_metrics
from bigdl_tpu.observe import trace as obs_trace
from bigdl_tpu.observe.metrics import Histogram, IterationMetrics
from bigdl_tpu.observe.trace import Tracer, validate_chrome_trace
from bigdl_tpu.utils import crc as crcmod


@pytest.fixture
def clean_observe():
    """Isolate the process-wide recorder: fresh registry, disabled tracer,
    no exporters — restored after the test too."""
    observe.shutdown()
    obs_metrics.registry().reset()
    obs_trace.get_tracer().clear()
    yield
    observe.shutdown()
    obs_metrics.registry().reset()
    obs_trace.get_tracer().clear()


# ------------------------------------------------------------------ CRC32C
def test_crc32c_c_and_py_agree():
    data = bytes(range(256)) * 37
    assert crcmod.crc32c(data) == crcmod.crc32c_py(data)
    # seeded/streamed form must equal one-shot
    mid = len(data) // 3
    assert crcmod.crc32c(data[mid:], crcmod.crc32c(data[:mid])) \
        == crcmod.crc32c(data)
    # the TFRecord mask is a pure function of the crc
    assert crcmod.masked_crc32c(b"x") == \
        ((crcmod.crc32c(b"x") >> 15 | crcmod.crc32c(b"x") << 17)
         + 0xA282EAD8) & 0xFFFFFFFF


def test_crc32c_of_arrays_matches_manifest_usage():
    arr = np.arange(100, dtype=np.float32)
    assert crcmod.crc32c_of(arr) == crcmod.crc32c(arr.tobytes())


# --------------------------------------------- event-file framing round-trip
def test_tb_event_file_roundtrip_scalar_and_histogram(tmp_path):
    """Frame + masked-CRC parse-back through the REAL writer thread
    (satellite: framing now rides the shared accelerated CRC)."""
    from bigdl_tpu.visualization import (EventWriter, parse_records,
                                         parse_histogram_event,
                                         parse_scalar_event)
    w = EventWriter(str(tmp_path))
    w.add_scalar("Loss", 1.25, 3)
    w.add_histogram("weights", np.arange(32.0), 4)
    w.close()
    with open(w.path, "rb") as fh:
        recs = parse_records(fh.read())
    assert len(recs) == 3                       # file-version + 2 events
    assert parse_scalar_event(recs[1]) == ("Loss", 1.25, 3)
    tag, stats, step = parse_histogram_event(recs[2])
    assert (tag, step) == ("weights", 4)
    assert stats["num"] == 32 and stats["max"] == 31.0


def test_frame_record_detects_corruption():
    from bigdl_tpu.visualization import (encode_scalar_event, frame_record,
                                         parse_records)
    blob = bytearray(frame_record(encode_scalar_event("t", 1.0, 1)))
    blob[14] ^= 0xFF                            # flip a payload byte
    with pytest.raises(ValueError, match="corrupt"):
        parse_records(bytes(blob))


def test_histogram_stats_event_roundtrip():
    """The flight recorder's bucket export path: precomputed stats in,
    identical stats back out of the proto."""
    from bigdl_tpu.visualization import (encode_histogram_stats_event,
                                         parse_histogram_event)
    stats = {"min": 0.5, "max": 8.0, "num": 6.0, "sum": 21.0,
             "sum_squares": 100.25, "bucket_limit": [1.0, 4.0, 16.0],
             "bucket": [1.0, 2.0, 3.0]}
    tag, parsed, step = parse_histogram_event(
        encode_histogram_stats_event("lat", stats, 7))
    assert (tag, step) == ("lat", 7)
    assert parsed["bucket_limit"] == stats["bucket_limit"]
    assert parsed["bucket"] == stats["bucket"]
    assert parsed["sum_squares"] == stats["sum_squares"]


# ------------------------------------------------------------- histograms
def test_histogram_log_bucket_boundaries():
    h = Histogram("t", bounds=(1e-3, 1e-2, 1e-1))
    # v <= bound lands in that bucket (Prometheus le semantics)
    h.record(1e-3)          # == bound 0 -> bucket 0
    h.record(2e-3)          # bucket 1
    h.record(1e-1)          # == last bound -> bucket 2
    h.record(5.0)           # overflow bucket
    assert h.counts == [1, 1, 1, 1]
    snap = h.snapshot()
    assert snap["count"] == 4
    assert snap["min"] == 1e-3 and snap["max"] == 5.0
    assert snap["sum"] == pytest.approx(1e-3 + 2e-3 + 1e-1 + 5.0)
    assert snap["sum_squares"] == pytest.approx(
        1e-6 + 4e-6 + 1e-2 + 25.0)


def test_histogram_default_bounds_geometric_and_bounded():
    h = Histogram("t")
    assert all(b2 / b1 == pytest.approx(2.0)
               for b1, b2 in zip(h.bounds, h.bounds[1:]))
    for v in np.random.RandomState(0).lognormal(size=1000):
        h.record(v)
    assert h.count == 1000
    assert len(h.counts) == len(h.bounds) + 1   # memory never grows
    assert h.quantile(0.5) >= h.quantile(0.1)


def test_histogram_rejects_unsorted_bounds():
    with pytest.raises(ValueError, match="ascend"):
        Histogram("t", bounds=(1.0, 0.5))


def test_registry_kind_conflict():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("a")
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("a")


# ----------------------------------------------------------------- tracer
def test_tracer_disabled_is_zero_allocation(clean_observe):
    """With no knob set (what was once the no-op path) a span still lands
    in the ring, with its own timing; the knob only decides whether
    finish() dumps the ring."""
    assert obs_trace.get_tracer().trace_dir is None
    with observe.span("x", cat="test") as s1:
        pass
    with observe.span("y", cat="test") as s2:
        pass
    assert s1 is not s2 and s1.dur_ns >= 0 and s2.t0_ns >= s1.t0_ns
    names = [e[1] for e in obs_trace.get_tracer().events()]
    assert names[-2:] == ["x", "y"]
    assert observe.finish() is None                # nothing dumped


def test_one_span_feeds_ring_histogram_and_annotation(clean_observe,
                                                      monkeypatch):
    """One span, one clock read an edge: the ring event, the phase
    histogram and the profiler annotation all see the same span."""
    entered = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(("enter", self.name))

        def __exit__(self, *exc):
            entered.append(("exit", self.name))
    monkeypatch.setattr(obs_trace, "_Annotation", Annotation)
    with observe.phase("test/p", cat="test", args={"k": 1}) as sp:
        pass
    assert entered == [("enter", "test/p"), ("exit", "test/p")]
    ph, name, cat, _, t0, dur, args = obs_trace.get_tracer().events()[-1]
    assert (ph, name, cat, args) == ("X", "test/p", "test", {"k": 1})
    assert (t0, dur) == (sp.t0_ns, sp.dur_ns)
    h = obs_metrics.registry().snapshot()["histograms"]["phase/test/p"]
    assert h["count"] == 1 and h["sum"] == pytest.approx(dur * 1e-9)


def test_span_is_in_a_live_profiler_capture(clean_observe, tmp_path):
    """No knob: a span's annotation reaches a `jax.profiler` capture."""
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        with observe.span("test/in_capture", cat="test"):
            jnp.ones((4,)).block_until_ready()
    paths = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
             for f in fs if f.endswith(".xplane.pb")]
    names = {ev.name for p in paths
             for plane in ProfileData.from_file(p).planes
             for line in plane.lines for ev in line.events}
    assert "test/in_capture" in names


def test_gc_collect_records_a_pause_span_and_counter(clean_observe):
    """A collection is the span `process/gc` (generation, collected) and
    its pause is added to `process/gc_pause_s` and `phase/process/gc`."""
    import gc
    before = observe.counter("process/gc_pause_s").value
    gc.collect()
    assert observe.counter("process/gc_pause_s").value > before
    gcs = [e for e in obs_trace.get_tracer().events()
           if e[1] == "process/gc"]
    assert gcs and gcs[-1][6]["generation"] == 2
    assert gcs[-1][6]["collected"] >= 0 and gcs[-1][5] > 0
    h = obs_metrics.registry().snapshot()["histograms"]["phase/process/gc"]
    assert h["count"] >= 1


def test_perfetto_trace_schema_and_nesting(tmp_path, clean_observe):
    t = obs_trace.get_tracer()
    t.configure(str(tmp_path))
    with observe.span("outer", cat="test", args={"k": 1}):
        with observe.span("inner", cat="test"):
            pass
    observe.instant("marker", cat="test")

    def other_thread():
        with observe.span("worker-span", cat="test"):
            pass
    th = threading.Thread(target=other_thread, name="worker-0")
    th.start()
    th.join()
    path = t.dump()
    with open(path) as fh:
        doc = json.load(fh)
    assert validate_chrome_trace(doc) == []
    evs = {e["name"]: e for e in doc["traceEvents"]}
    outer, inner = evs["outer"], evs["inner"]
    # spans close inner-first, so inner must nest inside outer's window
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert outer["tid"] == inner["tid"]
    assert evs["marker"]["ph"] == "i"
    assert evs["worker-span"]["tid"] != outer["tid"]
    thread_names = [e["args"]["name"] for e in doc["traceEvents"]
                    if e["name"] == "thread_name"]
    assert "worker-0" in thread_names
    assert evs["outer"]["args"] == {"k": 1}


def test_tracer_ring_is_bounded(clean_observe):
    t = Tracer(ring=16)
    for i in range(100):
        t.record(f"s{i}", "test", i, 1)
    assert len(t.events()) == 16
    assert t.events()[-1][1] == "s99"          # newest survive


# -------------------------------------------------------------- exporters
def _populate_registry():
    observe.counter("train/records").inc(128)
    observe.gauge("train/neval").set(7)
    observe.gauge("train/loss").set(0.5)
    h = observe.histogram("phase/train/dispatch", bounds=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 2.0):
        h.record(v)


def test_jsonl_and_prometheus_exporters(tmp_path, clean_observe):
    _populate_registry()
    jsonl = str(tmp_path / "run.jsonl")
    prom = str(tmp_path / "metrics.prom")
    mgr = obs_export.ExportManager(
        [obs_export.JsonlExporter(jsonl),
         obs_export.PrometheusExporter(prom)], flush_s=3600)
    mgr.flush()
    mgr.flush()
    mgr.close()
    lines = [json.loads(ln) for ln in open(jsonl)]
    assert len(lines) >= 2
    rec = lines[-1]
    assert rec["step"] == 7
    assert rec["counters"]["train/records"] == 128
    assert rec["histograms"]["phase/train/dispatch"]["count"] == 4
    text = open(prom).read()
    assert "# TYPE bigdl_tpu_train_records counter" in text
    assert "bigdl_tpu_train_records 128.0" in text
    assert 'bigdl_tpu_phase_train_dispatch_bucket{le="+Inf"} 4' in text
    # buckets are CUMULATIVE in prometheus format
    assert 'le="1.0"} 3' in text
    assert "bigdl_tpu_phase_train_dispatch_count 4" in text


def test_tensorboard_exporter_roundtrip(tmp_path, clean_observe):
    from bigdl_tpu.visualization import (parse_records,
                                         parse_histogram_event,
                                         parse_scalar_event)
    _populate_registry()
    ex = obs_export.TensorBoardExporter(str(tmp_path / "tb"))
    mgr = obs_export.ExportManager([ex], flush_s=3600)
    mgr.flush()
    ex._writer.flush()
    mgr.close()
    events = []
    for name in os.listdir(ex.log_dir):
        with open(os.path.join(ex.log_dir, name), "rb") as fh:
            events += parse_records(fh.read())
    scalars = [parse_scalar_event(e) for e in events]
    scalars = {s[0]: s for s in scalars if s}
    assert scalars["train/records"] == ("train/records", 128.0, 7)
    hists = [parse_histogram_event(e) for e in events]
    hists = {h[0]: h for h in hists if h}
    tag, stats, step = hists["phase/train/dispatch"]
    assert step == 7 and stats["num"] == 4.0
    assert stats["bucket"] == [1.0, 1.0, 1.0, 1.0]


def test_report_cli_phase_table(tmp_path, clean_observe, capsys):
    from bigdl_tpu.observe.report import main as report_main
    _populate_registry()
    jsonl = str(tmp_path / "run.jsonl")
    mgr = obs_export.ExportManager(
        [obs_export.JsonlExporter(jsonl)], flush_s=3600)
    mgr.flush()
    mgr.close()
    assert report_main([jsonl]) == 0
    out = capsys.readouterr().out
    assert "train/dispatch" in out
    assert "phase" in out and "share" in out
    assert "train/records" in out


def test_report_cli_trace_validation(tmp_path, clean_observe, capsys):
    from bigdl_tpu.observe.report import main as report_main
    t = obs_trace.get_tracer()
    t.configure(str(tmp_path))
    with observe.span("s"):
        pass
    path = t.dump()
    assert report_main(["--trace", path]) == 0
    assert "VALID" in capsys.readouterr().out


# ----------------------------------------------- registry/trainer contract
def _train(k, tmp_path, monkeypatch, instrumented, tag, iters=8):
    from bigdl_tpu.dataset import ArrayDataSet
    from bigdl_tpu.observe import doctor as obs_doctor
    from bigdl_tpu.optim.local import Optimizer
    from bigdl_tpu.optim.method import SGD
    from bigdl_tpu.optim.trigger import Trigger
    obs_doctor.reset_watchdog()          # re-read the WATCHDOG_PCT knob

    if instrumented:
        monkeypatch.setenv("BIGDL_TPU_TRACE",
                           str(tmp_path / f"trace_{tag}"))
        monkeypatch.setenv("BIGDL_TPU_METRICS_JSONL",
                           str(tmp_path / f"run_{tag}.jsonl"))
        monkeypatch.setenv("BIGDL_TPU_METRICS_PROM",
                           str(tmp_path / f"m_{tag}.prom"))
        monkeypatch.setenv("BIGDL_TPU_METRICS_FLUSH_S", "3600")
        # the LIVE plane too: statusz HTTP server + watchdog armed —
        # bit-identity and the sync count must hold with everything on
        import socket
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        monkeypatch.setenv("BIGDL_TPU_STATUSZ_PORT", str(port))
        monkeypatch.setenv("BIGDL_TPU_WATCHDOG_PCT", "50")
    else:
        for kk in ("BIGDL_TPU_TRACE", "BIGDL_TPU_METRICS_JSONL",
                   "BIGDL_TPU_METRICS_PROM", "BIGDL_TPU_METRICS_FLUSH_S",
                   "BIGDL_TPU_STATUSZ_PORT"):
            monkeypatch.delenv(kk, raising=False)
        monkeypatch.setenv("BIGDL_TPU_WATCHDOG_PCT", "0")
    r = np.random.RandomState(0)
    x = r.randn(16 * (iters + 2), 6).astype(np.float32)
    y = r.randint(0, 3, len(x)).astype(np.int32)
    model = nn.Sequential(nn.Linear(6, 3), nn.LogSoftMax())
    ds = ArrayDataSet(x, y, 16, drop_last=True, shuffle=False)
    opt = Optimizer(model, ds, nn.ClassNLLCriterion(), SGD(0.1),
                    seed=3, steps_per_call=k)
    opt._log_every = 4
    opt.set_end_when(Trigger.max_iteration(iters))
    syncs = {"n": 0}
    real_get = jax.device_get

    def counting_get(x):
        syncs["n"] += 1
        return real_get(x)
    monkeypatch.setattr(jax, "device_get", counting_get)
    params, _ = opt.optimize()
    monkeypatch.setattr(jax, "device_get", real_get)
    observe.shutdown()
    return params, opt.slots, opt._step_rng, syncs["n"]


@pytest.mark.parametrize("k", [1, 4])
def test_observability_bit_identical_and_no_extra_syncs(
        k, tmp_path, monkeypatch, clean_observe):
    """Acceptance: params/slots/rng bit-identical with the flight
    recorder fully on vs off, AND the instrumented loop performs exactly
    the same number of host syncs (jax.device_get) — metrics ride the
    existing _pending/_flush_metrics cadence."""
    p_off, s_off, rng_off, syncs_off = _train(
        k, tmp_path, monkeypatch, False, f"off{k}")
    obs_metrics.registry().reset()
    obs_trace.get_tracer().clear()
    p_on, s_on, rng_on, syncs_on = _train(
        k, tmp_path, monkeypatch, True, f"on{k}")
    # the ring records in both runs; 2 is the count the loop had when the
    # ring recorded only under BIGDL_TPU_TRACE
    assert syncs_on == syncs_off == 2
    for a, b in zip(jax.tree.leaves(p_off), jax.tree.leaves(p_on)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(s_off), jax.tree.leaves(s_on)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(rng_off), np.asarray(rng_on))
    # and the instrumented run actually recorded the step timeline
    trace_file = tmp_path / f"trace_on{k}" / "trace.p0.json"
    with open(trace_file) as fh:
        doc = json.load(fh)
    assert validate_chrome_trace(doc) == []
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"train/data_wait", "train/dispatch", "train/flush",
            "data/placement"} <= names


def test_instrumented_optimize_records_checkpoint_phases(
        tmp_path, monkeypatch, clean_observe):
    """A real optimize() with checkpointing: the trace carries every
    phase the acceptance criteria name, the JSONL drives the report CLI,
    and _ckpt_stalls stays bounded."""
    from bigdl_tpu.dataset import ArrayDataSet
    from bigdl_tpu.optim.local import Optimizer
    from bigdl_tpu.optim.method import SGD
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.observe.report import render_report
    monkeypatch.setenv("BIGDL_TPU_TRACE", str(tmp_path / "trace"))
    monkeypatch.setenv("BIGDL_TPU_METRICS_JSONL",
                       str(tmp_path / "run.jsonl"))
    monkeypatch.setenv("BIGDL_TPU_METRICS_FLUSH_S", "3600")
    r = np.random.RandomState(0)
    x = r.randn(160, 6).astype(np.float32)
    y = r.randint(0, 3, 160).astype(np.int32)
    model = nn.Sequential(nn.Linear(6, 3), nn.LogSoftMax())
    ds = ArrayDataSet(x, y, 16, drop_last=True, shuffle=False)
    opt = Optimizer(model, ds, nn.ClassNLLCriterion(), SGD(0.1), seed=0)
    opt._log_every = 4
    opt.set_checkpoint(str(tmp_path / "ck"), Trigger.several_iteration(4))
    opt.set_end_when(Trigger.max_iteration(8))
    opt.optimize()
    observe.shutdown()
    assert isinstance(opt._ckpt_stalls.maxlen, int)   # bounded (deque)
    with open(tmp_path / "trace" / "trace.p0.json") as fh:
        doc = json.load(fh)
    assert validate_chrome_trace(doc) == []
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"train/data_wait", "data/placement", "train/dispatch",
            "train/flush", "train/checkpoint", "checkpoint/plan",
            "checkpoint/persist"} <= names
    recs = [json.loads(ln) for ln in open(tmp_path / "run.jsonl")]
    report = render_report(recs)
    for phase_name in ("train/dispatch", "train/checkpoint",
                       "train/data_wait"):
        assert phase_name in report
    hist = recs[-1]["histograms"]["phase/train/checkpoint"]
    assert hist["count"] == len(opt._ckpt_stalls) == 2


# --------------------------------------------------------- multihost guard
def test_summary_only_process0_writes(tmp_path, monkeypatch):
    from bigdl_tpu import visualization as viz
    from bigdl_tpu.utils import runtime
    monkeypatch.setattr(runtime, "process_index", lambda: 1)
    s = viz.TrainSummary(str(tmp_path), "app")
    s.add_scalar("Loss", 1.0, 1)
    s.close()
    assert not os.path.isdir(s.log_dir)          # no event dir at all
    assert s.read_scalar("Loss") == []
    monkeypatch.setattr(runtime, "process_index", lambda: 0)
    s0 = viz.TrainSummary(str(tmp_path), "app")
    s0.add_scalar("Loss", 2.0, 1)
    assert s0.read_scalar("Loss") == [(1, 2.0)]
    s0.close()


def test_log_prefix_structured(caplog):
    import logging
    from bigdl_tpu.utils import runtime
    runtime.install_log_prefix()
    log = logging.getLogger("bigdl_tpu")
    with caplog.at_level(logging.INFO, logger="bigdl_tpu"):
        log.info("hello %d", 42)
    msg = caplog.records[-1].getMessage()
    assert msg.endswith("hello 42")
    assert msg.startswith("[p0 ")                # process idx + run id


def test_jsonl_exporter_process_suffix(tmp_path, monkeypatch,
                                       clean_observe):
    from bigdl_tpu.utils import runtime
    monkeypatch.setattr(runtime, "process_index", lambda: 2)
    monkeypatch.setattr(obs_export, "process_index", lambda: 2)
    ex = obs_export.JsonlExporter(str(tmp_path / "run.jsonl"))
    ex.export({"counters": {}, "gauges": {}, "histograms": {}}, 0)
    ex.close()
    assert os.path.exists(tmp_path / "run.jsonl.p2")


# ------------------------------------------------------- compile listener
def test_jit_compile_counter(clean_observe, monkeypatch):
    for kk in ("BIGDL_TPU_TRACE", "BIGDL_TPU_METRICS_JSONL",
               "BIGDL_TPU_METRICS_PROM"):
        monkeypatch.delenv(kk, raising=False)
    observe.ensure_started()
    before = observe.counter("jit/compiles").value
    f = jax.jit(lambda x: x * 3.0 + 1.5)   # fresh fn object -> fresh compile
    f(jnp.ones((3,)))
    assert observe.counter("jit/compiles").value >= before + 1
    assert observe.counter("jit/compile_seconds").value > 0.0


def test_jit_compile_counter_dedupes_duration_and_plain_events(
        clean_observe, monkeypatch):
    """Some jax versions fire BOTH record_event_duration_secs AND
    record_event with the same key for one compilation; counting both
    double-counted jit/compiles. The plain-event listener must skip every
    duration-owned key (observe._DURATION_OWNED)."""
    for kk in ("BIGDL_TPU_TRACE", "BIGDL_TPU_METRICS_JSONL",
               "BIGDL_TPU_METRICS_PROM"):
        monkeypatch.delenv(kk, raising=False)
    observe.ensure_started()
    before = observe.counter("jit/compiles").value
    # one compilation, both monitoring callbacks fire with the same key
    key = "/jax/compilation_cache/backend_compile_duration"
    observe._on_jax_duration(key, 0.25)
    observe._on_jax_event(key)
    assert observe.counter("jit/compiles").value == before + 1
    # same discipline for the cache-retrieval timing key
    rkey = "/jax/compilation_cache/cache_retrieval_time_sec"
    observe._on_jax_duration(rkey, 0.01)
    observe._on_jax_event(rkey)
    assert observe.counter("jit/compiles").value == before + 1
    # the NEXT duration event is flagged as a cache hit by the
    # retrieval marker the previous pair set
    observe._on_jax_duration(key, 0.02)
    assert observe.counter("jit/compiles").value == before + 2
    assert observe.counter("jit/cache_hit_compiles").value == 1
    # hit/miss plain events are NOT duration-owned: they count normally
    observe._on_jax_event("/jax/compilation_cache/cache_hits")
    observe._on_jax_event("/jax/compilation_cache/cache_misses")
    assert observe.counter("jit/cache_hits").value == 1
    assert observe.counter("jit/cache_misses").value == 1


# ------------------------------------------------------ resilience events
def test_retry_and_fault_counters(clean_observe, monkeypatch):
    from bigdl_tpu.resilience.retry import RetryPolicy
    from bigdl_tpu.resilience import faults
    pol = RetryPolicy(max_retries=3, window_s=60, backoff_s=0)
    pol.record_failure()
    pol.record_failure()
    assert observe.counter("resilience/retries").value == 2
    faults.configure("step:1:crash")
    with pytest.raises(faults.SimulatedCrash):
        faults.check_step(5)
    assert observe.counter("resilience/faults_injected").value == 1
    faults.configure("")                          # disarm for other tests


# -------------------------------------------------- IterationMetrics move
def test_iteration_metrics_reexport_and_mirror(clean_observe):
    from bigdl_tpu.utils.profile import IterationMetrics as Legacy
    assert Legacy is IterationMetrics
    m = IterationMetrics(mirror=True, prefix="custom/")
    m.add("fwd", 0.25)
    with m.time("fwd"):
        pass
    assert "fwd: total" in m.summary()
    snap = obs_metrics.registry().snapshot()
    assert snap["histograms"]["phase/custom/fwd"]["count"] == 2
