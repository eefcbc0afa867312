"""Device-memory observability tests (observe/memz.py — ISSUE 15):
buffer-ledger lifecycle (register → bytes appear, unregister/GC → back
to baseline), the decode KV pool accounted EXACTLY against the closed
form, unattributed drift ~0 on the clean path, the /memz live plane
scraped during a real optimize(), the memory watchdog opening exactly
ONE incident attributed to the fastest-growing owner, serve admission
refusal with a capacity report, OOM forensics round-tripping through
`observe doctor --json`, and the `observe memz` CLI smoke."""

import gc
import json
import os
import pathlib
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu import observe
from bigdl_tpu.observe import doctor as obs_doctor
from bigdl_tpu.observe import memz
from bigdl_tpu.observe import metrics as obs_metrics
from bigdl_tpu.observe import statusz as obs_statusz
from bigdl_tpu.observe import trace as obs_trace

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture
def clean_mem():
    """Fresh ledger/registry/watchdogs per test."""
    observe.shutdown()
    memz.reset()
    obs_metrics.registry().reset()
    obs_trace.get_tracer().clear()
    obs_doctor.reset_watchdog()
    yield
    observe.shutdown()
    memz.reset()
    obs_metrics.registry().reset()
    obs_trace.get_tracer().clear()
    obs_doctor.reset_watchdog()


def _get(port, path):
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                    timeout=30) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


# ------------------------------------------------------ ledger lifecycle
def test_ledger_register_bytes_appear_and_release(clean_mem):
    led = memz.ledger()
    tree = {"w": np.zeros((128, 64), np.float32),
            "b": np.zeros((64,), np.float32)}
    want = 128 * 64 * 4 + 64 * 4
    h = led.register("t/params", tree, kind="params")
    assert led.owners()["t/params"]["bytes"] == want
    assert led.total_bytes() == want
    assert observe.gauge("mem/t/params/bytes").value == want
    assert observe.gauge("mem/ledger/total_bytes").value == want
    assert observe.gauge("mem/ledger/owners").value == 1
    # update re-measures (the failover re-shard path)
    h.update({"w": np.zeros((64, 64), np.float32)})
    assert led.owners()["t/params"]["bytes"] == 64 * 64 * 4
    # peak is a high-water mark across updates
    assert led.owners()["t/params"]["peak_bytes"] == want
    # unregister: bytes return to baseline, gauge zeroed, release counted
    h.close()
    assert "t/params" not in led.owners()
    assert led.total_bytes() == 0
    assert observe.gauge("mem/t/params/bytes").value == 0
    assert observe.counter("mem/ledger/releases").value == 1


def test_ledger_weakref_finalized_on_anchor_gc(clean_mem):
    led = memz.ledger()

    class Anchor:
        pass

    a = Anchor()
    led.register("gc/owner", np.zeros((32,), np.float32), anchor=a)
    assert led.owners()["gc/owner"]["bytes"] == 128
    del a
    gc.collect()
    assert "gc/owner" not in led.owners()
    assert observe.gauge("mem/gc/owner/bytes").value == 0


def test_ledger_tracker_deltas_and_knob_off(clean_mem, monkeypatch):
    led = memz.ledger()
    t = led.tracker("data/staging")
    t.add_bytes(1000)
    t.add_bytes(500)
    assert led.owners()["data/staging"]["bytes"] == 1500
    t.add_bytes(-1500)
    assert led.owners()["data/staging"]["bytes"] == 0
    assert led.owners()["data/staging"]["peak_bytes"] == 1500
    # MEM_LEDGER=0: registration is inert (no-op handles, no owners)
    monkeypatch.setenv("BIGDL_TPU_MEM_LEDGER", "0")
    h = led.register("off/owner", np.zeros((8,), np.float32))
    h.update(np.zeros((16,), np.float32))
    h.close()
    assert "off/owner" not in led.owners()


def test_prefetch_staging_bytes_return_to_zero(clean_mem):
    from bigdl_tpu.dataset.prefetch import prefetch_to_device
    led = memz.ledger()
    batches = [(np.zeros((4, 8), np.float32),
                np.zeros((4,), np.int32)) for _ in range(6)]
    it = prefetch_to_device(iter(batches), size=2,
                            place_fn=lambda b: b)
    first = next(it)
    assert first[0].shape == (4, 8)
    # abandon mid-epoch: the drain path must give the bytes back too
    it.close()
    assert led.owners()["data/staging"]["bytes"] == 0
    # full consumption also lands on exactly zero
    it = prefetch_to_device(iter(batches), size=2, place_fn=lambda b: b)
    assert len(list(it)) == 6
    assert led.owners()["data/staging"]["bytes"] == 0
    assert led.owners()["data/staging"]["peak_bytes"] > 0


# --------------------------------------------------- decode pool account
def test_decode_kv_bucket_accounted_exactly_closed_form(clean_mem):
    from bigdl_tpu.serve.decode import decode_demo_model
    from bigdl_tpu.serve.engine import ServeEngine
    layers, heads, d_model, slots, seq, block = 2, 4, 32, 4, 32, 8
    model, params, state = decode_demo_model(
        num_layers=layers, d_model=d_model, num_heads=heads)
    eng = ServeEngine()
    entry = eng.register("lm", model, params, state, decode=True,
                         num_slots=slots, max_seq_len=seq, kv_block=block,
                         kv_pool_blocks=10, precompile_decode=False)
    # the pool: pool_blocks x kv_block x layers x heads x 2*hd (K's lanes
    # then V's) x itemsize, whatever slots x max_seq_len would come to
    # (the pool's live block accounting is in test_decode.py)
    hd = d_model // heads
    assert (entry.decode.pool_blocks, entry.decode.kv_block) == (10, block)
    want = 10 * block * layers * heads * 2 * hd * 4
    owners = memz.ledger().owners()
    assert owners["serve/lm/kv_pool"]["bytes"] == want
    assert entry.decode.kv_cache_bytes == want
    assert owners["serve/lm/kv_pool"]["meta"]["slots"] == slots
    assert owners["serve/lm/kv_pool"]["meta"]["bytes_per_block"] == \
        want // 10
    assert owners["serve/lm/params"]["bytes"] == \
        memz.tree_nbytes(params) + memz.tree_nbytes(state)
    # engine/entry teardown returns the pool's bytes to baseline
    eng.shutdown()
    assert "serve/lm/kv_pool" not in memz.ledger().owners()
    eng.registry.unregister("lm")
    assert "serve/lm/params" not in memz.ledger().owners()


# --------------------------------------------------- drift + /memz plane
def test_unattributed_drift_near_zero_on_clean_path(clean_mem):
    import jax.numpy as jnp
    memz.ledger().set_baseline()
    tree = {"w": jnp.zeros((256, 128), jnp.float32)}
    memz.ledger().register("t/params", tree, kind="params")
    util = memz.ledger().utilization()
    assert util["ledger_bytes"] == 256 * 128 * 4
    # every byte allocated since the baseline is attributed
    assert abs(util["unattributed_bytes"]) <= 1024
    assert abs(util["unattributed_pct"]) < 5.0
    assert observe.gauge("mem/unattributed_bytes").value == \
        util["unattributed_bytes"]


def test_headroom_estimates_from_limit(clean_mem, monkeypatch):
    led = memz.ledger()
    led.set_baseline()
    kv = tuple(np.zeros((2, 8, 8, 16), np.float32) for _ in range(2))
    led.register("serve/lm/kv_pool", kv, kind="kv_pool",
                 meta={"blocks": 8, "block": 8, "blocks_free": 5,
                       "bytes_per_block": 2 * 2 * 8 * 16 * 4})
    led.register("serve/lm/params", nbytes=10_000, kind="params")
    in_use = memz.backend_in_use()[0]
    monkeypatch.setenv("BIGDL_TPU_MEM_LIMIT_BYTES", str(in_use + 50_000))
    head = led.headroom()
    assert head["free_bytes"] == pytest.approx(50_000, abs=2048)
    assert head["kv_pools"]["serve/lm/kv_pool"] == {
        "blocks": 8, "blocks_free": 5, "block_tokens": 8,
        "bytes_per_block": 2 * 2 * 8 * 16 * 4}
    assert head["one_more_model"]["fits"] is True
    monkeypatch.setenv("BIGDL_TPU_MEM_LIMIT_BYTES", str(in_use + 5_000))
    assert led.headroom()["one_more_model"]["fits"] is False


def test_memz_endpoint_and_statusz_memory_section(clean_mem):
    led = memz.ledger()
    led.set_baseline()
    led.register("serve/m/kv_cache", nbytes=4096, kind="kv_cache",
                 meta={"slots": 2})
    led.register("trainer/params", nbytes=1024, kind="params")
    srv = obs_statusz.StatuszServer(0)
    try:
        code, body = _get(srv.port, "/memz")
        assert code == 200
        p = json.loads(body)
        assert p["owners"]["serve/m/kv_cache"]["bytes"] == 4096
        assert p["top_owner"]["owner"] == "serve/m/kv_cache"
        assert p["utilization"]["bytes_in_use"] >= 0
        assert "headroom" in p and "top_buffers" in p
        # the compact per-peer section rides /statusz (fleet merges it)
        code, body = _get(srv.port, "/statusz")
        mem = json.loads(body)["memory"]
        assert mem["ledger_bytes"] == 5120
        assert mem["top_owner"] == "serve/m/kv_cache"
        # /memz is advertised on the 404 map
        code, body = _get(srv.port, "/nope")
        assert "/memz" in json.loads(body)["endpoints"]
    finally:
        srv.close()


class _ScrapingDataSet:
    """Holds one batch back mid-epoch and scrapes /memz while
    optimize() is in flight (the test_statusz discipline)."""

    def __init__(self, ds, port, at=3):
        self.ds, self.port, self.at = ds, port, at
        self.results = {}

    def __iter__(self):
        for i, batch in enumerate(iter(self.ds)):
            if i == self.at and not self.results:
                self.results["/memz"] = _get(self.port, "/memz")
            yield batch


def test_memz_scraped_during_live_optimize(clean_mem, monkeypatch):
    """ISSUE 15 acceptance leg: /memz scraped DURING a live optimize()
    shows every registered trainer owner with ledger-vs-backend drift
    well under the 5% bar."""
    import socket
    from bigdl_tpu.dataset import ArrayDataSet
    from bigdl_tpu.optim.local import Optimizer
    from bigdl_tpu.optim.method import SGD
    from bigdl_tpu.optim.trigger import Trigger
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    monkeypatch.setenv("BIGDL_TPU_STATUSZ_PORT", str(port))
    r = np.random.RandomState(0)
    x = r.randn(160, 64).astype(np.float32)
    y = r.randint(0, 3, 160).astype(np.int32)
    # params must DOMINATE the in-flight batch for the drift bar to be
    # meaningful (exactly the real-workload shape: resident trees >>
    # one batch) — a 64x512 tower is ~140 KiB vs a 4 KiB batch
    model = nn.Sequential(nn.Linear(64, 512), nn.Linear(512, 3),
                          nn.LogSoftMax())
    ds = _ScrapingDataSet(
        ArrayDataSet(x, y, 16, drop_last=True, shuffle=False), port)
    opt = Optimizer(model, ds, nn.ClassNLLCriterion(), SGD(0.1), seed=0)
    opt.set_end_when(Trigger.max_iteration(10))
    opt.optimize()
    code, body = ds.results["/memz"]
    assert code == 200
    p = json.loads(body)
    for owner in ("trainer/params", "trainer/slots",
                  "trainer/model_state", "data/staging"):
        assert owner in p["owners"], sorted(p["owners"])
    assert p["owners"]["trainer/params"]["bytes"] > 64 * 512 * 4
    assert abs(p["utilization"]["unattributed_pct"]) < 5.0


# ------------------------------------------------------- memory watchdog
def test_memory_watchdog_one_incident_names_growing_owner(
        clean_mem, monkeypatch):
    """An injected memory-growth leak opens exactly ONE incident
    attributed to the growing owner (ISSUE 15 acceptance)."""
    monkeypatch.setenv("BIGDL_TPU_MEM_LIMIT_BYTES", "1000000")
    led = memz.ledger()
    led.set_baseline()
    steady = led.register("trainer/params", nbytes=100_000,
                          kind="params")
    leak = led.register("serve/lm/kv_cache", nbytes=100_000,
                        kind="kv_cache", meta={"slots": 4})
    in_use = {"v": 400_000}
    monkeypatch.setattr(memz, "backend_in_use",
                        lambda: (in_use["v"], 1_000_000, "fake"))
    monkeypatch.setenv("BIGDL_TPU_MEM_WATCHDOG_PCT", "80")
    wd = memz.memory_watchdog()        # the process-wide singleton —
    # doctor.incident_active() (the capture-on-crash gate) reads it
    for _ in range(6):                 # healthy polls feed the baselines
        assert wd.poll() is None
    # the leak: one owner grows poll over poll, utilization crosses 80%
    opened = []
    for step in range(1, 7):
        leak.add_bytes(120_000)
        in_use["v"] += 120_000
        inc = wd.poll()
        if inc:
            opened.append(inc)
    assert len(opened) == 1, opened    # exactly ONE incident
    inc = opened[0]
    assert inc["phase"] == "serve/lm/kv_cache"     # the growing owner
    assert inc["signal"] == "mem_utilization_pct"
    assert inc["value"] > 80.0
    assert inc["top_owner"] == "serve/lm/kv_cache"
    assert observe.counter("watchdog/memory/incidents").value == 1
    assert wd.active_alert() is not None
    assert obs_doctor.incident_active()            # capture-on-crash gate
    # recovery closes it
    leak.add_bytes(-600_000)
    in_use["v"] = 400_000
    wd.poll()
    assert wd.active_alert() is None
    assert steady.owner == "trainer/params"        # untouched


def test_memory_watchdog_skips_without_limit(clean_mem, monkeypatch):
    monkeypatch.delenv("BIGDL_TPU_MEM_LIMIT_BYTES", raising=False)
    wd = memz.MemoryWatchdog(pct=80.0)
    assert wd.poll() is None           # CPU census has no bytes_limit
    assert memz.arm_memory_watchdog() is False
    monkeypatch.setenv("BIGDL_TPU_MEM_WATCHDOG_PCT", "0")
    memz.stop_memory_watchdog()
    assert memz.memory_watchdog().enabled is False


# --------------------------------------------------------- OOM forensics
def test_oom_forensics_bundle_roundtrips_through_doctor(
        clean_mem, monkeypatch, tmp_path, capsys):
    """A forced RESOURCE_EXHAUSTED produces a forensics bundle whose
    memory.json names the top owner, plus the pprof memory.prof; the
    bundle round-trips through `observe doctor --json` (ISSUE 15
    acceptance)."""
    from bigdl_tpu.dataset import ArrayDataSet
    from bigdl_tpu.optim.local import Optimizer
    from bigdl_tpu.optim.method import SGD
    monkeypatch.setenv("BIGDL_TPU_FORENSICS", str(tmp_path))
    led = memz.ledger()
    led.set_baseline()
    led.register("serve/lm/kv_cache", nbytes=9_999_999, kind="kv_cache",
                 meta={"slots": 8})
    x = np.zeros((32, 4), np.float32)
    y = np.zeros((32,), np.int32)
    opt = Optimizer(nn.Sequential(nn.Linear(4, 2), nn.LogSoftMax()),
                    ArrayDataSet(x, y, 8), nn.ClassNLLCriterion(),
                    SGD(0.1), seed=0)

    def boom():
        raise RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory while trying to "
            "allocate 12345678 bytes")

    opt._optimize_impl = boom
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        opt.optimize()
    bundles = sorted(tmp_path.glob("forensics-*"))
    assert len(bundles) == 1
    b = bundles[0]
    meta = json.loads((b / "meta.json").read_text())
    assert meta["reason"] == "resource-exhausted"
    mem = json.loads((b / "memory.json").read_text())
    assert mem["top_owner"]["owner"] == "serve/lm/kv_cache"
    assert "serve/lm/kv_cache" in mem["headline"]
    assert mem["owners"]["serve/lm/kv_cache"]["bytes"] == 9_999_999
    # the pprof device-memory profile rides the same bundle
    assert (b / "memory.prof").exists()
    assert (b / "memory.prof").stat().st_size > 0
    # doctor --json carries the memory section verbatim
    rc = obs_doctor.doctor_main([str(b), "--json"])
    assert rc == 0
    d = json.loads(capsys.readouterr().out)
    assert d["memory"]["top_owner"]["owner"] == "serve/lm/kv_cache"
    assert d["meta"]["reason"] == "resource-exhausted"
    # and the human rendering prints the crash-time memory table
    rc = obs_doctor.doctor_main([str(b)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "device memory at crash time" in out
    assert "serve/lm/kv_cache" in out


def test_serve_dispatch_oom_dumps_bundle_and_fails_request(
        clean_mem, monkeypatch, tmp_path):
    from bigdl_tpu.serve.batcher import ContinuousBatcher
    monkeypatch.setenv("BIGDL_TPU_FORENSICS", str(tmp_path))

    def oom_dispatch(xs, n):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    b = ContinuousBatcher(oom_dispatch, (4,), name="m", start=False)
    fut = b.submit(np.zeros((2, 3), np.float32))
    b._run_batch(b._take())
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        fut.result(timeout=5)
    bundles = sorted(tmp_path.glob("forensics-*"))
    assert len(bundles) == 1
    meta = json.loads((bundles[0] / "meta.json").read_text())
    assert meta["reason"] == "serve-resource-exhausted"
    assert meta["model"] == "m"
    assert (bundles[0] / "memory.json").exists()
    b.close(drain=False)


# ----------------------------------------------------- admission control
def test_decode_admission_refused_with_capacity_report(
        clean_mem, monkeypatch):
    from bigdl_tpu.serve.decode import decode_demo_model
    from bigdl_tpu.serve.engine import ServeEngine
    model, params, state = decode_demo_model(num_layers=2, d_model=32,
                                             num_heads=4)
    in_use = memz.backend_in_use()[0]
    # leave less headroom than params + the KV pool need
    monkeypatch.setenv("BIGDL_TPU_MEM_LIMIT_BYTES", str(in_use + 10_000))
    eng = ServeEngine()
    with pytest.raises(memz.CapacityError) as ei:
        eng.register("lm", model, params, state, decode=True,
                     num_slots=8, max_seq_len=256,
                     precompile_decode=False)
    msg = str(ei.value)
    assert "paged pool" in msg
    assert "bytes" in msg and "/memz" in msg
    assert observe.counter("mem/admission_refused").value == 1
    # nothing was registered (no half-registered model, no scheduler)
    assert eng.models() == []
    owners = memz.ledger().owners()
    assert "serve/lm/kv_pool" not in owners
    # with the limit lifted the same registration succeeds
    monkeypatch.delenv("BIGDL_TPU_MEM_LIMIT_BYTES")
    eng.register("lm", model, params, state, decode=True, num_slots=4,
                 max_seq_len=32, precompile_decode=False)
    assert eng.models() == ["lm"]
    eng.shutdown()


def test_decode_admission_counts_resident_params_once(clean_mem,
                                                      monkeypatch):
    """Parameters that already lie on the device are in the backend's
    bytes in use: the check asks for the cache beside them, not for a
    second copy (a model of half the chip's memory could not register).
    The same parameters on the host have yet to be placed, and count."""
    import jax
    from bigdl_tpu.serve.decode import DecodeEntry, decode_demo_model
    model, params, _ = decode_demo_model(num_layers=2, d_model=64,
                                         num_heads=4)
    kw = dict(num_slots=2, max_seq_len=32, kv_block=8, kv_pool_blocks=8,
              paged=True)
    cache = DecodeEntry("probe", model, params, **kw).kv_cache_bytes
    assert memz.tree_nbytes(params) > 4 * cache
    in_use = memz.backend_in_use()[0]
    monkeypatch.setenv("BIGDL_TPU_MEM_LIMIT_BYTES",
                       str(in_use + 2 * cache))
    DecodeEntry("resident", model, params, **kw)
    on_host = jax.tree.map(np.asarray, params)
    with pytest.raises(memz.CapacityError, match="yet to be placed"):
        DecodeEntry("on_host", model, on_host, **kw)


# ------------------------------------------------------------ shims + CLI
def test_profile_shim_routes_through_memz(clean_mem, tmp_path):
    from bigdl_tpu.utils import profile as uprofile
    # CPU backend reports no memory_stats -> {} (the historical contract)
    assert uprofile.device_memory_summary() == \
        memz.device_memory_summary()
    out = uprofile.memory_profile(str(tmp_path / "m.prof"))
    assert os.path.getsize(out) > 0
    assert observe.counter("mem/profiles_saved").value >= 1


def test_memz_cli_smoke_and_drift_gate():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.observe", "memz", "--smoke",
         "--json"], capture_output=True, text=True, env=env, timeout=60)
    assert r.returncode == 0, r.stderr[-2000:]
    doc = json.loads(r.stdout)
    assert doc["ok"] is True
    assert doc["owners"]["serve/demo/kv_cache"]["bytes"] == 131072
    assert doc["owners"]["trainer/params"]["kind"] == "params"
    assert doc["drift_pct"] <= doc["threshold_pct"]
    assert doc["utilization"]["source"] in ("live_arrays",
                                            "memory_stats")
    # rc 1 when the drift gate is made unpassable
    r = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.observe", "memz", "--smoke",
         "--json", "--max-drift-pct", "-1"],
        capture_output=True, text=True, env=env, timeout=30)
    assert r.returncode == 1
    # human table renders
    r = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.observe", "memz", "--smoke"],
        capture_output=True, text=True, env=env, timeout=30)
    assert r.returncode == 0
    assert "serve/demo/kv_cache" in r.stdout
    assert "drift check" in r.stdout
