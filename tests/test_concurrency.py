"""Concurrency doctor (ISSUE 11): sanctioned thread/lock wrappers, the
runtime sanitizer (lock-order cycles, lockset races, host-sync
attribution), the thread-inventory CLI, and the thread-shutdown audit.

The injected-bug tests are the acceptance spine: a deliberate lock-order
inversion and a seeded unlocked write each produce EXACTLY ONE report
with module/line attribution, while the hammer test drives the real
serve + input-service + statusz paths concurrently under
BIGDL_TPU_SANITIZE=1 and demands zero findings.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import jax

import bigdl_tpu.nn as nn
from bigdl_tpu.analysis import sancov
from bigdl_tpu.analysis.__main__ import main as analysis_main, threads_payload
from bigdl_tpu.utils import threads as uthreads

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def sanitize(monkeypatch):
    """Enable every sanitizer mode for the test, restore + wipe after."""
    sancov.reset()
    monkeypatch.setenv("BIGDL_TPU_SANITIZE", "1")
    sancov.refresh()
    assert sancov.LOCKS_ON and sancov.SYNC_ON
    yield sancov
    monkeypatch.delenv("BIGDL_TPU_SANITIZE", raising=False)
    sancov.refresh()
    sancov.reset()


# ----------------------------------------------------------- default path
def test_factories_are_stock_primitives_when_off(monkeypatch):
    monkeypatch.delenv("BIGDL_TPU_SANITIZE", raising=False)
    sancov.refresh()
    assert type(uthreads.make_lock("t.off")) is type(threading.Lock())
    assert isinstance(uthreads.make_condition("t.off"),
                      threading.Condition)
    assert not sancov.LOCKS_ON and not sancov.SYNC_ON
    # and jax.device_get is the real one (no wrapper installed)
    assert jax.device_get.__module__ != "bigdl_tpu.analysis.sancov"


def test_spawn_registers_thread_inventory():
    done = threading.Event()
    t = uthreads.spawn(done.wait, name="inv-probe")
    inv = uthreads.thread_inventory()
    row = next(r for r in inv if r["name"] == "inv-probe")
    assert row["daemon"] and row["owner"] == __name__
    done.set()
    t.join(timeout=5)


# -------------------------------------------------------- injected bugs
def test_injected_lock_order_inversion_one_attributed_report(sanitize):
    a = uthreads.make_lock("inv.A")
    b = uthreads.make_lock("inv.B")
    with a:
        with b:
            pass
    with b:
        with a:                      # closes the cycle
            pass
    cycles = sancov.reports("lock-order-cycle")
    assert len(cycles) == 1, cycles
    (c,) = cycles
    assert sorted(c["locks"]) == ["inv.A", "inv.B"]
    # every edge carries the acquiring module:line
    assert all(e["site"].startswith("test_concurrency:")
               for e in c["edges"]), c["edges"]
    # re-running the same inversion does not duplicate the finding
    with b:
        with a:
            pass
    assert len(sancov.reports("lock-order-cycle")) == 1


def test_injected_unlocked_write_one_attributed_report(sanitize):
    lock = uthreads.make_lock("race.owner")
    with lock:
        sancov.check_owned(lock, "race.struct")     # held -> clean
    assert sancov.reports("unlocked-write") == []
    for _ in range(3):                              # race! (one site —
        sancov.check_owned(lock, "race.struct")     # repeats dedupe)
    reports = sancov.reports("unlocked-write")
    assert len(reports) == 1, reports
    assert reports[0]["shared"] == "race.struct"
    assert reports[0]["lock"] == "race.owner"
    assert reports[0]["where"].startswith("test_concurrency:")


def test_hostsync_attributed_to_phase_and_sanctioned_path_clean(sanitize):
    from bigdl_tpu import observe
    x = jax.numpy.ones((4,))
    with observe.phase("train/dispatch"):
        with sancov.sanctioned_sync("test fetch"):
            jax.device_get(x)                       # sanctioned -> clean
    assert sancov.reports("hostsync") == []
    with observe.phase("train/dispatch"):
        jax.device_get(x)                           # smuggled sync
    reports = sancov.reports("hostsync")
    assert len(reports) == 1, reports
    assert reports[0]["phase"] == "train/dispatch"
    assert reports[0]["where"].startswith("test_concurrency:")
    # outside any phase span a fetch is nobody's business
    jax.device_get(x)
    assert len(sancov.reports("hostsync")) == 1


def test_long_hold_report(sanitize, monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_SANITIZE_HOLD_MS", "10")
    lock = uthreads.make_lock("hold.slow")
    with lock:
        time.sleep(0.05)
    reports = sancov.reports("long-hold")
    assert len(reports) == 1 and reports[0]["lock"] == "hold.slow"
    assert reports[0]["held_ms"] >= 10


# ------------------------------------------------- hammer: clean paths
def test_hammer_serve_input_statusz_zero_reports(sanitize):
    """ServeEngine traffic + input-service read-ahead + statusz scrapes,
    all concurrent, sanitizer fully on: the clean paths must produce
    ZERO findings (locks ordered, writes locked, syncs sanctioned)."""
    from bigdl_tpu.dataset.service import read_ahead
    from bigdl_tpu.observe import statusz
    from bigdl_tpu.serve import ServeEngine

    model = nn.Sequential(nn.Linear(6, 8), nn.Tanh(), nn.Linear(8, 3))
    params, state = model.init(jax.random.PRNGKey(0))
    server = statusz.start(port=0)
    eng = ServeEngine()
    try:
        eng.register("hammer", model, params, state, max_batch=8,
                     max_wait_ms=1.0)
        r = np.random.RandomState(0)
        errors = []

        def client(i):
            try:
                for _ in range(15):
                    n = int(r.randint(1, 7))
                    out = eng.predict(
                        "hammer", r.randn(n, 6).astype(np.float32),
                        timeout=30)
                    assert out.shape == (n, 3)
            except Exception as e:        # noqa: BLE001 — reported below
                errors.append(e)

        def feeder():
            try:
                src = ((np.ones((2, 6), np.float32), np.zeros(2))
                       for _ in range(50))
                for _ in read_ahead(src, depth=4):
                    pass
            except Exception as e:        # noqa: BLE001 — reported below
                errors.append(e)

        def scraper():
            try:
                for _ in range(10):
                    for ep in ("/statusz", "/metrics", "/healthz"):
                        with urllib.request.urlopen(
                                f"http://127.0.0.1:{server.port}{ep}",
                                timeout=10) as resp:
                            resp.read()
            except Exception as e:        # noqa: BLE001 — reported below
                errors.append(e)

        ts = ([uthreads.spawn(client, name=f"hammer-client-{i}",
                              args=(i,), start=False) for i in range(3)]
              + [uthreads.spawn(feeder, name="hammer-feeder", start=False),
                 uthreads.spawn(scraper, name="hammer-scraper",
                                start=False)])
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not errors, errors
    finally:
        eng.shutdown()
        statusz.stop()
    assert sancov.reports() == [], sancov.reports()


# -------------------------------------------------------- surfacing
def test_statusz_payload_carries_sanitizer_section(sanitize):
    from bigdl_tpu.observe.statusz import status_payload
    lock = uthreads.make_lock("surf.owner")
    sancov.check_owned(lock, "surf.struct")
    payload = status_payload()
    assert payload["sanitizer"]["counts"] == {"unlocked-write": 1}
    json.dumps(payload, default=str)      # the handler must serialize it


def test_forensics_bundle_and_doctor_render_sanitizer(sanitize, tmp_path,
                                                      monkeypatch, capsys):
    from bigdl_tpu.observe import doctor
    lock = uthreads.make_lock("bundle.owner")
    sancov.check_owned(lock, "bundle.struct")
    monkeypatch.setenv("BIGDL_TPU_FORENSICS", str(tmp_path))
    path = doctor.dump_forensics("test-sanitizer")
    assert path is not None
    with open(os.path.join(path, "sanitizer.json")) as fh:
        san = json.load(fh)
    assert san["counts"] == {"unlocked-write": 1}
    assert doctor.doctor_main([path]) == 0
    out = capsys.readouterr().out
    assert "unlocked write to bundle.struct" in out


def test_threads_cli_inventory_and_exit_code(sanitize, capsys):
    done = threading.Event()
    t = uthreads.spawn(done.wait, name="cli-probe")
    lock = uthreads.make_lock("cli.lock")
    sancov.register_shared("cli.struct", lock)
    try:
        assert analysis_main(["threads"]) == 0          # no findings yet
        out = capsys.readouterr().out
        assert "cli-probe" in out and "cli.lock" in out \
            and "cli.struct" in out
        sancov.check_owned(lock, "cli.struct")
        assert analysis_main(["threads"]) == 1          # findings -> 1
        p = threads_payload()
        assert any(r["name"] == "cli.lock" and r["tracked"]
                   for r in p["locks"])
    finally:
        done.set()
        t.join(timeout=5)


def test_threads_cli_json_mode(capsys):
    assert analysis_main(["threads", "--json"]) in (0, 1)
    payload = json.loads(capsys.readouterr().out)
    assert set(payload) == {"threads", "unmanaged_threads", "locks",
                            "sanitizer"}


# ------------------------------------------------ thread-shutdown audit
def test_async_checkpointer_close_joins_writer(tmp_path):
    from bigdl_tpu.resilience.snapshot import AsyncCheckpointer
    import jax.numpy as jnp
    ckpt = AsyncCheckpointer(async_mode=True)
    trees = {"params": {"w": jnp.ones((4, 4))}}
    ckpt.save(str(tmp_path / "snap-1"), trees)
    assert ckpt.close() is None
    assert ckpt._worker is None or not ckpt._worker.is_alive()
    # reusable after close: a fresh worker spins up on demand
    ckpt.save(str(tmp_path / "snap-2"), trees)
    assert ckpt.close() is None


_EXIT_AUDIT = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["BIGDL_TPU_SANITIZE"] = "1"
os.environ["BIGDL_TPU_METRICS_JSONL"] = os.path.join(r"{tmp}", "run.jsonl")
os.environ["BIGDL_TPU_METRICS_PROM"] = os.path.join(r"{tmp}", "m.prom")
os.environ["BIGDL_TPU_METRICS_FLUSH_S"] = "0.2"
import numpy as np
import jax
import bigdl_tpu.nn as nn
from bigdl_tpu import observe
from bigdl_tpu.analysis import sancov
from bigdl_tpu.dataset.service import read_ahead
from bigdl_tpu.observe import statusz
from bigdl_tpu.resilience.snapshot import AsyncCheckpointer
from bigdl_tpu.serve import ServeEngine
import jax.numpy as jnp

observe.ensure_started()
server = statusz.start(port=0)
model = nn.Sequential(nn.Linear(4, 4))
params, state = model.init(jax.random.PRNGKey(0))
eng = ServeEngine()
eng.register("exit", model, params, state, max_batch=4)
eng.predict("exit", np.ones((2, 4), np.float32), timeout=30)
for _ in read_ahead(iter([np.ones(3)] * 10), depth=2):
    pass
ckpt = AsyncCheckpointer(async_mode=True)
ckpt.save(os.path.join(r"{tmp}", "snap"), {{"p": {{"w": jnp.ones((2, 2))}}}})
ckpt.close()
eng.shutdown()
print("REPORTS=%d" % len(sancov.reports()))
# exporters + statusz are left for the atexit hook — THE audit target
"""


@pytest.mark.parametrize("plane", ["full"])
def test_process_exits_cleanly_with_full_plane_on(tmp_path, plane):
    """A process that lit the whole plane (statusz + exporters + serve +
    input service + async checkpoint, sanitizer on) must exit 0, fast,
    with no interpreter-teardown tracebacks — the exporter flush thread
    and statusz server are joined by the observe atexit hook."""
    code = _EXIT_AUDIT.format(tmp=str(tmp_path))
    t0 = time.monotonic()
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=100, cwd=ROOT)
    wall = time.monotonic() - t0
    assert r.returncode == 0, r.stderr[-2000:]
    assert "REPORTS=0" in r.stdout, (r.stdout, r.stderr[-2000:])
    for marker in ("Traceback", "Exception ignored", "Fatal Python"):
        assert marker not in r.stderr, r.stderr[-2000:]
    assert wall < 90, f"exit took {wall:.1f}s — shutdown is hanging"
