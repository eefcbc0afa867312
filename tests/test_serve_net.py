"""Network serving front (ISSUE 18; docs/serving.md "Network front").

Covers the HTTP/SSE request plane end to end through REAL sockets:
the shared utils/httpd.py server core, the /v1/predict and
/v1/generate JSON codecs, SSE streaming at iteration cadence
(incremental arrival asserted with a gated fake backend — event k is
read back while event k+1 provably does not exist yet), priority
quota + per-client accounting, per-model admission bounds and the
fleet-wide cap, and the replica router: placement ordering, failover
on a closed front, and the SIGKILL-mid-stream resume with no
duplicate tokens (two subprocess replicas, bit-identical greedy
decode)."""

import http.client
import json
import os
import signal
import socket
import threading
import time

import numpy as np
import pytest

import jax

import bigdl_tpu.nn as nn
from bigdl_tpu import observe
from bigdl_tpu.serve import ServeEngine
from bigdl_tpu.serve.net import (LocalBackend, ServeFront,
                                 clean_client_id, error_payload,
                                 raise_for_payload)


def tiny_model():
    return nn.Sequential(nn.Linear(6, 8), nn.Tanh(), nn.Linear(8, 3))


def _counter(name):
    return observe.counter(name).value


def _post(port, path, body, headers=None, host="127.0.0.1"):
    """One JSON POST over a fresh connection: (status, payload)."""
    conn = http.client.HTTPConnection(host, port, timeout=30)
    try:
        conn.request("POST", path, json.dumps(body),
                     {"Content-Type": "application/json",
                      **(headers or {})})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read().decode())
    finally:
        conn.close()


def _get(port, path, host="127.0.0.1"):
    conn = http.client.HTTPConnection(host, port, timeout=30)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read().decode())
    finally:
        conn.close()


# ----------------------------------------------------------- fixtures
@pytest.fixture(scope="module")
def predict_front():
    """One engine + front for the whole module (register compiles)."""
    engine = ServeEngine(install_sigterm=False)
    model = tiny_model()
    params, state = model.init(jax.random.PRNGKey(0))
    engine.register("t", model, params, state, max_batch=8,
                    max_wait_ms=1.0)
    front = ServeFront(LocalBackend(engine), port=0)
    yield engine, front
    front.close()
    engine.shutdown()


@pytest.fixture(scope="module")
def decode_front():
    from bigdl_tpu.serve.decode import decode_demo_model
    engine = ServeEngine(install_sigterm=False)
    model, params, state = decode_demo_model(seed=0)
    engine.register("lm", model, params, state, decode=True,
                    num_slots=4, max_seq_len=64, prefill_chunk=8)
    front = ServeFront(LocalBackend(engine), port=0)
    yield engine, front
    front.close()
    engine.shutdown()


# ------------------------------------------------------- shared httpd
def test_httpd_server_slot_start_once_and_stop():
    from bigdl_tpu.utils.httpd import (HTTPServerThread, JSONHandler,
                                       ServerSlot)

    class _H(JSONHandler):
        def do_GET(self):                # noqa: N802 — http.server API
            self._send_json(200, {"pong": True})

    slot = ServerSlot("test.httpd.slot")
    a = slot.start(lambda: HTTPServerThread(_H, 0))
    b = slot.start(lambda: pytest.fail("factory must run once"))
    assert a is b is slot.get()
    assert _get(a.port, "/anything") == (200, {"pong": True})
    slot.stop()
    assert slot.get() is None
    c = slot.start(lambda: HTTPServerThread(_H, 0))   # restartable
    assert c is not None and c is slot.get()
    slot.stop()


def test_httpd_keepalive_two_requests_one_connection(predict_front):
    """HTTP/1.1 + Content-Length on every reply: the same connection
    serves consecutive requests (SSE legs opt out per-response)."""
    _, front = predict_front
    conn = http.client.HTTPConnection(front.host, front.port,
                                      timeout=30)
    try:
        for _ in range(2):
            conn.request("GET", "/healthz")
            resp = conn.getresponse()
            assert resp.status == 200
            assert json.loads(resp.read())["ok"] is True
    finally:
        conn.close()


def test_httpd_rejects_oversized_and_missing_body(predict_front):
    _, front = predict_front
    conn = http.client.HTTPConnection(front.host, front.port,
                                      timeout=30)
    try:
        conn.request("POST", "/v1/predict", "",
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 400
        assert json.loads(resp.read())["kind"] == "bad_request"
    finally:
        conn.close()


# ------------------------------------------------------- error codec
def test_error_codec_roundtrip():
    from bigdl_tpu.serve.batcher import Closed, Overloaded
    for exc, status, kind, back in (
            (Overloaded("full"), 429, "overloaded", Overloaded),
            (Closed("bye"), 503, "closed", Closed),
            (KeyError("m"), 404, "not_found", KeyError),
            (ValueError("bad"), 400, "bad_request", ValueError),
            (RuntimeError("boom"), 500, "internal", RuntimeError)):
        s, payload = error_payload(exc)
        assert s == status and payload["kind"] == kind
        with pytest.raises(back):
            raise_for_payload(s, payload)


def test_clean_client_id_clamps_cardinality():
    assert clean_client_id(None) == "anon"
    assert clean_client_id("") == "anon"
    assert clean_client_id("alice-1.svc") == "alice-1.svc"
    assert clean_client_id("a/b c\nd") == "a_b_c_d"
    assert len(clean_client_id("x" * 500)) == 64


# ---------------------------------------------------- predict endpoint
def test_predict_roundtrip_matches_engine(predict_front):
    engine, front = predict_front
    x = np.random.RandomState(0).randn(3, 6).astype(np.float32)
    before = _counter("serve/client/alice/rows")
    status, out = _post(front.port, "/v1/predict",
                        {"model": "t", "inputs": x.tolist(),
                         "dtype": "float32"},
                        headers={"X-Client-Id": "alice"})
    assert status == 200
    assert out["model"] == "t" and out["rows"] == 3
    ref = engine.predict("t", x, timeout=60)
    np.testing.assert_allclose(np.asarray(out["outputs"],
                                          np.float32),
                               np.asarray(ref), rtol=1e-5, atol=1e-5)
    assert _counter("serve/client/alice/rows") == before + 3


def test_error_mapping_over_the_wire(predict_front):
    _, front = predict_front
    st, p = _post(front.port, "/v1/predict",
                  {"model": "nope", "inputs": [[0.0] * 6]})
    assert (st, p["kind"]) == (404, "not_found")
    st, p = _post(front.port, "/v1/predict", {"model": "t"})
    assert (st, p["kind"]) == (400, "bad_request")
    st, p = _post(front.port, "/v1/predict",
                  {"model": "t", "inputs": [[0.0] * 6],
                   "priority": "vip"})
    assert (st, p["kind"]) == (400, "bad_request")
    st, p = _post(front.port, "/v1/frobnicate", {"model": "t"})
    assert (st, p["kind"]) == (404, "not_found")
    st, p = _get(front.port, "/nope")
    assert (st, p["kind"]) == (404, "not_found")


def test_models_and_healthz_endpoints(predict_front):
    _, front = predict_front
    st, models = _get(front.port, "/v1/models")
    assert st == 200 and "t" in models["models"]
    row = models["models"]["t"]
    assert row["decode"] is False and row["max_queue_rows"] >= 1
    st, health = _get(front.port, "/healthz")
    assert st == 200 and health["ok"] is True
    assert "t" in health["models"]
    assert "headroom_bytes" in health     # the router's placement feed


# --------------------------------------------- priority classes / quota
class _FakeStream:
    def __init__(self, gates, tokens):
        self.gates, self.tokens = gates, tokens
        self.cancelled = threading.Event()

    def __iter__(self):
        for i, (gate, tok) in enumerate(zip(self.gates, self.tokens)):
            gate.wait(timeout=30)
            if self.cancelled.is_set():
                return
            yield i, tok

    def cancel(self):
        self.cancelled.set()
        for g in self.gates:
            g.set()


class _FakeBackend:
    """Minimal backend-protocol stub with a dialable queue state and a
    gate-stepped token stream."""

    local_quota = True

    def __init__(self):
        self.util = 0.0
        self.stream = None

    def queue_state(self):
        return {"m": {"decode": True, "utilization": self.util}}

    def healthz(self):
        return {"ok": True, "models": self.queue_state()}

    def predict(self, model, inputs, dtype=None, *, priority, client):
        return np.asarray(inputs)

    def generate(self, model, prompt, max_new, eos_id=None, *,
                 priority, client, temperature=0.0, top_k=0, top_p=1.0,
                 seed=0):
        return [1, 2, 3]

    def stream_generate(self, model, prompt, max_new, eos_id=None, *,
                        priority, client, temperature=0.0, top_k=0,
                        top_p=1.0, seed=0):
        return self.stream

    def close(self):
        pass


@pytest.fixture()
def fake_front():
    backend = _FakeBackend()
    front = ServeFront(backend, port=0, batch_quota_pct=50.0)
    yield backend, front
    front.close()


def test_batch_priority_shed_past_quota(fake_front):
    backend, front = fake_front
    backend.util = 0.9                    # 90% >= the 50% quota
    before = _counter("serve/net/priority_shed")
    st, p = _post(front.port, "/v1/generate",
                  {"model": "m", "prompt": [1], "priority": "batch"})
    assert (st, p["kind"]) == (429, "overloaded")
    assert _counter("serve/net/priority_shed") == before + 1
    # interactive traffic rides the reserved headroom
    st, p = _post(front.port, "/v1/generate",
                  {"model": "m", "prompt": [1],
                   "priority": "interactive"})
    assert st == 200 and p["tokens"] == [1, 2, 3]
    backend.util = 0.2                    # under quota: batch admitted
    st, _ = _post(front.port, "/v1/generate",
                  {"model": "m", "prompt": [1], "priority": "batch"})
    assert st == 200


def test_retry_after_header_on_429(fake_front):
    backend, front = fake_front
    backend.util = 1.0
    conn = http.client.HTTPConnection(front.host, front.port,
                                      timeout=30)
    try:
        conn.request("POST", "/v1/generate",
                     json.dumps({"model": "m", "prompt": [1],
                                 "priority": "batch"}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 429
        assert resp.getheader("Retry-After") == "1"
        resp.read()
    finally:
        conn.close()


# --------------------------------------------------- SSE at iteration cadence
def test_sse_events_flush_per_token_not_at_eos(fake_front):
    """Event k is read off the socket while event k+1 provably does
    not exist yet (its gate is closed) — the stream cannot be
    buffering to EOS."""
    backend, front = fake_front
    gates = [threading.Event() for _ in range(3)]
    backend.stream = _FakeStream(gates, [7, 8, 9])
    conn = http.client.HTTPConnection(front.host, front.port,
                                      timeout=30)
    try:
        conn.request("POST", "/v1/generate",
                     json.dumps({"model": "m", "prompt": [1],
                                 "stream": True}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader("Content-Type") == "text/event-stream"
        for k, want in enumerate([7, 8, 9]):
            gates[k].set()                # release exactly one token
            line = resp.fp.readline().decode().strip()
            assert json.loads(line.split(":", 1)[1]) == {
                "token": want, "i": k}
            assert resp.fp.readline() == b"\n"
        assert resp.fp.readline().decode().strip() == "event: done"
    finally:
        conn.close()


def test_sse_client_disconnect_cancels_stream(fake_front):
    """Hanging up mid-stream cancels the backend stream (the decode
    slot frees instead of generating for nobody)."""
    backend, front = fake_front
    gates = [threading.Event() for _ in range(64)]
    backend.stream = _FakeStream(gates, list(range(64)))
    before = _counter("serve/net/client_disconnects")
    sock = socket.create_connection((front.host, front.port),
                                    timeout=30)
    try:
        body = json.dumps({"model": "m", "prompt": [1],
                           "stream": True}).encode()
        sock.sendall(b"POST /v1/generate HTTP/1.1\r\n"
                     b"Host: x\r\nContent-Type: application/json\r\n"
                     + f"Content-Length: {len(body)}\r\n\r\n".encode()
                     + body)
        gates[0].set()
        buf = b""
        deadline = time.monotonic() + 15
        while b"data:" not in buf:        # stream is live
            assert time.monotonic() < deadline
            buf += sock.recv(65536)
    finally:
        sock.close()                      # mid-stream hangup
    for g in gates:
        g.set()                           # let the writer hit the pipe
    deadline = time.monotonic() + 10
    while not backend.stream.cancelled.is_set():
        assert time.monotonic() < deadline, "stream never cancelled"
        time.sleep(0.02)
    deadline = time.monotonic() + 10
    while _counter("serve/net/client_disconnects") <= before:
        assert time.monotonic() < deadline
        time.sleep(0.02)


def test_sse_real_decode_stream_matches_nonstream(decode_front):
    """End to end on the real decode path: the SSE token sequence is
    bit-identical to the non-streamed reply (deterministic greedy)."""
    _, front = decode_front
    body = {"model": "lm", "prompt": [5, 9, 2], "max_new_tokens": 12}
    st, ref = _post(front.port, "/v1/generate", body)
    assert st == 200 and ref["count"] >= 1
    conn = http.client.HTTPConnection(front.host, front.port,
                                      timeout=60)
    try:
        conn.request("POST", "/v1/generate",
                     json.dumps({**body, "stream": True}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        toks = []
        for raw in resp.fp:
            line = raw.decode().strip()
            if line.startswith("data:") and '"token"' in line:
                toks.append(json.loads(line.split(":", 1)[1])["token"])
            elif line.startswith("event: done"):
                break
    finally:
        conn.close()
    assert toks == ref["tokens"]


def test_sse_start_offset_suppresses_prefix(decode_front):
    """The failover-resume contract: start=k replays the generation
    but ships only tokens[k:], indexed from k."""
    _, front = decode_front
    body = {"model": "lm", "prompt": [7, 3, 3, 1],
            "max_new_tokens": 10}
    st, ref = _post(front.port, "/v1/generate", body)
    assert st == 200
    k = min(2, ref["count"] - 1)
    conn = http.client.HTTPConnection(front.host, front.port,
                                      timeout=60)
    try:
        conn.request("POST", "/v1/generate",
                     json.dumps({**body, "stream": True, "start": k}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        events = []
        for raw in resp.fp:
            line = raw.decode().strip()
            if line.startswith("data:") and '"token"' in line:
                events.append(json.loads(line.split(":", 1)[1]))
            elif line.startswith("event: done"):
                break
    finally:
        conn.close()
    assert [e["i"] for e in events] == list(range(k, ref["count"]))
    assert [e["token"] for e in events] == ref["tokens"][k:]


def test_sse_disconnect_frees_real_decode_slot(decode_front):
    """Real-engine half of the disconnect contract: the slot the
    stream held is swept (decode/cancelled counter) after hangup."""
    engine, front = decode_front
    before = _counter("serve/lm/decode/cancelled")
    sock = socket.create_connection((front.host, front.port),
                                    timeout=30)
    body = json.dumps({"model": "lm", "prompt": [4, 4, 2],
                       "max_new_tokens": 50, "eos_id": -1,
                       "stream": True}).encode()
    try:
        sock.sendall(b"POST /v1/generate HTTP/1.1\r\n"
                     b"Host: x\r\nContent-Type: application/json\r\n"
                     + f"Content-Length: {len(body)}\r\n\r\n".encode()
                     + body)
        buf = b""
        deadline = time.monotonic() + 30
        while b"data:" not in buf:        # first token is out
            assert time.monotonic() < deadline
            buf += sock.recv(4096)
    finally:
        sock.close()
    deadline = time.monotonic() + 15
    while _counter("serve/lm/decode/cancelled") <= before:
        assert time.monotonic() < deadline, "slot never swept"
        time.sleep(0.05)
    deadline = time.monotonic() + 15
    while engine.queue_state()["lm"]["active_slots"] > 0:
        assert time.monotonic() < deadline, "slot still active"
        time.sleep(0.05)


# ------------------------------------- per-model bounds and fleet cap
def test_parse_model_queue_rows():
    from bigdl_tpu.serve.engine import parse_model_queue_rows as p
    assert p("") == {} and p(None) == {}
    assert p("512") == {"*": 512}
    assert p("m1=32, m2=8") == {"m1": 32, "m2": 8}
    assert p("16,big=64") == {"*": 16, "big": 64}
    with pytest.raises(ValueError):
        p("m=0")
    with pytest.raises(ValueError):
        p("=5")
    with pytest.raises(ValueError):
        p("m=lots")


def test_per_model_queue_rows_env(monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_SERVE_MODEL_QUEUE_ROWS", "t=7,*=33")
    engine = ServeEngine(install_sigterm=False)
    model = tiny_model()
    params, state = model.init(jax.random.PRNGKey(0))
    try:
        engine.register("t", model, params, state, max_batch=4)
        engine.register("u", model, params, state, max_batch=4)
        engine.register("v", model, params, state, max_batch=4,
                        max_queue_rows=5)   # explicit arg wins
        qs = engine.queue_state()
        assert qs["t"]["max_queue_rows"] == 7
        assert qs["u"]["max_queue_rows"] == 33   # wildcard
        assert qs["v"]["max_queue_rows"] == 5
    finally:
        engine.shutdown()


def test_fleet_cap_and_per_model_shed_counters(predict_front):
    from bigdl_tpu.serve.batcher import Overloaded
    engine, _ = predict_front
    before_m = _counter("serve/t/shed")
    before_g = _counter("serve/shed")
    old = engine._defaults["max_queue_rows"]
    engine._defaults["max_queue_rows"] = 4   # fleet-wide cap
    try:
        with pytest.raises(Overloaded) as ei:
            engine.submit("t", np.zeros((6, 6), np.float32))
        assert "fleet-wide" in str(ei.value)
    finally:
        engine._defaults["max_queue_rows"] = old
    assert _counter("serve/t/shed") == before_m + 1
    assert _counter("serve/shed") == before_g + 1


def test_batcher_per_model_shed_counter():
    from bigdl_tpu.serve.batcher import ContinuousBatcher, Overloaded
    b = ContinuousBatcher(lambda xs, n: xs, [4], name="shedm",
                          max_queue_rows=4, start=False)
    b.submit(np.ones((3, 2), np.float32))
    before = _counter("serve/shedm/shed")
    with pytest.raises(Overloaded):
        b.submit(np.ones((2, 2), np.float32))
    assert _counter("serve/shedm/shed") == before + 1


# --------------------------------------------------------- the router
def test_router_placement_prefers_low_load_then_headroom():
    from bigdl_tpu.serve.router import ReplicaRouter
    r = ReplicaRouter(["http://127.0.0.1:1", "http://127.0.0.1:2",
                       "http://127.0.0.1:3"], health_ttl_s=1e9)
    now = time.monotonic() + 1e9          # suppress live probes
    for rep, load, head in zip(r.replicas, (0.5, 0.1, 0.1),
                               (0, 0, 1024)):
        rep.health = {"ok": True,
                      "models": {"m": {"utilization": load}},
                      "headroom_bytes": head}
        rep.last_probe = now
    assert r._pick("m").index == 2        # tied load -> more headroom
    assert r.last_placement == 2
    r.replicas[2].alive = False
    assert r._pick("m").index == 1        # next-best survivor
    assert r._pick("m", exclude={1, 2}).index == 0


def test_router_skips_replicas_without_the_model():
    from bigdl_tpu.serve.router import ReplicaRouter
    r = ReplicaRouter(["http://127.0.0.1:1", "http://127.0.0.1:2"],
                      health_ttl_s=1e9)
    now = time.monotonic() + 1e9
    r.replicas[0].health = {"ok": True,
                            "models": {"other": {"utilization": 0.0}}}
    r.replicas[1].health = {"ok": True,
                            "models": {"m": {"utilization": 0.9}}}
    for rep in r.replicas:
        rep.last_probe = now
    assert r._pick("m").index == 1


def test_router_failover_to_surviving_front():
    """Two IN-PROCESS fronts over one engine; closing the placed one
    mid-flight fails the request over to the survivor."""
    from bigdl_tpu.serve.batcher import Closed
    from bigdl_tpu.serve.router import ReplicaRouter
    engine = ServeEngine(install_sigterm=False)
    model = tiny_model()
    params, state = model.init(jax.random.PRNGKey(0))
    engine.register("t", model, params, state, max_batch=4)
    f1 = ServeFront(LocalBackend(engine), port=0)
    f2 = ServeFront(LocalBackend(engine), port=0)
    try:
        r = ReplicaRouter([f1.url, f2.url], retries=2,
                          health_ttl_s=0.05)
        x = np.random.RandomState(1).randn(2, 6).astype(np.float32)
        out = r.predict("t", x.tolist(), "float32")
        assert np.asarray(out).shape == (2, 3)
        victim = r.last_placement
        (f1 if victim == 0 else f2).close()
        before = r.m_failovers.value
        time.sleep(0.1)                   # let the health TTL lapse
        out2 = r.predict("t", x.tolist(), "float32")
        np.testing.assert_allclose(np.asarray(out2), np.asarray(out),
                                   rtol=1e-5)
        assert r.last_placement != victim
        # the dead front was either probed out or failed over live
        assert (r.m_failovers.value > before
                or not r.replicas[victim].alive)
        (f2 if victim == 0 else f1).close()
        with pytest.raises(Closed):
            r.predict("t", x.tolist(), "float32")
    finally:
        for f in (f1, f2):
            try:
                f.close()
            except Exception:             # noqa: BLE001 — teardown
                pass
        engine.shutdown()


def test_router_typed_errors_do_not_fail_over(predict_front):
    """A 404/400 is the replica's ANSWER — it must propagate, not mark
    the replica dead."""
    from bigdl_tpu.serve.router import ReplicaRouter
    _, front = predict_front
    from bigdl_tpu.serve.batcher import Closed
    r = ReplicaRouter([front.url], retries=2, health_ttl_s=0.01)
    # a model NO replica advertises never even dispatches: placement
    # reports the retryable outage, and nobody gets marked dead
    with pytest.raises(Closed):
        r.predict("missing-model", [[0.0] * 6])
    with pytest.raises(ValueError):       # ragged inputs -> 400
        r.predict("t", [[1.0, 2.0], [3.0]])
    assert r.replicas[0].alive            # never marked dead


# --------------------------------- subprocess replicas: SIGKILL resume
# max_seq_len 256 so the streamed generation is long enough (200
# tokens) that the SIGKILL always lands mid-stream, never after EOS
REPLICA_ARGS = ["--decode", "--slots", "4", "--max-seq-len", "256",
                "--prefill-chunk", "8", "--max-new", "32",
                "--seed", "0"]
STREAM_NEW = 200


def test_sigkill_mid_stream_resumes_on_survivor_no_duplicates():
    """ISSUE 18 acceptance: two replica processes (same seed — greedy
    decode is bit-identical), SIGKILL the one serving an SSE stream
    after the first tokens, and the router resumes the stream on the
    survivor: every token exactly once, in order, equal to the
    survivor's non-streamed answer."""
    from bigdl_tpu.serve.router import (ReplicaRouter, launch_replicas,
                                        stop_replicas)
    procs, urls = launch_replicas(2, REPLICA_ARGS, ready_timeout_s=100)
    try:
        r = ReplicaRouter(urls, retries=2, health_ttl_s=0.05)
        prompt = [5, 9, 2, 11]
        ref = r.generate("default", prompt, STREAM_NEW, eos_id=-1)
        assert len(ref) == STREAM_NEW     # eos disabled -> full budget
        failovers0 = r.m_failovers.value
        resumes0 = r.m_resumes.value
        events = []
        it = iter(r.stream_generate("default", prompt, STREAM_NEW,
                                    eos_id=-1))
        for _ in range(3):
            events.append(next(it))
        victim = r.last_placement
        os.kill(procs[victim].pid, signal.SIGKILL)
        for ev in it:
            events.append(ev)
        assert [i for i, _ in events] == list(range(STREAM_NEW))
        assert [t for _, t in events] == ref
        assert r.m_failovers.value == failovers0 + 1
        assert r.m_resumes.value == resumes0 + 1
        # the dead replica sheds load, the survivor still answers
        again = r.generate("default", prompt, 8, eos_id=-1)
        assert again == ref[:8]
        assert r.healthz()["alive"] == 1
    finally:
        stop_replicas(procs)


# ----------------------------------------------------------------- CLI
def test_cli_http_smoke_decode(capsys):
    from bigdl_tpu.serve.__main__ import main
    rc = main(["--decode", "--http", "--smoke", "--slots", "4",
               "--max-seq-len", "64", "--prefill-chunk", "8",
               "--smoke-threads", "2", "--smoke-requests", "2",
               "--max-new", "8"])
    out = capsys.readouterr().out.strip().splitlines()[-1]
    rec = json.loads(out)
    assert rc == 0
    assert rec["mode"] == "http-smoke" and rec["decode"] is True
    assert rec["requests_ok"] == rec["requests_sent"] == 4
    assert rec["sse_streams"] == 2        # every second generate
    assert rec["errors"] == []
    assert rec["healthz_ok"] is True


def test_serve_net_knobs_registered():
    from bigdl_tpu.utils import config
    knobs = config.knobs()
    for name in ("SERVE_MODEL_QUEUE_ROWS", "SERVE_HTTP_PORT",
                 "SERVE_HTTP_HOST", "SERVE_REPLICAS",
                 "SERVE_BATCH_QUOTA_PCT", "SERVE_ROUTER_RETRIES",
                 "SERVE_ROUTER_HEALTH_TTL_S"):
        assert name in knobs and knobs[name].doc
    assert config.get("SERVE_HTTP_PORT") == 0       # off by default
    assert config.get("SERVE_REPLICAS") == 1
