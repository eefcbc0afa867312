"""Serving network front — the HTTP/SSE request plane over ServeEngine.

Until this PR, no byte ever crossed a socket to reach the serve path:
`ServeEngine` was in-process calls only. The reference system's value
came from putting the engine behind a real distributed front (BigDL's
Spark-hosted `PredictionService` dispatching over executors); the
TPU-native analogue is this module — a concurrent stdlib HTTP server
(the `utils/httpd.py` threading discipline proven by statusz) exposing
the engine to the network, composable with N-replica dispatch through
`serve/router.py`.

Endpoints:

  * `POST /v1/predict`  — JSON `{"model", "inputs", "dtype"?,
    "priority"?, "client"?}` → `{"model", "rows", "outputs"}`. Inputs
    are nested lists (rows along dim 0), outputs come back the same
    way.
  * `POST /v1/generate` — JSON `{"model", "prompt", "max_new_tokens",
    "eos_id"?, "stream"?, "priority"?, "client"?, "start"?}`. With
    `stream=false`: one JSON reply `{"tokens", "count"}`. With
    `stream=true`: an SSE (`text/event-stream`) response pushing
    `data: {"token": t, "i": k}` per generated token AT ITERATION
    CADENCE — each event is flushed as the decode step that produced
    it completes, so time-to-first-byte is time-to-first-token, not
    time-to-EOS. The stream ends with `event: done` (or
    `event: error`). `start=k` suppresses the first k token events —
    the router's failover-resume offset (greedy decode is
    deterministic, so a survivor regenerates the identical prefix and
    the client never sees a duplicate token).
  * `GET /v1/models`    — registered models + queue/slot state.
  * `GET /healthz`      — liveness + per-model queue occupancy + memz
    device headroom (`headroom_bytes`): the exact scrape the replica
    router's placement policy consumes.

Priority classes: every request carries `priority` ∈ {"interactive"
(default), "batch"}. Batch traffic is shed with 429 once the target
model's queue passes BIGDL_TPU_SERVE_BATCH_QUOTA_PCT percent of its
bound — the queue's headroom is reserved for interactive traffic, so
a bulk backfill job cannot starve live requests.

Per-client accounting: the client id (`X-Client-Id` header or the
body's `client` field, "anon" otherwise) lands in the metrics registry
as `serve/client/<id>/requests|rows|tokens` — per-tenant usage from
the same registry the exporters already flush.

Error codec (both directions of the router): JSON
`{"error", "kind"}` with `kind` ∈ overloaded (429, Retry-After),
closed (503), not_found (404), bad_request (400), internal (500) —
the typed serve exceptions (`Overloaded`/`Closed`/KeyError/ValueError)
survive the wire.

On SSE client disconnect mid-stream the front cancels the underlying
`GenReply`, so the decode slot frees at the next scheduler iteration
instead of generating tokens nobody reads.
"""

from __future__ import annotations

import json
import logging
import re
import time
from typing import Dict, List, Optional

import numpy as np

from bigdl_tpu import observe
from bigdl_tpu.serve.batcher import (LATENCY_MS_BOUNDS, Closed,
                                     Overloaded)
from bigdl_tpu.utils.httpd import (HTTPServerThread, JSONHandler,
                                   ServerSlot)

log = logging.getLogger("bigdl_tpu")

__all__ = ["ServeFront", "LocalBackend", "start", "stop",
           "error_payload", "raise_for_payload", "PRIORITIES"]

PRIORITIES = ("interactive", "batch")

# client ids become metric-name segments: clamp charset + length so an
# adversarial header cannot explode registry cardinality
_CLIENT_RE = re.compile(r"[^A-Za-z0-9._-]")
_CLIENT_MAX = 64


def clean_client_id(raw: Optional[str]) -> str:
    if not raw:
        return "anon"
    cleaned = _CLIENT_RE.sub("_", str(raw))[:_CLIENT_MAX]
    return cleaned or "anon"


# ----------------------------------------------------------- error codec
def error_payload(exc: BaseException):
    """(http_status, json_payload) for one serve-path exception — the
    wire form of the typed serve errors."""
    if isinstance(exc, Overloaded):
        return 429, {"error": str(exc), "kind": "overloaded"}
    if isinstance(exc, Closed):
        return 503, {"error": str(exc), "kind": "closed"}
    if isinstance(exc, KeyError):
        # KeyError's str() quotes its arg; unwrap for a readable body
        msg = exc.args[0] if exc.args else str(exc)
        return 404, {"error": str(msg), "kind": "not_found"}
    if isinstance(exc, (ValueError, TypeError)):
        return 400, {"error": str(exc), "kind": "bad_request"}
    return 500, {"error": f"{type(exc).__name__}: {exc}",
                 "kind": "internal"}


def raise_for_payload(status: int, payload: dict) -> None:
    """The router-side inverse of `error_payload`: re-raise the typed
    exception a replica shipped as JSON."""
    kind = (payload or {}).get("kind")
    msg = (payload or {}).get("error") or f"HTTP {status}"
    if kind == "overloaded":
        raise Overloaded(msg)
    if kind == "closed":
        raise Closed(msg)
    if kind == "not_found":
        raise KeyError(msg)
    if kind == "bad_request":
        raise ValueError(msg)
    raise RuntimeError(msg)


# --------------------------------------------------------- local backend
class _LocalStream:
    """Iterator adapter over a local GenReply: yields (index, token);
    `cancel()` frees the decode slot (GenReply.cancel)."""

    def __init__(self, reply):
        self._reply = reply

    def __iter__(self):
        for i, tok in enumerate(self._reply.stream()):
            yield i, int(tok)

    def cancel(self) -> None:
        self._reply.cancel()


class LocalBackend:
    """The in-process backend: one ServeEngine behind the front. The
    replica router (serve/router.py) implements the same four-method
    protocol over HTTP — the front cannot tell them apart."""

    # the front enforces the batch-priority quota only where the queue
    # occupancy is authoritative — in-process. The router sets this
    # False and each replica's own front applies the quota instead.
    local_quota = True

    def __init__(self, engine):
        self.engine = engine

    def predict(self, model: str, inputs, dtype: Optional[str] = None,
                *, priority: str = "interactive",
                client: str = "anon") -> np.ndarray:
        try:
            x = np.asarray(inputs,
                           dtype=np.dtype(dtype) if dtype else None)
        except (TypeError, ValueError) as e:
            raise ValueError(f"inputs not coercible to an array: {e}")
        return self.engine.predict(model, x)

    def generate(self, model: str, prompt, max_new: int,
                 eos_id: Optional[int] = None, *,
                 priority: str = "interactive",
                 client: str = "anon",
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, seed: int = 0) -> List[int]:
        out = self.engine.generate(model, prompt, max_new,
                                   eos_id=eos_id,
                                   temperature=temperature, top_k=top_k,
                                   top_p=top_p, seed=seed)
        return [int(t) for t in out]

    def stream_generate(self, model: str, prompt, max_new: int,
                        eos_id: Optional[int] = None, *,
                        priority: str = "interactive",
                        client: str = "anon",
                        temperature: float = 0.0, top_k: int = 0,
                        top_p: float = 1.0, seed: int = 0
                        ) -> _LocalStream:
        reply = self.engine.submit_generate(model, prompt, max_new,
                                            eos_id=eos_id,
                                            temperature=temperature,
                                            top_k=top_k, top_p=top_p,
                                            seed=seed)
        return _LocalStream(reply)

    def queue_state(self) -> Dict[str, Dict]:
        return self.engine.queue_state()

    def healthz(self) -> dict:
        payload = {"ok": True, "models": self.engine.queue_state()}
        try:
            from bigdl_tpu.observe import memz as _memz
            head = _memz.ledger().headroom()
            payload["headroom_bytes"] = head.get("free_bytes")
            payload["kv_pools"] = head.get("kv_pools")
        except Exception:                # noqa: BLE001 — telemetry
            payload["headroom_bytes"] = None
        return payload

    def close(self) -> None:
        pass                             # the engine's owner shuts it down


# ---------------------------------------------------------------- server
class _FrontHandler(JSONHandler):
    server_version = "bigdl-tpu-serve/1"
    log_prefix = "serve.net"
    front: "ServeFront" = None           # bound per-ServeFront subclass

    # ------------------------------------------------------------- GET
    def do_GET(self):                    # noqa: N802 — http.server API
        f = self.front
        try:
            if self.path == "/healthz":
                self._send_json(200, f.backend.healthz())
            elif self.path in ("/v1/models", "/v1/models/"):
                self._send_json(200, {"models": f.models_payload()})
            else:
                self._send_json(404, {
                    "error": "unknown endpoint", "kind": "not_found",
                    "endpoints": ["/healthz", "/v1/models",
                                  "POST /v1/predict",
                                  "POST /v1/generate"]})
        except BrokenPipeError:
            pass
        except Exception as e:           # noqa: BLE001 — handler edge
            self._fail(e)

    # ------------------------------------------------------------ POST
    def do_POST(self):                   # noqa: N802 — http.server API
        f = self.front
        t0 = time.monotonic()
        f.m_requests.inc()
        try:
            body = self._read_json()
            if not isinstance(body, dict):
                raise ValueError("request body must be a JSON object")
            client = clean_client_id(
                self.headers.get("X-Client-Id") or body.get("client"))
            observe.counter(f"serve/client/{client}/requests").inc()
            if self.path == "/v1/predict":
                self._predict(body, client)
            elif self.path == "/v1/generate":
                self._generate(body, client)
            else:
                self._send_json(404, {"error": "unknown endpoint",
                                      "kind": "not_found"})
        except BrokenPipeError:
            f.m_disconnects.inc()
        except Exception as e:           # noqa: BLE001 — typed codec
            self._fail(e)
        finally:
            f.h_http_ms.record((time.monotonic() - t0) * 1e3)

    def _fail(self, exc: BaseException) -> None:
        self.front.m_errors.inc()
        status, payload = error_payload(exc)
        if status >= 500:
            log.warning("serve.net: %s %s failed: %s", self.command,
                        self.path, exc)
        headers = {"Retry-After": "1"} if status == 429 else None
        try:
            self._send_json(status, payload, headers=headers)
        except Exception:                # noqa: BLE001 — socket gone
            pass

    # ------------------------------------------------------ validation
    def _common(self, body: dict):
        model = body.get("model")
        if not isinstance(model, str) or not model:
            raise ValueError("'model' (string) is required")
        priority = body.get("priority") or "interactive"
        if priority not in PRIORITIES:
            raise ValueError(
                f"priority must be one of {list(PRIORITIES)}, "
                f"got {priority!r}")
        self.front.check_quota(model, priority)
        return model, priority

    # -------------------------------------------------------- endpoints
    def _predict(self, body: dict, client: str) -> None:
        f = self.front
        model, priority = self._common(body)
        if "inputs" not in body:
            raise ValueError("'inputs' (nested list of rows) is "
                             "required")
        out = f.backend.predict(model, body["inputs"],
                                body.get("dtype"), priority=priority,
                                client=client)
        rows = int(np.asarray(out).shape[0])
        observe.counter(f"serve/client/{client}/rows").inc(rows)
        self._send_json(200, {"model": model, "rows": rows,
                              "outputs": np.asarray(out).tolist()})

    def _generate(self, body: dict, client: str) -> None:
        f = self.front
        model, priority = self._common(body)
        prompt = body.get("prompt")
        if not isinstance(prompt, list) or not prompt:
            raise ValueError("'prompt' (non-empty list of token ids) "
                             "is required")
        max_new = int(body.get("max_new_tokens", 32))
        eos_id = body.get("eos_id")
        eos_id = None if eos_id is None else int(eos_id)
        # sampling controls (greedy when temperature omitted / <= 0;
        # the model must be registered with sampling=True to honor
        # temperature > 0 — ValueError otherwise, surfaced as a 400)
        samp = dict(temperature=float(body.get("temperature", 0.0)),
                    top_k=int(body.get("top_k", 0)),
                    top_p=float(body.get("top_p", 1.0)),
                    seed=int(body.get("seed", 0)))
        if not body.get("stream"):
            tokens = f.backend.generate(model, prompt, max_new, eos_id,
                                        priority=priority,
                                        client=client, **samp)
            observe.counter(
                f"serve/client/{client}/tokens").inc(len(tokens))
            self._send_json(200, {"model": model, "tokens": tokens,
                                  "count": len(tokens)})
            return
        # ------------------------------------------------ SSE streaming
        start = int(body.get("start", 0))
        stream = f.backend.stream_generate(model, prompt, max_new,
                                           eos_id, priority=priority,
                                           client=client, **samp)
        f.m_streams.inc()
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Cache-Control", "no-cache")
        self.send_header("Connection", "close")
        self.end_headers()
        self.close_connection = True     # close-delimited, not chunked
        sent = 0
        tok_counter = observe.counter(f"serve/client/{client}/tokens")
        try:
            for i, tok in stream:
                if i < start:
                    continue             # failover resume: the survivor
                    # regenerated this prefix; the client already has it
                # one flush per token: the event leaves at the decode
                # iteration that produced it — never buffered to EOS
                self.wfile.write(
                    b"data: " + json.dumps(
                        {"token": tok, "i": i}).encode() + b"\n\n")
                self.wfile.flush()
                sent += 1
                tok_counter.inc()
            self.wfile.write(
                b"event: done\ndata: " + json.dumps(
                    {"count": sent}).encode() + b"\n\n")
            self.wfile.flush()
        except (BrokenPipeError, ConnectionResetError, OSError):
            # client hung up mid-stream: free the decode slot now
            stream.cancel()
            f.m_disconnects.inc()
            log.info("serve.net: SSE client disconnected mid-stream "
                     "(%s, %d tokens delivered) — cancelled", model,
                     sent)
        except Exception as e:           # noqa: BLE001 — mid-stream
            stream.cancel()
            f.m_errors.inc()
            _, payload = error_payload(e)
            try:
                self.wfile.write(
                    b"event: error\ndata: "
                    + json.dumps(payload).encode() + b"\n\n")
                self.wfile.flush()
            except Exception:            # noqa: BLE001 — socket gone
                pass


class ServeFront:
    """The network front: one HTTP server over one backend (a
    `LocalBackend(engine)` or a `serve.router.ReplicaRouter`).

    `port=0` binds an ephemeral port (`self.port` is the resolved one);
    `close()` joins the accept thread. The front owns no engine —
    shutting the front stops new requests but the backend's owner
    drains it."""

    def __init__(self, backend, *, port: int = 0,
                 host: Optional[str] = None,
                 batch_quota_pct: Optional[float] = None):
        from bigdl_tpu.utils import config
        observe.ensure_started()
        self.backend = backend
        self.batch_quota_pct = (
            config.get("SERVE_BATCH_QUOTA_PCT")
            if batch_quota_pct is None else float(batch_quota_pct))
        self.m_requests = observe.counter("serve/net/requests")
        self.m_errors = observe.counter("serve/net/errors")
        self.m_streams = observe.counter("serve/net/sse_streams")
        self.m_disconnects = observe.counter(
            "serve/net/client_disconnects")
        self.m_priority_shed = observe.counter(
            "serve/net/priority_shed")
        self.h_http_ms = observe.histogram("serve/net/http_ms",
                                           LATENCY_MS_BOUNDS)
        handler = type("_BoundFrontHandler", (_FrontHandler,),
                       {"front": self})
        self._server = HTTPServerThread(
            handler, port, host or config.get("SERVE_HTTP_HOST"),
            thread_name="serve-http")
        self.host = self._server.host
        self.port = self._server.port
        log.info("serve.net: network front on http://%s:%d "
                 "(/v1/predict /v1/generate /v1/models /healthz)",
                 self.host, self.port)

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    # ------------------------------------------------- admission policy
    def check_quota(self, model: str, priority: str) -> None:
        """Shed 'batch'-class traffic once `model`'s queue is past the
        quota percentage of its bound — the remaining queue headroom is
        reserved for interactive requests. Backends without local queue
        state (the router) skip this: each replica's own front enforces
        it with its true occupancy."""
        if priority != "batch":
            return
        if not getattr(self.backend, "local_quota", True):
            return
        state = self.backend.queue_state()
        if state is None:
            return
        util = (state.get(model) or {}).get("utilization")
        if util is not None and util * 100.0 >= self.batch_quota_pct:
            self.m_priority_shed.inc()
            raise Overloaded(
                f"batch-priority quota: {model!r} queue at "
                f"{util * 100.0:.0f}% >= "
                f"{self.batch_quota_pct:.0f}% "
                f"(BIGDL_TPU_SERVE_BATCH_QUOTA_PCT) — retry later or "
                f"use priority=interactive")

    def models_payload(self) -> Dict[str, Dict]:
        return self.backend.queue_state() or {}

    def close(self, timeout: float = 5.0) -> None:
        self._server.close(timeout=timeout)


# --------------------------------------------------- process-wide slot
_slot = ServerSlot("serve.net.server")


def start(engine, port: Optional[int] = None,
          host: Optional[str] = None) -> Optional[ServeFront]:
    """Start (or return) the process-wide front over `engine`. With
    `port=None` the BIGDL_TPU_SERVE_HTTP_PORT knob decides (0 = off);
    an explicit port (0 = ephemeral) always starts."""
    from bigdl_tpu.utils import config

    def _factory() -> Optional[ServeFront]:
        p = port
        if p is None:
            p = config.get("SERVE_HTTP_PORT")
            if not p:
                return None
        try:
            return ServeFront(LocalBackend(engine), port=int(p),
                              host=host)
        except OSError as e:
            log.warning("serve.net: cannot bind %s:%s (%s) — network "
                        "front disabled", host, p, e)
            return None

    return _slot.start(_factory)


def server() -> Optional[ServeFront]:
    return _slot.get()


def stop() -> None:
    _slot.stop()
