"""Replica router — one network front feeding N ServeEngine replicas.

The serving mirror of PR 6's training elasticity: where the trainer
resharded onto surviving slices when one died, the router re-places
requests onto surviving replicas. Each replica is a full serving
process (`python -m bigdl_tpu.serve --http`, its own engine + front +
telemetry plane); the router implements the front's backend protocol
(predict / generate / stream_generate / queue_state / healthz) over
HTTP, so `ServeFront(ReplicaRouter([...]))` IS the multi-replica
server — the front cannot tell it from a local engine.

Placement: each request goes to the alive replica that serves the
model, ordered by (queued load, -device headroom, index) — the queue
occupancy and `headroom_bytes` come from each replica's `/healthz`
scrape (the serve twin of the /memz + /fleetz planes), cached for
BIGDL_TPU_SERVE_ROUTER_HEALTH_TTL_S seconds so placement costs zero
round trips at steady state.

Failover: a connection failure or 503 marks the replica dead (it keeps
getting re-probed and rejoins when its plane answers again) and the
request retries on the next-best survivor, up to
BIGDL_TPU_SERVE_ROUTER_RETRIES times — predict and generate are
idempotent (pure forward / deterministic greedy decode), so the retry
is safe. A mid-flight SSE stream resumes on the survivor with
`start=<tokens already delivered>`: the survivor regenerates the
identical prefix (bit-identical greedy decode) but suppresses those
events, so the client sees every token exactly once, in order, with no
duplicates. Typed application errors (429/400/404) are NOT failed
over — the replica answered; its answer stands.

No blocking I/O is ever issued under the router lock (TPU-LINT104):
probe results are swapped in after the fetch.
"""

from __future__ import annotations

import json
import logging
import time
import urllib.error
import urllib.request
from typing import Dict, List, Optional, Sequence, Set

from bigdl_tpu import observe
from bigdl_tpu.serve.batcher import Closed, Overloaded
from bigdl_tpu.serve.net import raise_for_payload
from bigdl_tpu.utils.threads import make_lock

log = logging.getLogger("bigdl_tpu")

__all__ = ["ReplicaRouter", "ReplicaError", "launch_replicas",
           "stop_replicas"]


class ReplicaError(RuntimeError):
    """Connection-level failure talking to one replica (dead process,
    refused socket, mid-stream hangup) — the failover trigger, never
    surfaced to clients while a survivor can take the request."""


def _http_json(url: str, body: Optional[dict] = None,
               timeout: float = 10.0) -> dict:
    """One JSON round trip. Connection-level failures raise
    ReplicaError; HTTP error statuses re-raise the replica's typed
    error (net.py codec)."""
    try:
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            url, data=data,
            headers={"Content-Type": "application/json"} if data
            else {})
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return json.loads(resp.read().decode())
    except urllib.error.HTTPError as e:
        try:
            payload = json.loads(e.read().decode())
        except Exception:                # noqa: BLE001 — non-JSON body
            payload = {"error": f"HTTP {e.code}"}
        if e.code == 503:
            # the replica is up but closed/draining: for placement
            # purposes that is a dead replica — failover
            raise ReplicaError(payload.get("error", "replica closed"))
        raise_for_payload(e.code, payload)
    except (urllib.error.URLError, ConnectionError, TimeoutError,
            OSError) as e:
        raise ReplicaError(f"{url}: {e}")


class _Replica:
    __slots__ = ("url", "index", "alive", "health", "last_probe")

    def __init__(self, url: str, index: int):
        self.url = url.rstrip("/")
        self.index = index
        self.alive = True                # optimistic until a probe fails
        self.health: dict = {}
        self.last_probe = 0.0

    def load(self) -> float:
        """Queued work from the cached /healthz scrape: batcher rows +
        decode queue, normalized per model bound where known."""
        total = 0.0
        for info in (self.health.get("models") or {}).values():
            total += float(info.get("utilization") or 0.0)
        return total

    def headroom(self) -> float:
        return float(self.health.get("headroom_bytes") or 0.0)

    def has_model(self, model: str) -> bool:
        models = self.health.get("models")
        if not models:
            return True                  # unknown: let the replica 404
        return model in models


class ReplicaRouter:
    """Headroom-aware dispatch over N replica base URLs, implementing
    the serve/net.py backend protocol."""

    local_quota = False                  # each replica enforces its own

    def __init__(self, base_urls: Sequence[str], *,
                 retries: Optional[int] = None,
                 health_ttl_s: Optional[float] = None,
                 timeout_s: float = 30.0):
        from bigdl_tpu.utils import config
        if not base_urls:
            raise ValueError("need at least one replica URL")
        observe.ensure_started()
        self.replicas = [_Replica(u, i)
                         for i, u in enumerate(base_urls)]
        self.retries = (config.get("SERVE_ROUTER_RETRIES")
                        if retries is None else int(retries))
        self.health_ttl_s = (config.get("SERVE_ROUTER_HEALTH_TTL_S")
                             if health_ttl_s is None
                             else float(health_ttl_s))
        self.timeout_s = float(timeout_s)
        self._lock = make_lock("serve.router")
        self.last_placement: Optional[int] = None
        self.m_dispatch = observe.counter("serve/net/router/dispatch")
        self.m_retries = observe.counter("serve/net/router/retries")
        self.m_failovers = observe.counter(
            "serve/net/router/failovers")
        self.m_resumes = observe.counter(
            "serve/net/router/stream_resumes")
        self.g_live = observe.gauge("serve/net/router/live_replicas")
        self.g_live.set(len(self.replicas))

    # --------------------------------------------------------- placement
    def _probe(self, rep: _Replica) -> None:
        """Refresh one replica's /healthz snapshot. The fetch runs
        OUTSIDE the lock; only the state swap holds it."""
        try:
            health = _http_json(rep.url + "/healthz", timeout=2.0)
            alive = bool(health.get("ok"))
        except (ReplicaError, Exception):  # noqa: BLE001 — probe only
            health, alive = {}, False
        with self._lock:
            was = rep.alive
            rep.health = health
            rep.alive = alive
            rep.last_probe = time.monotonic()
        if alive and not was:
            log.info("serve.router: replica %d (%s) is back", rep.index,
                     rep.url)
        self.g_live.set(sum(1 for r in self.replicas if r.alive))

    def _refresh(self, force: bool = False) -> None:
        now = time.monotonic()
        for rep in self.replicas:
            if force or now - rep.last_probe > self.health_ttl_s:
                self._probe(rep)

    def _mark_dead(self, rep: _Replica, why: str) -> None:
        with self._lock:
            was, rep.alive = rep.alive, False
            rep.last_probe = time.monotonic()
        if was:
            self.m_failovers.inc()
            observe.instant("serve/net/router/failover", cat="serve",
                            args={"replica": rep.index, "why": why})
            log.warning("serve.router: replica %d (%s) marked dead: %s",
                        rep.index, rep.url, why)
        self.g_live.set(sum(1 for r in self.replicas if r.alive))

    def _pick(self, model: str,
              exclude: Set[int] = frozenset()) -> _Replica:
        """The placement policy: alive, serving `model`, least queued
        load, most device headroom, lowest index. Raises Closed when no
        replica qualifies (every one dead/excluded — the client's
        retryable total-outage signal)."""
        self._refresh()
        with self._lock:
            candidates = [r for r in self.replicas
                          if r.alive and r.index not in exclude
                          and r.has_model(model)]
        if not candidates:
            # one forced re-probe round before giving up: a replica
            # that recovered inside the TTL window should count
            self._refresh(force=True)
            with self._lock:
                candidates = [r for r in self.replicas
                              if r.alive and r.index not in exclude
                              and r.has_model(model)]
        if not candidates:
            raise Closed(
                f"no live replica serves {model!r} "
                f"({len(self.replicas)} configured, "
                f"{sum(1 for r in self.replicas if r.alive)} alive)")
        best = min(candidates,
                   key=lambda r: (r.load(), -r.headroom(), r.index))
        self.last_placement = best.index
        return best

    def _with_failover(self, model: str, fn):
        """Run `fn(replica)` with retry-on-survivor: connection-level
        failures mark the replica dead and move on; typed application
        errors propagate (the replica answered)."""
        exclude: Set[int] = set()
        last: Optional[BaseException] = None
        for attempt in range(self.retries + 1):
            rep = self._pick(model, exclude)
            try:
                self.m_dispatch.inc()
                return fn(rep)
            except ReplicaError as e:
                self._mark_dead(rep, str(e))
                exclude.add(rep.index)
                last = e
                if attempt < self.retries:
                    self.m_retries.inc()
        raise Closed(f"request failed on {len(exclude)} replica(s), "
                     f"retries exhausted: {last}")

    # ------------------------------------------------- backend protocol
    def predict(self, model: str, inputs, dtype: Optional[str] = None,
                *, priority: str = "interactive",
                client: str = "anon"):
        import numpy as np
        body = {"model": model, "inputs": inputs, "priority": priority,
                "client": client}
        if dtype:
            body["dtype"] = dtype
        out = self._with_failover(model, lambda rep: _http_json(
            rep.url + "/v1/predict", body, timeout=self.timeout_s))
        return np.asarray(out["outputs"],
                          dtype=np.dtype(dtype) if dtype else None)

    def generate(self, model: str, prompt, max_new: int,
                 eos_id: Optional[int] = None, *,
                 priority: str = "interactive",
                 client: str = "anon",
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, seed: int = 0) -> List[int]:
        body = {"model": model, "prompt": [int(t) for t in prompt],
                "max_new_tokens": int(max_new), "priority": priority,
                "client": client, "temperature": float(temperature),
                "top_k": int(top_k), "top_p": float(top_p),
                "seed": int(seed)}
        if eos_id is not None:
            body["eos_id"] = int(eos_id)
        out = self._with_failover(model, lambda rep: _http_json(
            rep.url + "/v1/generate", body, timeout=self.timeout_s))
        return [int(t) for t in out["tokens"]]

    def stream_generate(self, model: str, prompt, max_new: int,
                        eos_id: Optional[int] = None, *,
                        priority: str = "interactive",
                        client: str = "anon",
                        temperature: float = 0.0, top_k: int = 0,
                        top_p: float = 1.0, seed: int = 0
                        ) -> "_RouterStream":
        body = {"model": model, "prompt": [int(t) for t in prompt],
                "max_new_tokens": int(max_new), "stream": True,
                "priority": priority, "client": client,
                "temperature": float(temperature), "top_k": int(top_k),
                "top_p": float(top_p), "seed": int(seed)}
        if eos_id is not None:
            body["eos_id"] = int(eos_id)
        return _RouterStream(self, model, body)

    def queue_state(self) -> Dict[str, Dict]:
        """The merged model map (/v1/models through the router): each
        model's row is the least-loaded alive replica's view, plus the
        replica count serving it."""
        self._refresh()
        out: Dict[str, Dict] = {}
        with self._lock:
            for rep in self.replicas:
                if not rep.alive:
                    continue
                for name, info in (rep.health.get("models")
                                   or {}).items():
                    cur = out.get(name)
                    if cur is None or (info.get("utilization") or 0.0) \
                            < (cur.get("utilization") or 0.0):
                        out[name] = {**info, "replicas":
                                     (cur or {}).get("replicas", 0)}
                    out[name]["replicas"] = \
                        out[name].get("replicas", 0) + 1
        return out

    def healthz(self) -> dict:
        self._refresh()
        with self._lock:
            reps = [{"index": r.index, "url": r.url, "alive": r.alive,
                     "headroom_bytes": r.health.get("headroom_bytes"),
                     "load": round(r.load(), 4)}
                    for r in self.replicas]
        alive = sum(1 for r in reps if r["alive"])
        return {"ok": alive > 0, "router": True, "replicas": reps,
                "alive": alive, "models": self.queue_state()}

    def close(self) -> None:
        pass                             # replicas have their own owners


class _RouterStream:
    """SSE re-streamer with mid-flight failover.

    Iterates `(index, token)` events from one replica's /v1/generate
    SSE leg; when the replica dies mid-stream the iterator re-places
    the request on a survivor with `start=<delivered count>` — the
    survivor regenerates the identical greedy prefix but suppresses
    those events, so downstream sees each token exactly once."""

    def __init__(self, router: ReplicaRouter, model: str, body: dict):
        self._router = router
        self._model = model
        self._body = body
        self._resp = None
        self._cancelled = False

    def cancel(self) -> None:
        self._cancelled = True
        resp = self._resp
        if resp is not None:
            try:
                resp.close()             # replica front sees the hangup
            except Exception:            # noqa: BLE001 — socket state
                pass

    def _open(self, rep, start: int):
        body = dict(self._body)
        if start:
            body["start"] = start
        data = json.dumps(body).encode()
        req = urllib.request.Request(
            rep.url + "/v1/generate", data=data,
            headers={"Content-Type": "application/json"})
        try:
            return urllib.request.urlopen(
                req, timeout=self._router.timeout_s)
        except urllib.error.HTTPError as e:
            try:
                payload = json.loads(e.read().decode())
            except Exception:            # noqa: BLE001 — non-JSON body
                payload = {"error": f"HTTP {e.code}"}
            if e.code == 503:
                raise ReplicaError(
                    payload.get("error", "replica closed"))
            raise_for_payload(e.code, payload)
        except (urllib.error.URLError, ConnectionError, TimeoutError,
                OSError) as e:
            raise ReplicaError(f"{rep.url}: {e}")

    def __iter__(self):
        delivered = 0
        exclude: Set[int] = set()
        attempts = 0
        while True:
            rep = self._router._pick(self._model, exclude)
            failure: Optional[ReplicaError] = None
            try:
                self._router.m_dispatch.inc()
                self._resp = self._open(rep, delivered)
                for kind, payload in _iter_sse(self._resp):
                    if kind == "done":
                        return
                    if kind == "error":
                        # the replica ANSWERED with a typed failure —
                        # that is the request's outcome, not a failover
                        raise_for_payload(500, payload)
                    i, tok = payload
                    if i < delivered:
                        continue         # duplicate guard (belt over
                        # the server-side `start` suspenders)
                    if i > delivered:
                        raise ReplicaError(
                            f"stream gap: expected token {delivered}, "
                            f"got {i}")
                    delivered += 1
                    yield i, tok
                # close-delimited SSE that never sent `done`: the
                # replica died mid-stream
                raise ReplicaError("stream ended without done event")
            except ReplicaError as e:
                failure = e
            except GeneratorExit:
                self.cancel()
                raise
            finally:
                resp, self._resp = self._resp, None
                if resp is not None:
                    try:
                        resp.close()
                    except Exception:    # noqa: BLE001 — socket state
                        pass
            if self._cancelled:
                return
            self._router._mark_dead(rep, str(failure))
            exclude.add(rep.index)
            attempts += 1
            if attempts > self._router.retries:
                raise Closed(
                    f"stream failed on {len(exclude)} replica(s), "
                    f"retries exhausted: {failure}")
            self._router.m_retries.inc()
            self._router.m_resumes.inc()
            observe.instant(
                "serve/net/router/stream_resume", cat="serve",
                args={"model": self._model, "delivered": delivered})


def _iter_sse(resp):
    """Parse a replica's SSE stream into ('tok', (i, token)) /
    ('done', None) / ('error', payload) tuples. Connection-level
    failures (dead socket, truncated event) surface as ReplicaError;
    interpreting the replica's typed `error` event is the CALLER's
    job — this layer only frames."""
    import http.client
    event = "message"
    data_lines: List[str] = []
    try:
        for raw in resp:
            line = raw.decode("utf-8").rstrip("\n").rstrip("\r")
            if line.startswith("event:"):
                event = line.split(":", 1)[1].strip()
            elif line.startswith("data:"):
                data_lines.append(line.split(":", 1)[1].strip())
            elif line == "":             # event boundary
                if not data_lines:
                    continue
                try:
                    payload = json.loads("\n".join(data_lines))
                except ValueError as e:  # truncated by a dying replica
                    raise ReplicaError(f"SSE event truncated: {e}")
                data_lines = []
                if event == "error":
                    yield "error", payload
                    return
                if event == "done":
                    yield "done", None
                    return
                yield "tok", (int(payload["i"]),
                              int(payload["token"]))
                event = "message"
    except (ConnectionError, TimeoutError, OSError,
            http.client.HTTPException) as e:
        raise ReplicaError(f"SSE stream broke: {e}")


# ------------------------------------------------------ replica launcher
def launch_replicas(n: int, cli_args: Sequence[str], *,
                    ready_timeout_s: float,
                    env: Optional[dict] = None):
    """Spawn `n` `python -m bigdl_tpu.serve --http` replica processes
    (ephemeral ports) and wait up to `ready_timeout_s` — the caller's
    budget for start-up AND compilation, all replicas together — for
    each one's READY line. Returns `(procs, urls)`; pair with
    :func:`stop_replicas`. Used by the CLI `--replicas` mode and the
    failover tests.

    A replica runs on the platform the parent was given (its
    environment plus `env`; nothing here picks a backend) and writes
    to the parent's stderr, so one that cannot get a device, crashes or
    is still compiling says so where the operator is looking."""
    import os
    import select
    import subprocess
    import sys
    procs, urls = [], []
    try:
        for i in range(n):
            cmd = [sys.executable, "-m", "bigdl_tpu.serve", "--http",
                   "--http-port", "0", *cli_args]
            procs.append(subprocess.Popen(
                cmd, stdout=subprocess.PIPE, stdin=subprocess.PIPE,
                env={**os.environ, **(env or {})}, text=True))
        deadline = time.monotonic() + ready_timeout_s
        for i, p in enumerate(procs):
            # READY is the child's first stdout line, printed whole
            ready, _, _ = select.select(
                [p.stdout], [], [], max(0.0, deadline - time.monotonic()))
            line = p.stdout.readline() if ready else ""
            if not line:
                raise RuntimeError(
                    f"replica {i} printed no READY line within "
                    f"{ready_timeout_s:g}s (rc={p.poll()}; rc=None "
                    f"means still starting or compiling) — its own "
                    f"messages are on stderr")
            info = json.loads(line)
            if not info.get("ready"):
                raise RuntimeError(f"replica {i} bad READY: {info}")
            urls.append(f"http://127.0.0.1:{info['port']}")
        return procs, urls
    except BaseException:
        stop_replicas(procs)
        raise


def stop_replicas(procs) -> None:
    # Close stdin FIRST: replicas exit their serve loop on stdin EOF
    # (SIGTERM only raises the drain flag — the engine installs it as
    # a preemption signal, not an exit).
    for p in procs:
        try:
            if p.stdin is not None:
                p.stdin.close()
        except Exception:                # noqa: BLE001 — teardown
            pass
    for p in procs:
        try:
            if p.poll() is None:
                p.terminate()
        except Exception:                # noqa: BLE001 — teardown
            pass
    for p in procs:
        try:
            p.wait(timeout=10)
        except Exception:                # noqa: BLE001 — teardown
            try:
                p.kill()
            except Exception:            # noqa: BLE001 — teardown
                pass
