"""Serving CLI: stand up a ServeEngine around a model factory.

    python -m bigdl_tpu.serve bigdl_tpu.models.lenet:build \
        --input 28,28,1 --smoke

The factory is `module.path:callable` (the analysis/kernels CLI
convention) — called with no arguments it must return a `Module`.
`--input` is the PER-ROW feature shape (no batch dim), with an optional
`:dtype` suffix (`--input 16:int32`).

Modes:
  * default — line protocol on stdin: each line is a JSON array of
    input rows (one request); the reply rows are printed as one JSON
    array per line. EOF drains and exits. A transportless serving
    surface: pipe a socket relay (socat) in front for the network.
  * --smoke — self-drive: T client threads submit R mixed-size
    requests, then ONE JSON summary line (requests, batches, mean
    batch fill, p50/p99 ms, shed count) is printed. Exit 0 on a clean
    drain with every request answered — the tier-1 CI probe.
  * --decode — the iteration-level autoregressive path
    (serve/decode.py): the factory's model must carry the slot-decode
    contract (GPT2LM/LlamaLM; no factory = a tiny built-in demo LM).
    stdin lines are `{"prompt": [ids...], "max_new_tokens": N}` (or a
    bare JSON array of ids, decoded with --max-new); `--decode --smoke`
    self-drives T threads of concurrent mixed-length generates and
    prints one JSON summary (tokens, tokens/s, ttft p50/p99, slot
    occupancy) — the decode tier-1 CI probe.
  * --http — the network front (serve/net.py): /v1/predict and
    /v1/generate over a real socket instead of stdin. Prints ONE
    READY json line `{"ready": true, "port": ...}` then blocks until
    stdin closes (the multihost_worker subprocess protocol — replica
    launchers read the port from it). `--http-port 0` (default) binds
    an ephemeral port. `--replicas N` (N>1) spawns N single-engine
    replica processes of THIS command line and fronts them with the
    headroom-aware ReplicaRouter (serve/router.py). `--http --smoke`
    self-drives through the real socket (for decode models: half the
    generates streamed over SSE) and prints one JSON summary — the
    network-front tier-1 CI probe.

`--precompile` AOT-compiles every shape bucket before traffic (warm
compile cache => zero fresh programs; decode registrations always
precompile). `--int8` serves the quantized forward. Knob defaults:
BIGDL_TPU_SERVE_* (docs/configuration.md).
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
from typing import Optional, Sequence


def _parse_input(spec: str):
    import numpy as np
    dtype = "float32"
    if ":" in spec:
        spec, dtype = spec.rsplit(":", 1)
    shape = tuple(int(s) for s in spec.split(",") if s != "")
    return shape, np.dtype(dtype)


def _load_factory(ref: str):
    if ":" not in ref:
        raise SystemExit(f"factory must be 'module.path:callable', got "
                         f"'{ref}'")
    mod_name, attr = ref.split(":", 1)
    obj = getattr(importlib.import_module(mod_name), attr)
    model = obj() if callable(obj) and not hasattr(obj, "apply") else obj
    if not hasattr(model, "apply"):
        raise SystemExit(f"{ref} did not produce a Module (got "
                         f"{type(model).__name__})")
    return model


def _smoke(engine, name: str, feature_shape, dtype, *, threads: int,
           requests: int, seed: int) -> dict:
    """Self-drive: mixed-size requests from concurrent clients, checked
    row-for-row against a direct forward of the same padded program."""
    import numpy as np
    r = np.random.RandomState(seed)
    entry = engine.registry.get(name)
    cap = min(entry.max_batch, 16)
    reqs = [[_rand(r, feature_shape, dtype, int(r.randint(1, cap + 1)))
             for _ in range(requests)] for _ in range(threads)]
    errors: list = []
    ok = [0]

    def client(ti):
        try:
            for q in reqs[ti]:
                out = engine.predict(name, q, timeout=60)
                assert out.shape[0] == q.shape[0], (out.shape, q.shape)
                ok[0] += 1
        except Exception as exc:           # noqa: BLE001 — reported in JSON
            errors.append(f"client {ti}: {exc!r}")

    from bigdl_tpu.utils.threads import spawn
    ts = [spawn(client, name=f"serve-smoke-client-{ti}", args=(ti,),
                start=False) for ti in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    stats = engine.stats()
    return {
        "mode": "smoke",
        "model": name,
        "clients": threads,
        "requests_sent": threads * requests,
        "requests_ok": ok[0],
        "errors": errors[:5],
        "buckets": stats[name]["buckets"],
        "p50_ms": stats[name]["p50_ms"],
        "p99_ms": stats[name]["p99_ms"],
        "batches": stats["_totals"]["batches"],
        "rows": stats["_totals"]["rows"],
        "shed": stats["_totals"]["shed"],
        "mean_batch_fill": stats["_totals"]["mean_batch_fill"],
    }


def _rand(r, feature_shape, dtype, n: int):
    import numpy as np
    if np.issubdtype(dtype, np.integer):
        return r.randint(0, 8, (n,) + feature_shape).astype(dtype)
    return r.randn(n, *feature_shape).astype(dtype)


def _decode_smoke(engine, name: str, *, threads: int, requests: int,
                  max_new: int, seed: int) -> dict:
    """Self-drive the decode path: concurrent mixed-length generates,
    each checked for a non-empty, budget-respecting reply."""
    import numpy as np
    entry = engine.registry.get(name)
    vocab = entry.decode.vocab_size
    cap = max(2, entry.decode.max_seq_len - max_new)
    r = np.random.RandomState(seed)
    prompts = [[r.randint(2, vocab, int(r.randint(1, min(cap, 24) + 1)))
                for _ in range(requests)] for _ in range(threads)]
    errors: list = []
    ok = [0]

    def client(ti):
        try:
            for p in prompts[ti]:
                out = engine.generate(name, p, max_new, timeout=120)
                assert 1 <= out.shape[0] <= max_new, out.shape
                ok[0] += 1
        except Exception as exc:           # noqa: BLE001 — in the JSON
            errors.append(f"client {ti}: {exc!r}")

    from bigdl_tpu.utils.threads import spawn
    ts = [spawn(client, name=f"serve-decode-smoke-{ti}", args=(ti,),
                start=False) for ti in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    st = engine.stats()[name]["decode"]
    return {
        "mode": "decode-smoke",
        "model": name,
        "clients": threads,
        "requests_sent": threads * requests,
        "requests_ok": ok[0],
        "errors": errors[:5],
        "slots": st["slots"],
        "retired": st["retired"],
        "tokens": st["tokens"],
        "tokens_per_s": st["tokens_per_s"],
        "slot_occupancy_mean": st["slot_occupancy_mean"],
        "ttft_p50_ms": st["ttft_p50_ms"],
        "ttft_p99_ms": st["ttft_p99_ms"],
        "step_p50_ms": st["step_p50_ms"],
    }


def _decode_stdin_loop(engine, name: str, max_new: int) -> int:
    import numpy as np
    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        req = json.loads(line)
        if isinstance(req, dict):
            prompt = np.asarray(req["prompt"], np.int32)
            n = int(req.get("max_new_tokens", max_new))
        else:
            prompt, n = np.asarray(req, np.int32), max_new
        out = engine.generate(name, prompt, n, timeout=120)
        print(json.dumps(np.asarray(out).tolist()))
        sys.stdout.flush()
    return 0


def _stdin_loop(engine, name: str, dtype) -> int:
    import numpy as np
    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        x = np.asarray(json.loads(line), dtype=dtype)
        out = engine.predict(name, x, timeout=60)
        print(json.dumps(np.asarray(out).tolist()))
        sys.stdout.flush()
    return 0


# ------------------------------------------------------ network front
def _post_json(url: str, body: dict, timeout: float = 60.0) -> dict:
    import urllib.request
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read().decode())


def _sse_tokens(url: str, body: dict, timeout: float = 120.0):
    """POST a streamed /v1/generate and collect its SSE tokens,
    counting the distinct socket arrivals (reads) — incremental
    delivery shows many arrivals, a buffered-to-EOS stream one."""
    import urllib.request
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    tokens, reads = [], 0
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        for raw in resp:
            line = raw.decode().strip()
            if line:
                reads += 1
            if line.startswith("data:") and '"token"' in line:
                tokens.append(json.loads(line.split(":", 1)[1])["token"])
            elif line.startswith("event: done"):
                break
    return tokens, reads


def _http_smoke(base_url: str, name: str, *, decode: bool,
                feature_shape=None, dtype=None, threads: int = 4,
                requests: int = 8, max_new: int = 16,
                seed: int = 0, max_batch: int = 16) -> dict:
    """Self-drive the network front through REAL sockets: T client
    threads POST R requests each; decode models stream every second
    generate over SSE and assert the stream matches its non-streamed
    twin (bit-identical greedy decode)."""
    import urllib.request

    import numpy as np
    errors: list = []
    ok = [0]
    streamed = [0]

    def predict_client(ti):
        rr = np.random.RandomState(seed + ti)
        try:
            for _ in range(requests):
                n = int(rr.randint(1, max_batch + 1))
                x = _rand(rr, feature_shape, dtype, n)
                out = _post_json(base_url + "/v1/predict",
                                 {"model": name, "inputs": x.tolist(),
                                  "dtype": str(dtype),
                                  "client": f"smoke-{ti}"})
                assert out["rows"] == n, (out["rows"], n)
                ok[0] += 1
        except Exception as exc:         # noqa: BLE001 — in the JSON
            errors.append(f"client {ti}: {exc!r}")

    def decode_client(ti):
        rr = np.random.RandomState(seed + ti)
        try:
            for k in range(requests):
                plen = int(rr.randint(1, 12))
                prompt = [int(t) for t in rr.randint(2, 48, plen)]
                body = {"model": name, "prompt": prompt,
                        "max_new_tokens": max_new,
                        "client": f"smoke-{ti}"}
                if k % 2 == 0:
                    out = _post_json(base_url + "/v1/generate", body)
                    assert 1 <= out["count"] <= max_new, out
                else:
                    toks, _ = _sse_tokens(base_url + "/v1/generate",
                                          {**body, "stream": True})
                    assert 1 <= len(toks) <= max_new, len(toks)
                    ref = _post_json(base_url + "/v1/generate", body)
                    assert toks == ref["tokens"], (
                        "stream/non-stream mismatch")
                    streamed[0] += 1
                ok[0] += 1
        except Exception as exc:         # noqa: BLE001 — in the JSON
            errors.append(f"client {ti}: {exc!r}")

    from bigdl_tpu.utils.threads import spawn
    client = decode_client if decode else predict_client
    ts = [spawn(client, name=f"serve-http-smoke-{ti}", args=(ti,),
                start=False) for ti in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    health = json.loads(urllib.request.urlopen(
        base_url + "/healthz", timeout=10).read())
    from bigdl_tpu import observe
    from bigdl_tpu.serve.batcher import LATENCY_MS_BOUNDS
    h = observe.histogram("serve/net/http_ms", LATENCY_MS_BOUNDS)
    return {
        "mode": "http-smoke",
        "model": name,
        "decode": decode,
        "url": base_url,
        "clients": threads,
        "requests_sent": threads * requests,
        "requests_ok": ok[0],
        "sse_streams": streamed[0],
        "errors": errors[:5],
        "healthz_ok": bool(health.get("ok")),
        "http_p50_ms": round(h.quantile(0.5), 3) if h.count else None,
        "http_p99_ms": round(h.quantile(0.99), 3) if h.count else None,
    }


def _http_serve_loop(front, extra: dict) -> int:
    """READY line + block until stdin closes (the subprocess replica
    protocol: the launcher reads the port, closing our stdin is the
    graceful-shutdown signal)."""
    print(json.dumps({"ready": True, "port": front.port,
                      "url": front.url, **extra}), flush=True)
    for _ in sys.stdin:                  # pragma: no branch — blocks
        pass
    return 0


def _child_cli_args(args) -> list:
    """Reconstruct the per-replica command line from our own flags
    (everything model-shaped; the launcher adds --http --http-port 0)."""
    out = []
    if args.factory:
        out.append(args.factory)
    if args.input:
        out += ["--input", args.input]
    if args.decode:
        out.append("--decode")
    for flag, val in (("--slots", args.slots),
                      ("--max-seq-len", args.max_seq_len),
                      ("--prefill-chunk", args.prefill_chunk),
                      ("--eos", args.eos),
                      ("--max-batch", args.max_batch),
                      ("--max-wait-ms", args.max_wait_ms),
                      ("--max-queue-rows", args.max_queue_rows)):
        if val is not None:
            out += [flag, str(val)]
    out += ["--max-new", str(args.max_new), "--name", args.name,
            "--seed", str(args.seed)]
    if args.int8:
        out.append("--int8")
    if args.precompile:
        out.append("--precompile")
    return out


def _router_main(args, replicas: int) -> int:
    """--http --replicas N: N replica processes + router + front."""
    from bigdl_tpu.serve import net as _net
    from bigdl_tpu.serve import router as _router
    procs, urls = _router.launch_replicas(
        replicas, _child_cli_args(args),
        ready_timeout_s=args.replica_ready_s)
    front = None
    try:
        backend = _router.ReplicaRouter(urls)
        front = _net.ServeFront(
            backend, port=args.http_port if args.http_port is not None
            else 0)
        if args.smoke:
            feature = (_parse_input(args.input)
                       if args.input else (None, None))
            rec = _http_smoke(
                front.url, args.name, decode=args.decode,
                feature_shape=feature[0], dtype=feature[1],
                threads=args.smoke_threads,
                requests=args.smoke_requests, max_new=args.max_new,
                seed=args.seed,
                max_batch=min(args.max_batch or 16, 16))
            rec["replicas"] = replicas
            print(json.dumps(rec))
            return 1 if rec["errors"] else 0
        return _http_serve_loop(front, {"replicas": replicas,
                                        "replica_urls": urls})
    finally:
        if front is not None:
            front.close()
        _router.stop_replicas(procs)


def _http_main(engine, args, *, decode: bool, feature=(None, None)
               ) -> int:
    """--http over the in-process engine: front + smoke or READY loop."""
    from bigdl_tpu.serve import net as _net
    front = _net.ServeFront(
        _net.LocalBackend(engine),
        port=args.http_port if args.http_port is not None else 0)
    try:
        if args.smoke:
            rec = _http_smoke(
                front.url, args.name, decode=decode,
                feature_shape=feature[0], dtype=feature[1],
                threads=args.smoke_threads,
                requests=args.smoke_requests, max_new=args.max_new,
                seed=args.seed,
                max_batch=min(args.max_batch or 16, 16))
            print(json.dumps(rec))
            return 1 if rec["errors"] else 0
        return _http_serve_loop(front, {"decode": decode,
                                        "model": args.name})
    finally:
        front.close()


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m bigdl_tpu.serve",
        description="Online inference engine around a model factory "
                    "(docs/serving.md)")
    ap.add_argument("factory", nargs="?", default=None,
                    help="model factory as 'pkg.module:callable' "
                         "(optional with --decode: defaults to the "
                         "built-in demo LM)")
    ap.add_argument("--input", default=None, metavar="SHAPE[:DTYPE]",
                    help="per-row feature shape, e.g. 28,28,1 or 16:int32 "
                         "(required unless --decode)")
    ap.add_argument("--decode", action="store_true",
                    help="iteration-level autoregressive decode serving "
                         "(GPT2LM/LlamaLM-style models; serve/decode.py)")
    ap.add_argument("--slots", type=int, default=None,
                    help="decode: concurrent KV slots "
                         "(BIGDL_TPU_SERVE_DECODE_SLOTS)")
    ap.add_argument("--max-seq-len", type=int, default=None,
                    help="decode: slot cache length "
                         "(BIGDL_TPU_SERVE_MAX_SEQ_LEN)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="decode: largest prompt-prefill chunk "
                         "(BIGDL_TPU_SERVE_PREFILL_CHUNK)")
    ap.add_argument("--max-new", type=int, default=16,
                    help="decode: default max_new_tokens per request")
    ap.add_argument("--eos", type=int, default=None,
                    help="decode: stop-token id override")
    ap.add_argument("--name", default="default", help="registry model name")
    ap.add_argument("--max-batch", type=int, default=None)
    ap.add_argument("--max-wait-ms", type=float, default=None)
    ap.add_argument("--max-queue-rows", type=int, default=None)
    ap.add_argument("--int8", action="store_true",
                    help="serve the int8-quantized forward")
    ap.add_argument("--mesh", action="store_true",
                    help="dispatch under the global device mesh "
                         "(sharded batch inference)")
    ap.add_argument("--precompile", action="store_true",
                    help="AOT-compile every shape bucket before traffic")
    ap.add_argument("--http", action="store_true",
                    help="serve over the HTTP/SSE network front "
                         "(serve/net.py) instead of stdin")
    ap.add_argument("--http-port", type=int, default=None,
                    help="network-front port (0/default = ephemeral, "
                         "printed in the READY line; knob: "
                         "BIGDL_TPU_SERVE_HTTP_PORT)")
    ap.add_argument("--replicas", type=int, default=None,
                    help="with --http: spawn N replica processes and "
                         "front them with the ReplicaRouter "
                         "(BIGDL_TPU_SERVE_REPLICAS)")
    ap.add_argument("--replica-ready-s", type=float, default=600.0,
                    help="with --replicas: seconds the launcher waits "
                         "for every replica's READY line (start-up and "
                         "compilation included)")
    ap.add_argument("--smoke", action="store_true",
                    help="self-drive concurrent clients, print one JSON "
                         "summary, exit (CI probe)")
    ap.add_argument("--smoke-threads", type=int, default=4)
    ap.add_argument("--smoke-requests", type=int, default=8,
                    help="requests per smoke client thread")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.http:
        from bigdl_tpu.utils import config
        replicas = (args.replicas if args.replicas is not None
                    else int(config.get("SERVE_REPLICAS")))
        if replicas > 1:
            # The parent is transport-only: no model, no engine, no
            # jax — each replica subprocess owns a full engine.
            return _router_main(args, replicas)

    import jax
    from bigdl_tpu.serve.engine import ServeEngine

    mesh = None
    if args.mesh:
        from bigdl_tpu.parallel.mesh import create_mesh
        mesh = create_mesh(drop_trivial_axes=True)

    if args.decode:
        if args.factory is None:
            from bigdl_tpu.serve.decode import decode_demo_model
            model, params, state = decode_demo_model(seed=args.seed)
        else:
            model = _load_factory(args.factory)
            params, state = model.init(
                jax.random.PRNGKey(args.seed))  # tpu-lint: disable=004
        engine = ServeEngine(install_sigterm=not args.smoke)
        try:
            engine.register(
                args.name, model, params, state, mesh=mesh, decode=True,
                num_slots=args.slots, max_seq_len=args.max_seq_len,
                prefill_chunk=args.prefill_chunk, eos_id=args.eos)
            if args.http:
                return _http_main(engine, args, decode=True)
            if args.smoke:
                rec = _decode_smoke(
                    engine, args.name, threads=args.smoke_threads,
                    requests=args.smoke_requests, max_new=args.max_new,
                    seed=args.seed)
                print(json.dumps(rec))
                return 1 if rec["errors"] else 0
            return _decode_stdin_loop(engine, args.name, args.max_new)
        finally:
            engine.shutdown()

    if args.input is None:
        raise SystemExit("--input is required (unless --decode)")
    if args.factory is None:
        raise SystemExit("a model factory is required (unless --decode)")
    feature_shape, dtype = _parse_input(args.input)
    model = _load_factory(args.factory)
    params, state = model.init(
        jax.random.PRNGKey(args.seed))  # tpu-lint: disable=004

    engine = ServeEngine(install_sigterm=not args.smoke)
    try:
        engine.register(
            args.name, model, params, state, mesh=mesh,
            max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
            max_queue_rows=args.max_queue_rows,
            int8=True if args.int8 else None,
            precompile_input=((feature_shape, dtype)
                              if args.precompile else None))
        if args.http:
            return _http_main(engine, args, decode=False,
                              feature=(feature_shape, dtype))
        if args.smoke:
            rec = _smoke(engine, args.name, feature_shape, dtype,
                         threads=args.smoke_threads,
                         requests=args.smoke_requests, seed=args.seed)
            print(json.dumps(rec))
            return 1 if rec["errors"] else 0
        return _stdin_loop(engine, args.name, dtype)
    finally:
        engine.shutdown()


if __name__ == "__main__":
    # persistent XLA cache at JAX_COMPILATION_CACHE_DIR, else
    # <checkout>/.jax_cache: a setting, not a backend — the --replicas
    # parent still never initialises one (docs/compile_cache.md)
    from bigdl_tpu import compilecache
    compilecache.enable()
    sys.exit(main())
