"""The load generator: a process of its own that never imports JAX.

The parent holds the chip and serves; this child is told the port, the mix
and the seed, sends the requests over plain sockets and writes one line per
request with the client's clock at the send and at every token. A client in
the server's interpreter would charge its own work to the server through the
interpreter lock.

Protocol with the parent, over the child's standard streams: the child warms
the server with a few requests of the same mix, prints `READY`, waits for a
line on standard input, measures for `--seconds`, waits for what is still in
flight, writes `--out` and prints `DONE <t0>` (`t0` is the window's first
instant on CLOCK_MONOTONIC, which parent and child share).
"""

import argparse
import http.client
import json
import sys
import threading
import time

import trafficgen

WARM_BASE = 1 << 20         # warm-up requests use indices the window never does


def ask(host, port, model, req, stream, timeout):
    """Send one request. Returns (t_sent, stamps, tokens, error)."""
    body = json.dumps({"model": model, "prompt": req["prompt"],
                       "max_new_tokens": req["max_new_tokens"],
                       "eos_id": -1, "stream": bool(stream)}).encode()
    stamps, tokens = [], []
    t_sent = time.monotonic()
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("POST", "/v1/generate", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            return t_sent, stamps, tokens, \
                f"HTTP {resp.status}: {resp.read(300)!r}"
        if not stream:
            tokens = json.loads(resp.read().decode())["tokens"]
            return t_sent, [time.monotonic()] * len(tokens), tokens, None
        event = "message"
        for raw in resp:
            line = raw.strip()
            if line.startswith(b"event:"):
                event = line.split(b":", 1)[1].strip().decode()
                if event == "done":
                    return t_sent, stamps, tokens, None
            elif line.startswith(b"data:"):
                now = time.monotonic()
                payload = json.loads(line.split(b":", 1)[1])
                if event == "error":
                    return t_sent, stamps, tokens, f"SSE error: {payload}"
                tokens.append(int(payload["token"]))
                stamps.append(now)
        return t_sent, stamps, tokens, "stream ended without 'done'"
    except (OSError, ValueError, KeyError, http.client.HTTPException) as e:
        return t_sent, stamps, tokens, repr(e)
    finally:
        conn.close()


class Run:
    def __init__(self, args, mix):
        self.args, self.mix = args, mix
        self.requests = trafficgen.Requests(mix, args.seed, args.vocab,
                                            args.eos, args.max_seq_len)
        self.lock = threading.Lock()
        self.next_index = 0
        self.records = []

    def one(self, index, due=None):
        req = self.requests[index]
        a = self.args
        t_sent, stamps, tokens, err = ask(
            a.host, a.port, a.model, req, self.mix.get("stream", True),
            a.timeout)
        if err is None and len(tokens) != req["max_new_tokens"]:
            err = f"{len(tokens)} tokens, asked {req['max_new_tokens']}"
        rec = {"index": index, "due": due, "sent": t_sent, "stamps": stamps,
               "tokens": tokens, "error": err,
               "prompt_len": len(req["prompt"]),
               "asked": req["max_new_tokens"]}
        with self.lock:
            self.records.append(rec)
        return rec

    def take(self):
        with self.lock:
            i = self.next_index
            self.next_index += 1
        return i

    def closed_loop(self, t0, seconds):
        def caller():
            while time.monotonic() < t0 + seconds:
                self.one(self.take())
        threads = [threading.Thread(target=caller, daemon=True)
                   for _ in range(int(self.mix["clients"]))]
        for t in threads:
            t.start()
        return threads

    def open_loop(self, t0, seconds):
        threads = []
        for i, due in enumerate(self.requests.due_times(seconds)):
            wait = t0 + due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            t = threading.Thread(target=self.one, args=(i, t0 + due),
                                 daemon=True)
            t.start()
            threads.append(t)
        return threads

    def warm(self):
        n = int(self.mix.get("clients", 4))
        per = int(self.mix.get("warmup_requests_per_client", 1))

        def caller(c):
            for k in range(per):
                self.one(WARM_BASE + c * per + k)
        threads = [threading.Thread(target=caller, args=(c,), daemon=True)
                   for c in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(self.args.timeout)
        bad = [r["error"] for r in self.records if r["error"]]
        self.records = []
        return bad


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--model", required=True)
    ap.add_argument("--traffic", required=True, help="the mix's file")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--eos", type=int, required=True)
    ap.add_argument("--max-seq-len", type=int, required=True)
    ap.add_argument("--timeout", type=float, default=120.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    mix = trafficgen.read_mix(args.traffic)
    run = Run(args, mix)
    bad = run.warm()
    if bad:
        print("FAILED warm-up: " + "; ".join(bad[:3]), flush=True)
        return 1
    print("READY", flush=True)
    if not sys.stdin.readline():
        return 1                                    # the parent went away
    t0 = time.monotonic()
    loop = run.closed_loop if mix["kind"] == "closed_loop" else run.open_loop
    threads = loop(t0, args.seconds)
    deadline = t0 + args.seconds + args.timeout
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    hung = sum(t.is_alive() for t in threads)
    with run.lock:
        records = sorted(run.records, key=lambda r: r["sent"])
    with open(args.out, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
    print(f"DONE {t0!r} {hung}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
