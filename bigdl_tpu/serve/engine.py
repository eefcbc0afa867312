"""ServeEngine — the online inference server.

Ties the pieces together: a `ModelRegistry` of named models
(registry.py), one `ContinuousBatcher` per model (batcher.py), SLO
accounting through the observe registry, and graceful drain riding the
resilience SIGTERM handler. The reference's live-inference surface is
`Predictor`/`PredictionService` (SURVEY L5/L6); this is that surface
grown into a traffic-shaped server: bounded queues, dynamic batching
over AOT shape buckets, admission control, and per-model latency SLOs.

    engine = ServeEngine()
    engine.register("mnist", model, params, state, mesh=mesh)
    fut = engine.submit("mnist", batch_of_rows)   # -> Future-like
    out = engine.predict("mnist", rows)           # sync sugar
    engine.stats()["mnist"]["p99_ms"]             # SLO view
    engine.shutdown()                             # drains every queue

Request lifecycle: `submit` validates (empty requests are a client
error), CHUNKS oversized requests into <= max_batch pieces (each rides
the queue as its own unit, so one huge request cannot monopolize a
bucket), and returns a reply whose `.result()` reassembles the rows.
Admission control raises the typed `Overloaded` before queueing.

Shutdown: `shutdown()` — or SIGTERM, via the same
`resilience.faults.install_sigterm_handler` path the trainers use —
stops admission (submit raises `Closed`), drains every queued request
to completion, and joins the scheduler threads: no future is ever lost.
"""

from __future__ import annotations

import logging
from typing import Dict, List, Optional

import numpy as np

from bigdl_tpu import observe
from bigdl_tpu.serve.batcher import Closed, ContinuousBatcher, Overloaded
from bigdl_tpu.serve.decode import DecodeScheduler, GenReply
from bigdl_tpu.serve.registry import ModelEntry, ModelRegistry
from bigdl_tpu.utils.threads import make_lock

log = logging.getLogger("bigdl_tpu")

__all__ = ["ServeEngine", "Reply", "GenReply", "Overloaded", "Closed",
           "parse_model_queue_rows"]


def parse_model_queue_rows(raw: str) -> Dict[str, int]:
    """Parse BIGDL_TPU_SERVE_MODEL_QUEUE_ROWS: '' -> {} (every model
    takes the SERVE_MAX_QUEUE_ROWS default), a bare int ('512') -> a
    '*' wildcard entry applying to every model, 'm1=512,m2=256' ->
    per-model entries (a bare int may ride the same list as the
    default for unnamed models). Raises ValueError on garbage — a
    typo'd admission bound must not silently become the default."""
    out: Dict[str, int] = {}
    for part in (raw or "").split(","):
        part = part.strip()
        if not part:
            continue
        if "=" in part:
            model, _, rows = part.partition("=")
            model = model.strip()
            if not model:
                raise ValueError(
                    f"SERVE_MODEL_QUEUE_ROWS entry {part!r}: empty "
                    f"model name")
            out[model] = int(rows)
        else:
            out["*"] = int(part)
    for model, rows in out.items():
        if rows < 1:
            raise ValueError(
                f"SERVE_MODEL_QUEUE_ROWS for {model!r} must be >= 1, "
                f"got {rows}")
    return out


class Reply:
    """Handle for one submitted request (possibly chunked across several
    queue units). `.result(timeout)` blocks and reassembles the rows in
    submission order; chunk failures re-raise."""

    __slots__ = ("_futures",)

    def __init__(self, futures: List):
        self._futures = futures

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        outs = [f.result(timeout) for f in self._futures]
        return outs[0] if len(outs) == 1 else np.concatenate(outs, axis=0)

    def done(self) -> bool:
        return all(f.done() for f in self._futures)


class ServeEngine:
    """Registry + per-model continuous batchers behind one facade."""

    def __init__(self, *, install_sigterm: bool = False):
        from bigdl_tpu.utils import config
        observe.ensure_started()
        # live telemetry plane: /statusz serves this engine's per-model
        # stats() (p50/p99/shed/queue-depth) — weakly held, so a dropped
        # engine vanishes from the payload (observe/statusz.py)
        from bigdl_tpu.observe import statusz as _statusz
        _statusz.register_engine(self)
        # serve-SLO watchdog (observe/doctor.py): the step-time
        # watchdog's median/MAD machinery pointed at this engine's
        # per-model p99 — armed once per process by the first engine
        # (BIGDL_TPU_SERVE_WATCHDOG_PCT, 0 = off), polled on a
        # sanctioned background cadence, never on the dispatch path
        from bigdl_tpu.observe import doctor as _doctor
        _doctor.arm_serve_watchdog()
        self.registry = ModelRegistry()
        self._batchers: Dict[str, ContinuousBatcher] = {}
        self._decoders: Dict[str, DecodeScheduler] = {}
        self._lock = make_lock("serve.engine")
        self._closed = False
        self._defaults = {
            "max_batch": config.get("SERVE_MAX_BATCH"),
            "max_wait_ms": config.get("SERVE_MAX_WAIT_MS"),
            # the global bound is the FLEET-WIDE cap (total queued rows
            # across every model of this engine); per-model bounds come
            # from SERVE_MODEL_QUEUE_ROWS / register(max_queue_rows=)
            # and default to the same value (docs/serving.md)
            "max_queue_rows": config.get("SERVE_MAX_QUEUE_ROWS"),
            "model_queue_rows": parse_model_queue_rows(
                config.get("SERVE_MODEL_QUEUE_ROWS")),
        }
        if install_sigterm:
            # the trainers' preemption path doubles as the server's
            # graceful-drain signal: SIGTERM -> preempt_requested() ->
            # every batcher drains and stops accepting
            from bigdl_tpu.resilience import faults
            faults.install_sigterm_handler()

    # ----------------------------------------------------------- registry
    def register(self, name: str, model, params, state, *, mesh=None,
                 max_batch: Optional[int] = None,
                 max_wait_ms: Optional[float] = None,
                 max_queue_rows: Optional[int] = None,
                 int8: Optional[bool] = None,
                 coalesce: bool = True,
                 precompile_input=None,
                 decode: bool = False,
                 num_slots: Optional[int] = None,
                 max_seq_len: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 max_queue: int = 256,
                 precompile_decode: bool = True,
                 paged: Optional[bool] = None,
                 kv_block: Optional[int] = None,
                 kv_pool_blocks: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 prefix_cache_blocks: Optional[int] = None,
                 sampling: Optional[bool] = None,
                 kv_shard: Optional[bool] = None) -> ModelEntry:
        """Register a model and start its scheduler. `precompile_input`
        = (feature_shape, dtype) AOT-compiles every bucket up front.

        `decode=True` registers the iteration-level autoregressive path
        instead (serve/decode.py): the model must carry the slot-decode
        contract (GPT2LM/LlamaLM/OlmoHybridLM), requests enter through
        `submit_generate`, and `precompile_decode` (default on)
        AOT-compiles the fused step + every prefill bucket so warm
        serving compiles zero fresh programs. num_slots / max_seq_len /
        prefill_chunk default to the BIGDL_TPU_SERVE_DECODE_* knobs;
        kv_block / kv_pool_blocks / prefix_cache / sampling / kv_shard
        override the BIGDL_TPU_SERVE_KV_* and
        BIGDL_TPU_SERVE_{PREFIX_CACHE,SAMPLING} knobs (paged KV block
        pool + shared-prefix reuse — docs/serving.md). `paged` takes
        None or True (DecodeEntry says why it is still here); False is
        refused: the dense slot bucket was removed.

        Admission is memory-checked (observe/memz.py): params+state —
        and for decode the closed-form KV pool, BEFORE allocation —
        must fit the remaining device headroom, else a `CapacityError`
        with the per-owner capacity report is raised and nothing is
        registered (no model entry, no scheduler thread). Registered
        trees are accounted in the buffer ledger (`serve/<name>/params`,
        `serve/<name>/kv_pool` — the /memz plane)."""
        if self._closed:
            raise Closed("engine is shut down")
        d = self._defaults
        entry = self.registry.register(
            name, model, params, state, mesh=mesh,
            max_batch=max_batch if max_batch is not None
            else d["max_batch"], int8=int8, decode=decode,
            num_slots=num_slots, max_seq_len=max_seq_len,
            prefill_chunk=prefill_chunk, eos_id=eos_id, paged=paged,
            kv_block=kv_block, kv_pool_blocks=kv_pool_blocks,
            prefix_cache=prefix_cache,
            prefix_cache_blocks=prefix_cache_blocks, sampling=sampling,
            kv_shard=kv_shard)
        from bigdl_tpu.resilience import faults
        if decode:
            if precompile_decode:
                entry.precompile_decode()
            sched = DecodeScheduler(entry.decode, name=name,
                                    max_queue=max_queue, start=False)
            sched.start(stop_check=faults.preempt_requested)
            with self._lock:
                self._decoders[name] = sched
            log.info("serve: decode model %r registered (slots=%d, "
                     "max_seq_len=%d, prefill buckets %s)", name,
                     entry.decode.num_slots, entry.decode.max_seq_len,
                     entry.decode.buckets)
            return entry
        if precompile_input is not None:
            shape, dtype = precompile_input
            entry.precompile_for(tuple(shape), dtype)
        if max_queue_rows is None:
            # per-model admission bound: explicit arg > per-model env
            # entry > bare-int env wildcard > the global default
            mq = d["model_queue_rows"]
            max_queue_rows = mq.get(name, mq.get("*",
                                                 d["max_queue_rows"]))
        batcher = ContinuousBatcher(
            entry.dispatch, entry.buckets, name=name, coalesce=coalesce,
            max_wait_ms=max_wait_ms if max_wait_ms is not None
            else d["max_wait_ms"],
            max_queue_rows=max_queue_rows,
            start=False)
        batcher.start(stop_check=faults.preempt_requested)
        with self._lock:
            self._batchers[name] = batcher
        log.info("serve: model %r registered (buckets %s, int8=%s)",
                 name, entry.buckets, entry.int8)
        return entry

    def unregister(self, name: str, drain: bool = True) -> None:
        with self._lock:
            batcher = self._batchers.pop(name, None)
            decoder = self._decoders.pop(name, None)
        if batcher is not None:
            batcher.close(drain=drain)
        if decoder is not None:
            decoder.close(drain=drain)
        self.registry.unregister(name)

    def models(self) -> List[str]:
        return self.registry.names()

    # ------------------------------------------------------------ serving
    def submit(self, name: str, x) -> Reply:
        """Queue a request for model `name`; returns a `Reply`. Raises
        ValueError (empty/scalar request), `Overloaded` (queue at
        bound — nothing partially queued), or `Closed` (shut down).
        Requests wider than the model's max_batch are chunked."""
        x = np.asarray(x)
        if x.ndim == 0:
            raise ValueError("request must be at least 1-D "
                             "(a batch of input rows)")
        if x.shape[0] == 0:
            raise ValueError("empty request: a serving request must "
                             "carry at least one row")
        with self._lock:
            batcher = self._batchers.get(name)
            total_rows = sum(b.queued_rows
                             for b in self._batchers.values())
        if batcher is None:
            raise KeyError(f"no model {name!r} registered")
        # fleet-wide cap: the global SERVE_MAX_QUEUE_ROWS bounds TOTAL
        # queued rows across every model of this engine — per-model
        # bounds shape one model's queue, this one protects the host
        # (the check is advisory-at-admission: concurrent submits may
        # overshoot by one request, which is the same race the
        # per-model bound already tolerates between lock scopes)
        fleet_cap = self._defaults["max_queue_rows"]
        if total_rows + x.shape[0] > fleet_cap:
            observe.counter("serve/shed").inc()
            observe.counter(f"serve/{name}/shed").inc()
            observe.instant("serve/shed", cat="serve",
                            args={"model": name, "fleet": True,
                                  "queued_rows": total_rows})
            raise Overloaded(
                f"fleet-wide queue at bound: {total_rows} rows queued "
                f"across {len(self._batchers)} model(s) + "
                f"{x.shape[0]} requested > {fleet_cap} "
                f"(BIGDL_TPU_SERVE_MAX_QUEUE_ROWS)")
        cap = batcher.buckets[-1]
        if x.shape[0] <= cap:
            return Reply([batcher.submit(x)])
        # oversized: all-or-nothing admission, then chunk FIFO —
        # contiguous submits under the batcher lock keep the chunks
        # adjacent so they pack into full buckets
        if x.shape[0] > batcher.max_queue_rows:
            observe.counter("serve/shed").inc()
            observe.counter(f"serve/{name}/shed").inc()
            raise Overloaded(
                f"request of {x.shape[0]} rows exceeds the queue bound "
                f"{batcher.max_queue_rows} for model {name!r}")
        futures = []
        try:
            for i in range(0, x.shape[0], cap):
                futures.append(batcher.submit(x[i:i + cap]))
        except (Overloaded, Closed):
            for f in futures:
                f.cancel()
            raise
        return Reply(futures)

    def predict(self, name: str, x,
                timeout: Optional[float] = None) -> np.ndarray:
        """Synchronous request: submit + wait + reassemble."""
        return self.submit(name, x).result(timeout)

    # ----------------------------------------------- autoregressive decode
    def submit_generate(self, name: str, prompt_ids,
                        max_new_tokens: int,
                        eos_id: Optional[int] = None,
                        temperature: float = 0.0, top_k: int = 0,
                        top_p: float = 1.0, seed: int = 0) -> GenReply:
        """Queue one generate request against a `decode=True` model;
        returns a streaming-capable `GenReply` (`.result()` blocks for
        the full generation, `.stream()` yields token ids as they
        decode). `temperature > 0` samples (top_k/top_p filtered,
        deterministic per seed — model must be registered with
        `sampling=True`); the default is greedy argmax. Raises KeyError
        (not a decode model), ValueError (empty prompt / budget over
        the slot cache length / sampling not compiled in),
        `Overloaded`, or `Closed`."""
        with self._lock:
            sched = self._decoders.get(name)
        if sched is None:
            raise KeyError(
                f"no decode model {name!r} registered (register with "
                f"decode=True; have: "
                f"{sorted(self._decoders) or 'none'})")
        return sched.submit(prompt_ids, max_new_tokens, eos_id=eos_id,
                            temperature=temperature, top_k=top_k,
                            top_p=top_p, seed=seed)

    def generate(self, name: str, prompt_ids, max_new_tokens: int,
                 eos_id: Optional[int] = None,
                 timeout: Optional[float] = None,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, seed: int = 0) -> np.ndarray:
        """Synchronous generate: submit + wait; returns the generated
        token ids (np.int32, EOS included when emitted)."""
        return self.submit_generate(
            name, prompt_ids, max_new_tokens, eos_id=eos_id,
            temperature=temperature, top_k=top_k, top_p=top_p,
            seed=seed).result(timeout)

    # ---------------------------------------------------------------- SLO
    def stats(self) -> Dict[str, Dict]:
        """Per-model SLO snapshot: p50/p99 latency (ms), request/batch
        counts, mean batch fill, queued rows — read from the observe
        registry (the same numbers the exporters flush)."""
        from bigdl_tpu.serve.batcher import (BATCH_FILL_BOUNDS,
                                             LATENCY_MS_BOUNDS)
        reg = observe.registry()
        out: Dict[str, Dict] = {}
        fill = reg.histogram("serve/batch_fill")
        with self._lock:
            batchers = dict(self._batchers)
            decoders = dict(self._decoders)
        for name, b in batchers.items():
            lat = reg.histogram(f"serve/{name}/latency_ms",
                                LATENCY_MS_BOUNDS)
            qw = reg.histogram(f"serve/{name}/queue_wait_ms",
                               LATENCY_MS_BOUNDS)
            disp = reg.histogram(f"serve/{name}/dispatch_ms",
                                 LATENCY_MS_BOUNDS)
            mfill = reg.histogram(f"serve/{name}/batch_fill",
                                  BATCH_FILL_BOUNDS)
            out[name] = {
                "requests": lat.count,
                "p50_ms": round(lat.quantile(0.50), 3),
                "p99_ms": round(lat.quantile(0.99), 3),
                # the latency decomposition the serve-SLO watchdog
                # attributes regressions with (observe/doctor.py)
                "queue_wait_p99_ms": round(qw.quantile(0.99), 3),
                "dispatch_mean_ms": round(
                    disp.sum / disp.count, 3) if disp.count else 0.0,
                # per-model bucket fill: the global serve/batch_fill
                # would misreport once a decode model shares the
                # process (decode slot occupancy is its own histogram)
                "mean_batch_fill": round(mfill.sum / mfill.count, 4)
                if mfill.count else 0.0,
                "queued_rows": b.queued_rows,
                "max_queue_rows": b.max_queue_rows,
                "shed": int(reg.counter(f"serve/{name}/shed").value),
                "buckets": list(b.buckets),
            }
        for name, sched in decoders.items():
            out.setdefault(name, {})["decode"] = sched.stats()
        out["_totals"] = {
            "requests": reg.counter("serve/requests").value,
            "rows": reg.counter("serve/rows").value,
            "batches": reg.counter("serve/batches").value,
            "shed": reg.counter("serve/shed").value,
            "mean_batch_fill": round(fill.sum / fill.count, 4)
            if fill.count else 0.0,
        }
        return out

    def queue_state(self) -> Dict[str, Dict]:
        """Lightweight admission view — per-model queue occupancy vs
        bound, decode slot availability — read by the network front's
        priority quota and /healthz (serve/net.py) without the
        histogram walks stats() pays."""
        with self._lock:
            batchers = dict(self._batchers)
            decoders = dict(self._decoders)
        out: Dict[str, Dict] = {}
        for name, b in batchers.items():
            bound = b.max_queue_rows
            out[name] = {"decode": False,
                         "queued_rows": b.queued_rows,
                         "max_queue_rows": bound,
                         "utilization": (b.queued_rows / bound)
                         if bound else 0.0}
        for name, s in decoders.items():
            out[name] = {"decode": True,
                         "queued": s.queued,
                         "max_queue": s.max_queue,
                         "active_slots": s.active_slots,
                         "free_slots": (s.entry.num_slots
                                        - s.active_slots),
                         "utilization": (s.queued / s.max_queue)
                         if s.max_queue else 0.0}
        return out

    # ----------------------------------------------------------- shutdown
    def shutdown(self, drain: bool = True,
                 timeout: Optional[float] = 30.0) -> None:
        """Stop admission and close every batcher. `drain=True` (the
        SIGTERM path) completes everything queued first; `drain=False`
        fails queued futures with `Closed`. Idempotent."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            batchers = dict(self._batchers)
            decoders = dict(self._decoders)
        for name, b in batchers.items():
            with observe.span("serve/drain", cat="serve",
                              args={"model": name}):
                b.close(drain=drain, timeout=timeout)
        for name, sched in decoders.items():
            with observe.span("serve/drain", cat="serve",
                              args={"model": name, "decode": True}):
                sched.close(drain=drain, timeout=timeout)
        n = len(batchers) + len(decoders)
        log.info("serve: engine shut down (%d model%s drained)",
                 n, "s" if n != 1 else "")

    def __enter__(self) -> "ServeEngine":
        return self

    def __exit__(self, *exc) -> bool:
        self.shutdown()
        return False
