"""bigdl_tpu.serve — online inference: continuous batching over AOT
shape buckets.

The training stack's serving counterpart (reference surface:
`Predictor`, `PredictionService.scala:56-66`, dlframes — SURVEY L5/L6).
Batch predict already exists (`optim/predictor.py`); this package
handles LIVE traffic:

  * **batcher**  — bounded FIFO request queue + scheduler thread packing
                   concurrent requests into the smallest precompiled
                   shape bucket (continuous/dynamic batching), with a
                   `max_wait_ms` deadline trading batch fullness against
                   latency, typed `Overloaded` admission control, and
                   graceful drain (no lost futures);
  * **registry** — named models, each with its own params/mesh/dtype,
                   a zero-pad + valid-mask forward (pad content can
                   never leak), optional int8 via BIGDL_TPU_SERVE_INT8,
                   and per-bucket AOT executables
                   (compilecache.precompile_buckets) so a warm server
                   compiles zero fresh programs;
  * **engine**   — the facade: submit/predict, oversized-request
                   chunking, per-model p50/p99 latency + queue-depth +
                   batch-fill SLO metrics through the observe registry,
                   SIGTERM drain riding the resilience handler;
  * **decode**   — iteration-level continuous batching for
                   autoregressive LMs: a persistent paged KV pool
                   a model, chunked prompt prefill through
                   length-bucketed AOT programs, one fused greedy step
                   per iteration over the ragged active set, requests
                   joining free slots and retiring (EOS/max_new) EVERY
                   step — no head-of-line blocking, O(L) per token
                   (`ServeEngine.register(decode=True)` +
                   `submit_generate`, serve/decode.py);
  * **net**      — the HTTP/SSE network front (`ServeFront` +
                   `LocalBackend`): /v1/predict and /v1/generate JSON
                   codecs over a real socket, SSE token streaming at
                   iteration cadence, priority classes with a batch
                   admission quota, and per-client accounting
                   (serve/net.py, shared server core utils/httpd.py);
  * **router**   — multi-replica dispatch (`ReplicaRouter`): one front
                   over N replica processes, placement by queue load +
                   /memz headroom, health-cached probes, and
                   retry-on-survivor failover that resumes mid-flight
                   SSE streams with no duplicate tokens
                   (serve/router.py);
  * **CLI**      — `python -m bigdl_tpu.serve <factory> --input SHAPE`
                   (line-JSON requests on stdin; `--smoke` self-drives;
                   `--decode` stands up the autoregressive path;
                   `--http [--replicas N]` the network front).

Knobs: BIGDL_TPU_SERVE_MAX_BATCH / _MAX_WAIT_MS / _MAX_QUEUE_ROWS /
_MODEL_QUEUE_ROWS / _INT8 / _DECODE_SLOTS / _PREFILL_CHUNK /
_MAX_SEQ_LEN / _HTTP_PORT / _HTTP_HOST / _REPLICAS / _BATCH_QUOTA_PCT /
_ROUTER_RETRIES / _ROUTER_HEALTH_TTL_S (utils/config.py).
Docs: docs/serving.md.
"""

from bigdl_tpu.serve.batcher import (Closed, ContinuousBatcher, Overloaded)
from bigdl_tpu.serve.decode import (DecodeEntry, DecodeScheduler, GenReply,
                                    decode_demo_model, prefill_buckets)
from bigdl_tpu.serve.engine import Reply, ServeEngine
from bigdl_tpu.serve.net import LocalBackend, ServeFront
from bigdl_tpu.serve.registry import (ModelEntry, ModelRegistry,
                                      serve_buckets)
from bigdl_tpu.serve.router import ReplicaRouter

__all__ = [
    "ServeEngine", "Reply", "GenReply",
    "ContinuousBatcher", "Overloaded", "Closed",
    "ModelRegistry", "ModelEntry", "serve_buckets",
    "DecodeEntry", "DecodeScheduler", "decode_demo_model",
    "prefill_buckets",
    "ServeFront", "LocalBackend", "ReplicaRouter",
]
