"""Config / flag system (reference: the ~40 `bigdl.*` JVM system properties
— utils/Engine.scala:210-216, parameters/AllReduceParameter.scala:32-44,
optim/DistriOptimizer.scala:882-883, nn/mkldnn/Fusion.scala:34 — documented
in docs/docs/ScalaUserGuide/configuration.md).

Here: one typed env-var registry under the `BIGDL_TPU_` prefix. Every knob
is declared with a default + docstring so `print_config()` is the
configuration reference."""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional


def _bool(s: str) -> bool:
    return s.lower() in ("1", "true", "yes", "on")


@dataclass
class Knob:
    name: str                 # env var suffix
    default: Any
    parse: Callable
    doc: str

    @property
    def env(self) -> str:
        return f"BIGDL_TPU_{self.name}"

    def get(self):
        raw = os.environ.get(self.env)
        return self.default if raw is None else self.parse(raw)


_REGISTRY: Dict[str, Knob] = {}


def _register(name, default, parse, doc):
    _REGISTRY[name] = Knob(name, default, parse, doc)


_register("SEED", 1, int,
          "Global default RNG seed for trainers "
          "(reference: RandomGenerator defaults)")
_register("COMPUTE_DTYPE", "", str,
          "Forward/backward compute dtype of the trainers: "
          "'' (fp32) or 'bfloat16' (reference: FP16 wire compression, "
          "parameters/FP16CompressedTensor.scala — bf16 is the TPU form)")
_register("PREFETCH_SIZE", 2, int,
          "Host->device prefetch depth (dataset/prefetch.py; reference: "
          "bigdl.Parameter.syncPoolSize data threads)")
_register("FAILURE_RETRY_TIMES", 5, int,
          "Driver-loop retries from last checkpoint before giving up "
          "(reference: bigdl.failure.retryTimes, DistriOptimizer.scala:882)")
_register("FAILURE_RETRY_INTERVAL_S", 120, int,
          "Sliding window (seconds) for counting retries "
          "(reference: bigdl.failure.retryTimeInterval)")
_register("CHECK_SINGLETON", False, _bool,
          "Warn when two trainers share one process "
          "(reference: bigdl.check.singleton)")
_register("LOG_THROUGHPUT_EVERY", 20, int,
          "Iterations between trainer log lines "
          "(reference: per-iteration Throughput log)")
_register("STEPS_PER_CALL", 1, int,
          "Fused dispatch: optimizer steps per jitted call. K>1 stacks K "
          "host batches into one super-batch (one H2D transfer) and runs "
          "lax.scan over the train step on device, amortizing the Python "
          "dispatch that dominates small per-device workloads "
          "(optim/local.py; reference: the per-iteration Spark job "
          "overhead DistriOptimizer.scala:185-516 pays twice per step)")
_register("ACCUM_STEPS", 1, int,
          "Gradient accumulation: microbatches per optimizer step. M>1 "
          "splits each batch into M microbatches inside the jitted step, "
          "scans over them averaging gradients, then applies ONE update — "
          "the reference's mini-batch aggregation "
          "(optim/DistriOptimizer.scala gradient sum over sub-batches)")
_register("FAILURE_RETRY_BACKOFF_S", 0.0, float,
          "Initial exponential-backoff sleep between driver-loop retries "
          "(doubles per failure, capped at 16x; 0 disables — "
          "resilience/retry.py)")
_register("CHECKPOINT_FORMAT", 2, int,
          "On-disk snapshot format: 2 = per-host sharded shards + "
          "manifest.json + COMMIT marker (resilience/manifest.py), "
          "1 = legacy single-npz gather-to-host-0 (utils/checkpoint.py). "
          "Both formats load transparently on resume")
_register("CHECKPOINT_ASYNC", True, _bool,
          "Format-2 snapshots: take the device->host snapshot at the step "
          "boundary and run serialization+IO in a background thread "
          "(resilience/snapshot.py; CheckFreq-style split). 0 = write "
          "inline (the bench baseline)")
_register("CHECKPOINT_KEEP_N", 0, int,
          "Retention: keep only the newest N committed snapshots under "
          "the checkpoint root (0 = keep all; resilience/manifest.py)")
_register("CHECKPOINT_COMMIT_TIMEOUT_S", 300, int,
          "Multi-host format-2 commit: seconds process 0 polls for the "
          "other hosts' shard files before declaring the snapshot failed")
_register("CHECKPOINT_ON_PREEMPT", True, _bool,
          "Install a SIGTERM handler that requests one final checkpoint "
          "at the next steps_per_call K-boundary before stopping "
          "(resilience/faults.py; the TPU-preemption grace window)")
_register("FAULT", "", str,
          "Deterministic fault injection for resilience tests — a "
          "comma-separated list of one-shot events: 'step:N[:kind]' with "
          "kind crash (raise SimulatedCrash) | preempt (SIGTERM self) | "
          "io (fail the next shard write); 'slice:I@step:N' (lose slice "
          "I at the first K-boundary >= N — in-run failover, "
          "resilience/failover.py); 'grow@step:N' (capacity returns: "
          "grow back to the full mesh); 'nan@step:N' (poison iteration "
          "N's batch to NaN — exercises the non-finite step guard). "
          "Each event fires once (resilience/faults.py)")
_register("SLICES", 1, int,
          "Two-tier data parallelism: number of TPU slices. >1 splits "
          "the batch axis into a ('slice', 'data') mesh — ICI gradient "
          "reduction inside a slice, the cross-slice leg factored into "
          "the labeled cross_slice_exchange seam (parallel/mesh.py) — "
          "and arms in-run slice failover (docs/resilience.md)")
_register("SLICE_GRAD_DTYPE", "", str,
          "Compressed cross-slice gradient exchange: '' (off, exact) or "
          "'bfloat16' — floating grads round-trip through this dtype in "
          "the labeled cross-slice scope, halving DCN bytes at a "
          "quantization cost (parallel/mesh.py cross_slice_exchange)")
_register("SLICE_EXCHANGE_EVERY", 1, int,
          "DCN-tier gradient exchange period T (parallel/dcn.py): each "
          "slice accumulates its own gradient contribution locally and "
          "the cross-slice exchange — an explicit psum over ('slice',) "
          "in a shard_map'd exchange step — runs every T-th iteration, "
          "cutting DCN round trips by T (Local SGD / DiLoCo style). "
          "1 (default) = exchange every step: the pre-DCN path, "
          "bit-identical to every earlier build. T>1 needs a two-tier "
          "mesh (BIGDL_TPU_SLICES > 1); params/slots then advance only "
          "at window boundaries (docs/parallelism.md 'DCN-tier "
          "exchange')")
_register("SLICE_GRAD_COMPRESS", "", str,
          "Wire compression for the T-window cross-slice exchange: '' "
          "(off, exact), 'bfloat16', or 'int8' (symmetric per-256-"
          "element-block scales — the nn/quantized window recipe on "
          "the gradient wire), both with ERROR FEEDBACK: the "
          "compression residual is carried in the per-slice "
          "accumulator and re-enters the next window instead of "
          "biasing the outer step. 'int8' arms the accumulate/"
          "exchange machinery even at T=1. The legacy per-step "
          "BIGDL_TPU_SLICE_GRAD_DTYPE round-trip applies only when "
          "this machinery is off (docs/parallelism.md)")
_register("SLICE_OUTER", "", str,
          "Outer update applied at each T-window exchange "
          "(parallel/dcn.py): '' (default) = plain averaging — ONE "
          "inner-optimizer update from the cross-slice mean of the "
          "accumulated window gradient; 'nesterov' = DiLoCo-style "
          "outer Nesterov momentum (0.9) on the averaged window "
          "gradient before the inner update. Outer state rides the "
          "checkpoint next to the accumulator, so kill-and-resume "
          "mid-window is exact")
_register("ZERO1_SLICE_LOCAL", False, _bool,
          "ZeRO-1 slot layout on a two-tier mesh: 0 (default) shards "
          "over the composed ('slice','data') axes — bit-identical to "
          "the flat mesh, S-times smaller slots; 1 shards within a "
          "slice only, so every slice keeps a complete slot copy that "
          "survives a real slice death without the host round-trip "
          "(parallel/sharding.py zero1_spec)")
_register("MAX_NONFINITE", 3, int,
          "Abort training (NonFiniteLossError) after this many "
          "CONSECUTIVE non-finite training steps; 0 disables the abort "
          "(bad steps are still counted in train/nonfinite_steps and, "
          "on the fused path, their updates are masked out — "
          "optim/local.py)")
_register("TRACE", "", str,
          "Flight-recorder span tracing (observe/trace.py): a directory "
          "records host spans and dumps Chrome/Perfetto trace JSON there "
          "at the end of each optimize(); '1' uses /tmp/bigdl_tpu_trace; "
          "'' disables (zero-allocation no-op spans)")
_register("TRACE_RING", 100_000, int,
          "Span ring-buffer capacity: the newest N events are kept, the "
          "oldest fall off — a flight recorder, not an unbounded log "
          "(observe/trace.py)")
_register("METRICS_JSONL", "", str,
          "Structured run log: one JSON object per metrics flush appended "
          "to this path (observe/export.py); input of the "
          "`python -m bigdl_tpu.observe` phase report. '' disables")
_register("METRICS_PROM", "", str,
          "Prometheus textfile-collector export: the metrics registry "
          "rewritten atomically to this path every flush "
          "(observe/export.py). '' disables")
_register("METRICS_TB", "", str,
          "TensorBoard export dir for the metrics registry (scalars + "
          "native histogram events through visualization.EventWriter; "
          "process 0 only). '' disables")
_register("METRICS_FLUSH_S", 5.0, float,
          "Seconds between background exporter flushes "
          "(observe/export.py ExportManager)")
_register("RUN_ID", "", str,
          "Run id stamped into log prefixes, traces, and JSONL records; "
          "set the same value on every host of a multihost job "
          "(utils/runtime.py; '' derives one per process)")
_register("PRECOMPILE", False, _bool,
          "AOT warmup: trainers call precompile() at the top of "
          "optimize(), compiling the step/eval programs from shape specs "
          "before the first batch arrives and logging XLA cost analysis "
          "(optim/local.py precompile; CLI --precompile)")
_register("FUSED_UPDATE", "", str,
          "Run the optimizer update (Adam/AdamW/SGD) through the fused "
          "one-pass kernel (kernels/fused_update.py). '' / 0 (default) "
          "= off: the tree-map OptimMethod.update path stays the oracle "
          "and training is bit-identical. 1 = auto layout (flat blocks "
          "+ donated buffers through Pallas on TPU; per-leaf fused math "
          "elsewhere and on ZeRO-1/TP-sharded trees). 'flat' / 'leaf' "
          "force a layout. Unsupported methods log once and keep the "
          "tree-map path")
_register("AUTOTUNE", False, _bool,
          "Shape-keyed kernel autotuner (kernels/autotune.py): Pallas "
          "call sites using default block sizes consult the persistent "
          "table; a miss searches the block-size space once and records "
          "the winner. Off = hard-coded defaults, bit-identical "
          "behavior. CLI: python -m bigdl_tpu.kernels {tune,stats,clear}")
_register("AUTOTUNE_CACHE", "", str,
          "Autotune table root directory. '' derives "
          "<compile cache dir>/autotune when the persistent compile "
          "cache is on (compilecache.enable() or "
          "JAX_COMPILATION_CACHE_DIR), else the table is in-memory only "
          "for this process")
_register("SERVE_MAX_BATCH", 256, int,
          "Online serving: the largest shape bucket (rows) the engine "
          "compiles/dispatches. Buckets are powers-of-two times the "
          "mesh's data-axis size, capped here, so each model compiles "
          "O(log max_batch) programs total (serve/registry.py)")
_register("SERVE_MAX_WAIT_MS", 2.0, float,
          "Continuous batching deadline: a queued request older than "
          "this dispatches even if the batch is not full — the batch-"
          "fullness vs latency knob. 0 = greedy (dispatch whatever is "
          "queued the moment the scheduler is free; serve/batcher.py)")
_register("SERVE_MAX_QUEUE_ROWS", 4096, int,
          "Admission control: queued rows per model above which submit "
          "sheds load with the typed Overloaded error instead of "
          "queueing into latency collapse (serve/batcher.py)")
_register("SERVE_INT8", False, _bool,
          "Serve registered models through an int8-quantized forward "
          "(nn/quantized.quantize at registration; on a TPU backend "
          "QuantizedLinear routes through the fused Pallas "
          "kernels/quantized_matmul.py). Per-model override: "
          "ServeEngine.register(int8=...)")
_register("SERVE_DECODE_SLOTS", 8, int,
          "Autoregressive decode serving: KV slots per model — the "
          "number of sequences decoded concurrently by one fused "
          "iteration-level step. Requests join free slots every decode "
          "step and retire the moment they finish (serve/decode.py). "
          "Per-model override: ServeEngine.register(num_slots=...)")
_register("SERVE_PREFILL_CHUNK", 64, int,
          "Autoregressive decode serving: largest prompt-prefill chunk "
          "(tokens). Prompts stream into their slot's KV cache through "
          "power-of-two length-bucketed AOT prefill programs capped "
          "here — O(log chunk) programs total, and a long prompt "
          "cannot stall concurrent decode for more than one chunk "
          "(serve/decode.py)")
_register("SERVE_MAX_SEQ_LEN", 1024, int,
          "Autoregressive decode serving: the hard cap on prompt + "
          "generated tokens per sequence, and with it the length of a "
          "slot's block table; the paged KV pool is allocated once "
          "per model and donated across steps (serve/decode.py). "
          "Per-model override: ServeEngine.register(max_seq_len=...)")
_register("SERVE_KV_BLOCK", 16, int,
          "Paged KV cache: tokens per block. Smaller blocks waste "
          "less tail capacity per sequence but grow the block table; "
          "16 is the PagedAttention sweet spot. Per-model override: "
          "ServeEngine.register(kv_block=...)")
_register("SERVE_KV_POOL_BLOCKS", 0, int,
          "Paged KV cache: total blocks in the per-model pool. "
          "0 (default) = every slot at full length "
          "(slots x ceil(max_seq_len/block) blocks — the "
          "zero-risk default); size it BELOW that to spend less HBM "
          "than the worst case and let live block accounting admit "
          "against real usage (docs/serving.md sizing runbook). "
          "Per-model override: ServeEngine.register(kv_pool_blocks=...)")
_register("SERVE_PREFIX_CACHE", True, _bool,
          "Paged KV cache: retain finished sequences' full prompt-"
          "prefix blocks as refcounted read-only cache entries keyed "
          "by token-prefix hash, so requests sharing a prompt prefix "
          "(system prompts) skip its prefill entirely. "
          "Per-model override: "
          "ServeEngine.register(prefix_cache=...)")
_register("SERVE_PREFIX_CACHE_BLOCKS", 0, int,
          "Prefix cache retention cap: max UNREFERENCED cached blocks "
          "kept for future reuse (beyond it the LRU entry is evicted "
          "on release). 0 (default) = half the pool. Referenced "
          "(live-shared) blocks are never counted against the cap")
_register("SERVE_SAMPLING", False, _bool,
          "Autoregressive decode serving: compile the fused decode "
          "step with temperature/top-k/top-p sampling + per-slot "
          "stateless rng (nn/sampling.py). Greedy stays the default "
          "per request (temperature=0 rows take the argmax path "
          "bit-identically); off (default) compiles the pure greedy "
          "step — the parity-oracle path. Per-model override: "
          "ServeEngine.register(sampling=...)")
_register("SERVE_KV_SHARD", False, _bool,
          "Paged KV cache: shard the block pool's block dimension "
          "over the mesh's 'data' axis via NamedSharding (pool "
          "blocks rounded up to a multiple of the axis size; specs "
          "pinned and asserted on the AOT executables) — readies the "
          "pool for real-chip scale. Requires a mesh at registration; "
          "replicated (default) otherwise")
_register("SERVE_MODEL_QUEUE_ROWS", "", str,
          "Per-model admission bounds for the serve queues "
          "(serve/engine.py): '' = every model takes the "
          "SERVE_MAX_QUEUE_ROWS default; a bare int applies to every "
          "model; 'm1=512,m2=256' sets named models (a bare int may "
          "ride the same list as the default for the rest). "
          "register(max_queue_rows=...) still wins. The global "
          "SERVE_MAX_QUEUE_ROWS stays the FLEET-WIDE cap on total "
          "queued rows across all models of one engine")
_register("SERVE_HTTP_PORT", 0, int,
          "Serving network front (serve/net.py): HTTP port for the "
          "/v1/predict /v1/generate /v1/models /healthz request plane "
          "over this process's ServeEngine. 0 (default) = off; the "
          "CLI (`python -m bigdl_tpu.serve --http`) passes its own "
          "port (0 there binds an ephemeral one and prints it)")
_register("SERVE_HTTP_HOST", "127.0.0.1", str,
          "Bind address for the serving network front. Loopback by "
          "default — widening the bind to real traffic is a "
          "deliberate operator choice (docs/serving.md runbook)")
_register("SERVE_REPLICAS", 1, int,
          "`python -m bigdl_tpu.serve --http` replica count: N > 1 "
          "spawns N single-engine replica processes and fronts them "
          "with the headroom-aware ReplicaRouter (serve/router.py) "
          "instead of serving one in-process engine")
_register("SERVE_BATCH_QUOTA_PCT", 50.0, float,
          "Priority admission quota (serve/net.py): requests in the "
          "'batch' priority class are shed with 429 once a model's "
          "queue is fuller than this percent of its bound, reserving "
          "the rest for 'interactive' traffic. 100 disables the "
          "distinction; 0 rejects all batch traffic")
_register("SERVE_ROUTER_RETRIES", 2, int,
          "ReplicaRouter (serve/router.py): attempts on OTHER replicas "
          "after a replica death/connection failure before the request "
          "fails (predict is idempotent; a resumed stream skips "
          "already-delivered tokens). 0 = no failover")
_register("SERVE_ROUTER_HEALTH_TTL_S", 0.5, float,
          "ReplicaRouter placement-state cache: seconds a replica's "
          "/healthz headroom+queue snapshot stays fresh before the "
          "next placement re-scrapes it (0 = scrape every request)")
_register("DATA_SERVICE", True, _bool,
          "Streaming input service (dataset/service.py): trainers feed "
          "through the staged host pipeline — background read-ahead, "
          "optional echoing, and double-buffered H2D placement — instead "
          "of the plain prefetch thread. 0 = the pre-service feed path "
          "(batch content is identical either way; docs/data.md)")
_register("DATA_WORKERS", 0, int,
          "Host-pipeline decode workers shared by the record-shard / "
          "vision / text loaders (dataset/service.py resolve_workers). "
          "0 = auto: min(8, max(4, cpu_count)) — more threads than cores "
          "is right for IO-bound record fetch, which is what the workers "
          "overlap (reference: MTImageFeatureToBatch parallelism knob)")
_register("DATA_ECHO", 1, int,
          "Data echoing (Choi et al., 'Faster Neural Network Training "
          "with Data Echoing'): each host batch is trained N times "
          "before the next one is read, multiplying effective training "
          "throughput for IO-bound runs by up to N. Echoed copies are "
          "re-augmented when the dataset exposes `echo_transform`. The "
          "resume cursor counts echoed batches (the echo counter rides "
          "the snapshot's data_state) — keep N fixed across a "
          "kill/resume pair (dataset/service.py echo_batches)")
_register("DATA_DOUBLE_BUFFER", 1, int,
          "Double-buffered H2D placement depth under the input service: "
          "a background thread places super-batch N+1 while the device "
          "computes N (depth 1 = one placed batch queued + one in "
          "flight, the classic double buffer; 0 = synchronous "
          "placement). Ignored when BIGDL_TPU_DATA_SERVICE=0, where "
          "PREFETCH_SIZE keeps its legacy meaning")
_register("STATUSZ_PORT", 0, int,
          "Live telemetry plane (observe/statusz.py): HTTP port for the "
          "in-process /healthz /metrics /statusz /tracez /profilez "
          "endpoints, served from a stdlib http.server thread on "
          "process 0. 0 (default) = off. The server reads only "
          "host-side registry state — a scrape never adds a device "
          "sync (docs/observability.md)")
_register("STATUSZ_HOST", "127.0.0.1", str,
          "Bind address for the statusz server. The default is "
          "loopback-only; set 0.0.0.0 deliberately when a scraper "
          "lives off-host (the endpoints expose run metadata)")
_register("WATCHDOG_PCT", 50.0, float,
          "Step-time anomaly watchdog (observe/doctor.py): flag a "
          "sustained regression when the per-flush mean step time "
          "exceeds the rolling-median baseline by this percentage "
          "(robust MAD gate on top). Rides the existing _flush_metrics "
          "cadence — no extra host syncs. 0 disables the watchdog")
_register("WATCHDOG_WINDOW", 32, int,
          "Watchdog rolling-baseline window: number of recent flush "
          "samples the median/MAD baseline is computed over (anomalous "
          "samples are kept OUT of the baseline so a slowdown cannot "
          "normalize itself)")
_register("WATCHDOG_SUSTAIN", 2, int,
          "Consecutive anomalous flush windows before the watchdog "
          "opens an incident (one loud log + watchdog/incidents + the "
          "/statusz alerts entry); transient single-window blips only "
          "count in watchdog/anomalies")
_register("FORENSICS", "1", str,
          "Crash forensics bundles (observe/doctor.py): on "
          "NonFiniteLossError, retry exhaustion, or an unhandled "
          "optimize() exception, dump a forensics-<ts>/ bundle (ring "
          "spans, metrics snapshot, statusz JSON, live config, error "
          "traceback). '1' (default) writes next to the trace dir "
          "(or /tmp/bigdl_tpu_forensics without one), a path overrides "
          "the destination root, '0' disables. Newest 8 bundles kept")
_register("FLEET", False, _bool,
          "Fleet telemetry aggregation (observe/fleet.py): process 0 "
          "polls every peer's /statusz plane and serves the merged "
          "/fleetz + /fleetz/metrics endpoints; non-zero processes "
          "serve their own statusz plane at STATUSZ_PORT + "
          "process_index so the aggregator can reach them. Peer "
          "addresses derive from the distributed process table "
          "(utils/runtime.py fleet_peer_candidates) unless "
          "BIGDL_TPU_FLEET_PEERS names them explicitly (which also "
          "implies FLEET=1 on the process that carries it)")
_register("FLEET_PEERS", "", str,
          "Explicit fleet peer list: comma-separated host:port statusz "
          "endpoints the aggregator polls (the real-topology override "
          "of the derived per-process ports). Setting it arms fleet "
          "aggregation on this process (observe/fleet.py)")
_register("FLEET_POLL_S", 0.0, float,
          "Fleet aggregator poll cadence in seconds; 0 (default) rides "
          "the exporter flush cadence (BIGDL_TPU_METRICS_FLUSH_S) — "
          "one fleet scrape per export flush")
_register("FLEET_STALE_POLLS", 3, int,
          "Consecutive failed polls after which a fleet peer is marked "
          "STALE in /fleetz (never dropped: its last-known state and "
          "failure count stay visible; fleet/peer_unreachable counts "
          "every miss)")
_register("SERVE_WATCHDOG_PCT", 50.0, float,
          "Serve-SLO watchdog (observe/doctor.py ServeWatchdog): flag a "
          "poll window whose per-model serve p99 exceeds the rolling-"
          "median baseline by this percentage (3xMAD gate on top, same "
          "machinery as the step-time watchdog). A sustained regression "
          "opens ONE incident attributed to queue-wait vs dispatch vs "
          "batch-fill. 0 disables. Armed by the first ServeEngine; "
          "polls on the FLEET_POLL_S/METRICS_FLUSH_S cadence")
_register("ALERT_CMD", "", str,
          "Alert fan-out hook: shell command run once per opened "
          "incident (watchdog or serve-SLO) with the incident JSON on "
          "stdin — a pager/Slack bridge without new deps. Runs on a "
          "background thread with bounded retry "
          "(ALERT_RETRIES/ALERT_BACKOFF_S); never blocks the flush "
          "path. '' disables (observe/alerts.py)")
_register("ALERT_WEBHOOK", "", str,
          "Alert fan-out hook: URL that receives the incident JSON as "
          "an HTTP POST (application/json) once per opened incident; "
          "same bounded-retry, never-blocks contract as ALERT_CMD. "
          "'' disables")
_register("ALERT_RETRIES", 2, int,
          "Bounded re-delivery attempts per alert sink after the first "
          "failure (exponential backoff from ALERT_BACKOFF_S, the "
          "resilience/retry.py curve); exhaustion counts "
          "alerts/failed and is logged, never raised")
_register("ALERT_BACKOFF_S", 0.5, float,
          "Initial backoff between alert delivery retries (doubles per "
          "attempt, 16x cap — resilience/retry.py backoff_delay)")
_register("FORENSICS_PROFILE_S", 1.0, float,
          "Capture-on-crash: when a crash lands WHILE a watchdog or "
          "serve-SLO incident is live, dump_forensics arms a "
          "/profilez-style jax.profiler capture of this many seconds "
          "into the bundle's profile/ dir (the device timeline of the "
          "regression that preceded the crash). 0 disables")
_register("MEM_LEDGER", True, _bool,
          "Device-memory buffer ledger (observe/memz.py): subsystems "
          "that pin long-lived device memory (trainer param/slot trees, "
          "serve model params, decode KV pools, data-service "
          "staging) register their trees under named owners — "
          "mem/<owner>/bytes gauges, the /memz endpoint, headroom "
          "estimates, and OOM forensics attribution all read from it. "
          "Bytes are computed from shapes host-side (never a device "
          "sync). 0 disables every registration (no-op handles)")
_register("MEM_WATCHDOG_PCT", 85.0, float,
          "Memory watchdog (observe/memz.py MemoryWatchdog): open ONE "
          "incident — attributed to the fastest-growing ledger owner, "
          "riding the alert fan-out — when device-memory utilization "
          "stays above this percent of the capacity limit for "
          "WATCHDOG_SUSTAIN polls. Armed by observe.ensure_started() "
          "ONLY when a limit is known (backend bytes_limit or "
          "BIGDL_TPU_MEM_LIMIT_BYTES); polls on the FLEET_POLL_S/"
          "METRICS_FLUSH_S cadence. 0 disables")
_register("MEM_LIMIT_BYTES", 0, int,
          "Device-memory capacity override in bytes (observe/memz.py): "
          "0 (default) trusts the backend's bytes_limit (TPU/GPU report "
          "one; the CPU test mesh does not). Setting it arms the memory "
          "watchdog + serve admission checks on limit-less backends and "
          "caps utilization/headroom math everywhere")
_register("MEM_DRIFT_PCT", 5.0, float,
          "Ledger-vs-backend drift tolerance: `python -m "
          "bigdl_tpu.observe memz` exits 1 when |unattributed bytes| "
          "exceeds this percent of backend in-use (unattributed = "
          "in_use - baseline - ledger total: XLA workspace + anything "
          "that skipped registration — observe/memz.py)")
_register("SANITIZE", "", str,
          "Concurrency sanitizer (analysis/sancov.py): '' (default) = "
          "off, wrappers never installed, zero cost. '1' enables every "
          "mode; a comma list picks from 'locks' (instrumented "
          "Lock/RLock/Condition via utils/threads factories: "
          "lock-acquisition-order graph with cycle reports, long-hold "
          "reports, lockset unlocked-write checks on registered shared "
          "structures) and 'sync' (jax.device_get guard attributing "
          "un-sanctioned device->host fetches inside phase spans). Set "
          "at process start — locks constructed before enabling stay "
          "untracked. Findings surface in /statusz, forensics bundles, "
          "`observe doctor`, and `python -m bigdl_tpu.analysis threads`")
_register("SANITIZE_HOLD_MS", 250.0, float,
          "Long-hold threshold for the locks sanitizer: releasing a "
          "lock held longer than this many milliseconds files a "
          "long-hold report (a sleeping/IO-bound lock holder "
          "serializes every other participant)")


def get(name: str):
    """config.get('SEED') — typed, env-overridable."""
    return _REGISTRY[name].get()


def knobs() -> Dict[str, Knob]:
    return dict(_REGISTRY)


def print_config() -> str:
    lines = []
    for k in _REGISTRY.values():
        cur = k.get()
        mark = " (set)" if os.environ.get(k.env) is not None else ""
        lines.append(f"{k.env} = {cur!r}{mark}\n    {k.doc}")
    out = "\n".join(lines)
    print(out)
    return out
