"""Benchmark harness — prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

The analogue of the reference's perf CLIs
(models/utils/DistriOptimizerPerf.scala:32, nn/mkldnn/Perf.scala:125-126).

One process: `python bench.py <leg>` is the only process to touch JAX and
sets no platform itself. The chip legs (resnet50, resnet50_sweep, llama,
lenet, lstm, transformer, kernels) fail when `jax.default_backend()` is not
"tpu" — a CPU number under a chip metric's name is worse than none. The
other legs compare two host-side timings of the same program (dispatch,
queueing, checkpoint stall ...) on whatever the caller selected with
`JAX_PLATFORMS` / `XLA_FLAGS` (they were written for
`JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8`),
and every record says what that was.

Measured: ResNet-50 train step throughput (imgs/sec/chip) in bf16 (headline,
the TPU-native precision policy) and fp32, plus MFU = model FLOPs/step ×
steps/sec ÷ chip peak FLOPs (FLOPs/step from XLA's compiled cost analysis).

vs_baseline: the reference publishes no absolute imgs/sec (BASELINE.json
"published": {}). The ratio uses a documented proxy: ~50 imgs/sec for fp32
ResNet-50 training on the reference's dual-socket Broadwell-class Xeon
(the hardware cited in docs/docs/whitepaper.md:160-164; 2-socket Xeon
ResNet-50 training throughput of that era is ~30-60 imgs/sec).
"""

import functools
import json
import os
import subprocess
import sys
import time

PROXY_BASELINE_IPS = 50.0     # fp32 ResNet-50, 2-socket Xeon proxy (see above)

_METRICS = {
    "resnet50": ("resnet50_imagenet_train_throughput_per_chip",
                 "images/sec"),
    "lenet": ("lenet_mnist_train_throughput", "images/sec"),
    "lstm": ("lstm_ptb_train_throughput", "tokens/sec"),
    "transformer": ("transformer_ptb_train_throughput", "tokens/sec"),
    "kernels": ("pallas_kernel_speedups", "ratio"),
    "resnet50_sweep": ("resnet50_bf16_mfu_best", "mfu"),
    "llama": ("llama_125m_train_throughput", "tokens/sec"),
    "dispatch": ("fused_dispatch_cpu8_speedup", "ratio"),
    "input": ("input_service_data_wait_reduction", "ratio"),
    "checkpoint": ("async_checkpoint_stall_reduction", "ratio"),
    "overhead": ("observability_overhead_pct", "percent"),
    "compile": ("compile_cache_warm_startup_speedup", "ratio"),
    "chaos": ("slice_failover_budget_headroom", "ratio"),
    "serve": ("serve_dynamic_batching_speedup", "ratio"),
    "dcn": ("dcn_t8_int8_speedup_vs_t1", "ratio"),
    "decode": ("decode_iteration_level_tokens_speedup", "ratio"),
    "decode_paged": ("decode_paged_kv_hbm_efficiency", "ratio"),
    "serve_net": ("serve_net_http_front_overhead_ratio", "ratio"),
}

# legs that mean nothing off the chip
_CHIP_LEGS = ("resnet50", "resnet50_sweep", "llama", "lenet", "lstm",
              "transformer", "kernels")

# bf16 peak FLOPs/sec per chip, keyed by substring of device_kind
_PEAK_FLOPS = [
    ("v6", 918e12), ("v5p", 459e12), ("v5", 197e12),
    ("v4", 275e12), ("v3", 123e12), ("v2", 45e12),
]


def _peak_flops(device_kind: str):
    dk = device_kind.lower()
    for key, peak in _PEAK_FLOPS:
        if key in dk:
            return peak
    raise ValueError(
        f"no peak FLOP/s on record for device kind {device_kind!r} — add "
        f"it to _PEAK_FLOPS with its source; MFU is not reported against "
        f"a guess")


# ---------------------------------------------------------------------- legs
def _time_steps(step, carry, warmup, iters, n_runs=1):
    """Timing through utils/sync.py time_steps (data-dependent chains,
    the region ends when the last output is complete). n_runs>1 repeats
    the timed pass (warmup paid once) and returns (best_sec,
    [sec_per_run]) so noise on a loaded host is visible in the artifact
    instead of masquerading as a code regression (ROUND5_NOTES.md: a
    1.1→0.7 imgs/sec scare was host-core count, not code)."""
    from bigdl_tpu.utils.sync import time_steps

    def adapt(c):
        out = step(c)
        return out, out                    # carry IS the observed tree
    secs = []
    for i in range(max(1, n_runs)):
        sec, carry = time_steps(adapt, carry, warmup if i == 0 else 0,
                                iters)
        secs.append(sec)
    return min(secs), secs


def _cache_env(root):
    """Environment for a measured grandchild: the compile cache placed at
    `root` from outside, every program persisted."""
    return dict(os.environ, JAX_COMPILATION_CACHE_DIR=root,
                JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")


def _host_provenance():
    """Enough host context to tell a real perf regression from a noisy
    or smaller machine: core count + load averages at measurement time."""
    try:
        la = os.getloadavg()
    except OSError:
        la = (None, None, None)
    return {"ncpu": os.cpu_count(),
            "loadavg_1m": round(la[0], 2) if la[0] is not None else None,
            "loadavg_5m": round(la[1], 2) if la[1] is not None else None}


def _bench_resnet50(compute_dtype=None, batch_size=None, spatial=None,
                    warmup=None, iters=None, n_runs=1):
    """Returns (imgs_per_sec, flops_per_step, sec_per_step,
    imgs_per_sec_per_run). n_runs>1 repeats the timed pass only where the
    per-run list is actually published (the headline paths)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.core.module import cast_floating
    from bigdl_tpu.models import resnet
    from bigdl_tpu.nn.criterion import ClassNLLCriterion
    from bigdl_tpu.optim.method import SGD

    batch_size = batch_size or 128
    spatial = spatial or 224
    warmup = warmup if warmup is not None else 3
    iters = iters if iters is not None else 20

    model = resnet.build(depth=50, class_num=1000)
    criterion = ClassNLLCriterion()
    method = SGD(0.1, momentum=0.9, weight_decay=1e-4)
    params, state = model.init(jax.random.PRNGKey(0))
    slots = method.init_slots(params)

    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(batch_size, spatial, spatial, 3)
                    .astype(np.float32))
    y = jnp.asarray(r.randint(0, 1000, size=batch_size).astype(np.int32))
    rng = jax.random.PRNGKey(7)

    def step(params, slots, model_state, x, y):
        def loss_fn(p):
            pc = cast_floating(p, compute_dtype) if compute_dtype else p
            xc = x.astype(compute_dtype) if compute_dtype else x
            out, ns = model.apply(pc, model_state, xc, training=True,
                                  rng=rng)
            if compute_dtype:
                out = out.astype(jnp.float32)
            return criterion.forward(out, y), ns
        (loss, ns), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        if compute_dtype:
            grads = cast_floating(grads, jnp.float32)
        new_p, new_s = method.update(params, grads, slots,
                                     jnp.float32(0.1), jnp.int32(0))
        # ns (BN running stats) rides the carry so XLA can't DCE the
        # EMA-update subgraph out of the timed step
        return new_p, new_s, ns, loss

    jitted = jax.jit(step, donate_argnums=(0, 1, 2))
    compiled = jitted.lower(params, slots, state, x, y).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    flops = float((cost or {}).get("flops", 0.0))

    sec, runs = _time_steps(lambda c: compiled(c[0], c[1], c[2], x, y),
                            (params, slots, state, jnp.float32(0.0)),
                            warmup, iters, n_runs=n_runs)
    return (batch_size / sec, flops, sec,
            [round(batch_size / s, 2) for s in runs])


def _bench_lenet(batch_size=512, warmup=3, iters=20):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.models import lenet
    from bigdl_tpu.nn.criterion import ClassNLLCriterion
    from bigdl_tpu.optim.method import SGD

    model = lenet.build(10)
    criterion = ClassNLLCriterion()
    method = SGD(0.01, momentum=0.9)
    params, state = model.init(jax.random.PRNGKey(0))
    slots = method.init_slots(params)

    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(batch_size, 28, 28, 1).astype(np.float32))
    y = jnp.asarray(r.randint(0, 10, size=batch_size).astype(np.int32))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, slots, model_state, x, y):
        def loss_fn(p):
            out, ns = model.apply(p, model_state, x, training=True)
            return criterion.forward(out, y), ns
        (loss, ns), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        new_p, new_s = method.update(params, grads, slots,
                                     jnp.float32(0.01), jnp.int32(0))
        return new_p, new_s, ns, loss

    sec, _ = _time_steps(lambda c: step(c[0], c[1], c[2], x, y),
                         (params, slots, state, jnp.float32(0.0)),
                         warmup, iters)
    return batch_size / sec


def _bench_lm(which="transformer", batch_size=None, seq_len=None,
              warmup=None, iters=None):
    """Tokens/sec for the PTB LM configs (BASELINE: LSTM PTB; the
    transformer is the parity-plus long-context variant)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.models import rnn as rnn_zoo
    from bigdl_tpu.nn.criterion import (ClassNLLCriterion,
                                        CrossEntropyCriterion)
    from bigdl_tpu.optim.method import Adam

    batch_size = batch_size or 32
    seq_len = seq_len or 128
    warmup = warmup or 2
    iters = iters or 10
    vocab = 10000

    if which == "lstm":
        model = rnn_zoo.build_lstm(vocab)
        criterion = ClassNLLCriterion()
    else:
        model = rnn_zoo.build_transformer(vocab)
        criterion = CrossEntropyCriterion()
    method = Adam(1e-3)
    params, state = model.init(jax.random.PRNGKey(0))
    slots = method.init_slots(params)

    r = np.random.RandomState(0)
    x = jnp.asarray(r.randint(0, vocab, (batch_size, seq_len)), jnp.int32)
    y = jnp.asarray(r.randint(0, vocab, (batch_size, seq_len)), jnp.int32)

    def step(params, slots, model_state, x, y):
        def loss_fn(p):
            out, ns = model.apply(p, model_state, x, training=True,
                                  rng=jax.random.PRNGKey(3))
            return criterion.forward(out.reshape(-1, vocab),
                                     y.reshape(-1)), ns
        (loss, ns), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        new_p, new_s = method.update(params, grads, slots, jnp.float32(1e-3),
                                     jnp.int32(0))
        return new_p, new_s, ns, loss

    jitted = jax.jit(step, donate_argnums=(0, 1, 2))
    compiled = jitted.lower(params, slots, state, x, y).compile()
    sec, _ = _time_steps(lambda c: compiled(c[0], c[1], c[2], x, y),
                         (params, slots, state, jnp.float32(0.0)),
                         warmup, iters)
    return batch_size * seq_len / sec


def _bench_kernels():
    """TPU-only: wall-clock each Pallas kernel against its XLA-compiled
    dense equivalent — the 'did the hand kernels earn their keep' table.
    Returns a dict of speedup ratios (>1 = kernel faster)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.utils.sync import chain_dep, time_steps

    r = np.random.RandomState(0)

    def timeit(fn, *args, iters=20, warmup=3):
        # the first arg rides the carry with a data dependency on the
        # previous output, so dispatch i+1 cannot start before dispatch i
        # completes (utils/sync.py chain_dep)
        def adapt(carry):
            out = fn(carry, *args[1:])
            return chain_dep(args[0], out), out
        sec, _ = time_steps(adapt, args[0], warmup, iters)
        return sec

    out = {}
    # flash attention vs dense attention (B=4, H=8, T=2048, d=64)
    from bigdl_tpu.kernels.flash_attention import flash_attention
    from bigdl_tpu.nn.attention import causal_mask, dot_product_attention
    q = jnp.asarray(r.randn(4, 8, 2048, 64).astype(np.float32))
    k = jnp.asarray(r.randn(4, 8, 2048, 64).astype(np.float32))
    v = jnp.asarray(r.randn(4, 8, 2048, 64).astype(np.float32))
    cm = causal_mask(2048, 2048)
    t_flash = timeit(jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True)), q, k, v)
    t_dense = timeit(jax.jit(lambda q, k, v: dot_product_attention(
        q, k, v, cm)), q, k, v)
    out["flash_attention_vs_dense_T2048"] = round(t_dense / t_flash, 3)

    # int8 fused matmul vs bf16 XLA matmul (M=1024, K=4096, N=4096)
    from bigdl_tpu.kernels.quantized_matmul import int8_matmul
    xq = jnp.asarray(r.randint(-127, 128, (1024, 4096)).astype(np.int8))
    wq = jnp.asarray(r.randint(-127, 128, (4096, 4096)).astype(np.int8))
    sx = jnp.asarray((r.rand(1024, 1) + 0.5).astype(np.float32) / 100)
    sw = jnp.asarray((r.rand(1, 4096) + 0.5).astype(np.float32) / 100)
    xb = jnp.asarray(r.randn(1024, 4096), jnp.bfloat16)
    wb = jnp.asarray(r.randn(4096, 4096), jnp.bfloat16)
    t_int8 = timeit(jax.jit(lambda a, b, s1, s2: int8_matmul(
        a, b, s1, s2)), xq, wq, sx, sw)
    t_bf16 = timeit(jax.jit(lambda a, b: (a @ b).astype(jnp.float32)),
                    xb, wb)
    out["int8_matmul_vs_bf16_4096"] = round(t_bf16 / t_int8, 3)

    # cut cross-entropy vs dense log_softmax NLL (N=4096, D=512, V=50257)
    from bigdl_tpu.kernels.cut_cross_entropy import cut_cross_entropy
    h = jnp.asarray(r.randn(4096, 512).astype(np.float32))
    w = jnp.asarray(r.randn(50257, 512).astype(np.float32) * 0.02)
    labels = jnp.asarray(r.randint(0, 50257, 4096), jnp.int32)

    def dense_nll(h, w, labels):
        logp = jax.nn.log_softmax(h @ w.T, axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], 1)[:, 0]
    t_cce = timeit(jax.jit(lambda h, w, l: cut_cross_entropy(h, w, l)),
                   h, w, labels, iters=10)
    t_dxe = timeit(jax.jit(dense_nll), h, w, labels, iters=10)
    out["cut_xent_vs_dense_V50k"] = round(t_dxe / t_cce, 3)
    return out


def _bench_fused_update(batch_size=32, window=48, iters=192, depth=24):
    """Fused optimizer update vs the tree-map path, measured through the
    REAL DistriOptimizer.optimize() loop on the 8-virtual-device CPU
    mesh — the dispatch-bench configuration with the update cost made
    visible: Adam (2 slot trees) on a `depth`-layer MLP (~2*depth param
    leaves), K=8 fused dispatch. The tree-map update pays ~10 elementwise
    ops x n_leaves x K per call; the flat fused kernel pays one
    flattened pass. Throughput per mode is the best post-compile flush
    window (the dispatch-bench convention). Modes: unfused vs fused on
    replicated slots (flat layout) and on ZeRO-1 sharded slots (leaf
    layout). Returns {mode: rec_per_sec}."""
    import numpy as np
    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import ArrayDataSet
    from bigdl_tpu.optim.method import Adam
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.parallel import DistriOptimizer, create_mesh

    class _Windows:
        def __init__(self):
            self.rates = []

        def add_scalar(self, name, v, step):
            if name == "Throughput":
                self.rates.append(v)

    r = np.random.RandomState(0)
    n = batch_size * (iters + window)
    x = r.randn(n, 32).astype(np.float32)
    y = r.randint(0, 2, n).astype(np.int32)
    mesh = create_mesh(drop_trivial_axes=True)
    rows = {}
    for mode, flag, zero1 in (("unfused", "0", False),
                              ("fused", "1", False),
                              ("fused_flat", "flat", False),
                              ("unfused_zero1", "0", True),
                              ("fused_zero1", "1", True)):
        os.environ["BIGDL_TPU_FUSED_UPDATE"] = flag
        try:
            layers = []
            for _ in range(depth):
                layers += [nn.Linear(32, 32), nn.ReLU()]
            model = nn.Sequential(*layers, nn.Linear(32, 2),
                                  nn.LogSoftMax())
            ds = ArrayDataSet(x, y, batch_size, drop_last=True,
                              shuffle=False)
            opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(),
                                  Adam(1e-3), mesh=mesh, seed=0,
                                  steps_per_call=8, zero1=zero1)
            opt._log_every = window
            w = _Windows()
            opt.set_train_summary(w)
            opt.set_end_when(Trigger.max_iteration(iters))
            opt.optimize()
            post = w.rates[window:]       # first window eats compile
            rows[mode] = round(max(post), 1)
        finally:
            os.environ.pop("BIGDL_TPU_FUSED_UPDATE", None)
    return rows


def _bench_autotune_warm(shape_set="smoke"):
    """Cold-search vs warm-table autotune: this process sweeps the named
    shape set (paying the search), then a FRESH subprocess resolves the
    same shapes against the published table — the acceptance bar is a
    100% warm-start hit rate (zero searches) and table-lookup latency in
    the microseconds where the cold path paid a full search."""
    import tempfile
    from bigdl_tpu.kernels import autotune

    root = tempfile.mkdtemp(prefix="bigdl_autotune_bench_")
    autotune.detach()
    autotune._attach(root)
    t0 = time.perf_counter()
    recs = autotune.tune_set(shape_set)
    cold_s = time.perf_counter() - t0
    cold_searches = autotune.process_search_count()
    autotune.sync()

    child = (
        "import os, sys, time, json\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "from bigdl_tpu.kernels import autotune\n"
        "from bigdl_tpu import observe\n"
        "shape_set, root = sys.argv[1], sys.argv[2]\n"
        "autotune._attach(root)\n"
        "t0 = time.perf_counter()\n"
        "for kernel, shape in autotune.SHAPE_SETS[shape_set]:\n"
        "    autotune.tune(kernel, shape)\n"
        "lookup_s = time.perf_counter() - t0\n"
        "snap = observe.registry().snapshot()['counters']\n"
        "print(json.dumps({'searches': autotune.process_search_count(),\n"
        "    'hits': snap.get('autotune/hits', 0),\n"
        "    'misses': snap.get('autotune/misses', 0),\n"
        "    'lookup_s': lookup_s}))\n")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", child, shape_set, root],
                       env=env, capture_output=True, text=True,
                       timeout=450)
    warm = {}
    if r.returncode == 0:
        line = next((ln for ln in reversed(r.stdout.splitlines())
                     if ln.startswith("{")), "{}")
        warm = json.loads(line)
    else:                                # report, don't hide
        warm = {"error": (r.stderr or "")[-300:]}
    import shutil as _sh
    _sh.rmtree(root, ignore_errors=True)
    n_shapes = len(autotune.SHAPE_SETS[shape_set])
    hits = warm.get("hits", 0)
    return {
        "shape_set": shape_set,
        "shapes": n_shapes,
        "cold_searches": cold_searches,
        "cold_search_s": round(cold_s, 3),
        "warm_searches": warm.get("searches"),
        "warm_hits": hits,
        "warm_misses": warm.get("misses"),
        "warm_lookup_s": round(warm["lookup_s"], 4)
        if "lookup_s" in warm else None,
        "warm_hit_rate": round(hits / n_shapes, 3) if n_shapes else None,
        "configs": {rec["kernel"]: rec["config"] for rec in recs},
        **({"warm_error": warm["error"]} if "error" in warm else {}),
    }


def _bench_llama(batch_size=None, seq_len=None, warmup=None, iters=None):
    """Tokens/sec + MFU for a ~125M LLaMA-architecture train step in
    bf16 — the modern-decoder headline (GQA + RoPE + SwiGLU + flash-size
    attention; model from interop.huggingface.LlamaLM)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.core.module import cast_floating
    from bigdl_tpu.interop.huggingface import LlamaLM
    from bigdl_tpu.optim.method import Adam

    batch_size = batch_size or 8
    seq_len = seq_len or 1024
    warmup = warmup or 2
    iters = iters or 10
    vocab, d, H, KV, L = 32000, 768, 12, 4, 12

    model = LlamaLM(vocab, d, H, KV, 4 * d, L, tied=True)
    method = Adam(3e-4)
    params, state = model.init(jax.random.PRNGKey(0))
    slots = method.init_slots(params)
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randint(0, vocab, (batch_size, seq_len)), jnp.int32)
    y = jnp.asarray(r.randint(0, vocab, (batch_size, seq_len)), jnp.int32)

    def step(params, slots, x, y):
        def loss_fn(p):
            pc = cast_floating(p, jnp.bfloat16)
            out, _ = model.apply(pc, state, x)
            lp = jax.nn.log_softmax(out.astype(jnp.float32))
            return -jnp.take_along_axis(lp, y[..., None], -1).mean()
        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = cast_floating(grads, jnp.float32)
        new_p, new_s = method.update(params, grads, slots,
                                     jnp.float32(3e-4), jnp.int32(0))
        return new_p, new_s, loss

    jitted = jax.jit(step, donate_argnums=(0, 1))
    compiled = jitted.lower(params, slots, x, y).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    flops = float((cost or {}).get("flops", 0.0))
    sec, _ = _time_steps(lambda c: compiled(c[0], c[1], x, y),
                         (params, slots, jnp.float32(0.0)), warmup, iters)
    return batch_size * seq_len / sec, flops, sec


def _bench_dispatch(batch_size=32, window=64, iters=256):
    """Fused-dispatch amortization microbench: a small MLP trained through
    the REAL DistriOptimizer.optimize() loop on an 8-virtual-device CPU
    mesh (the PERF_r05 scaling-efficiency configuration), sweeping
    steps_per_call K ∈ {1,2,4,8}. Per-K throughput is the BEST
    post-compile flush window of the trainer's own throughput meter — the
    best-sample convention _time_steps already uses (min over runs), since
    single-window samples on a 1-core host swing ±30% with scheduler
    noise. The number measures exactly what the fused path amortizes:
    per-step Python dispatch + placement plumbing. Returns
    {k: rec_per_sec}."""
    import numpy as np
    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import ArrayDataSet
    from bigdl_tpu.optim.method import SGD
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.parallel import DistriOptimizer, create_mesh

    class _Windows:                       # summary stub: collect rates only
        def __init__(self):
            self.rates = []

        def add_scalar(self, name, v, step):
            if name == "Throughput":
                self.rates.append(v)

    r = np.random.RandomState(0)
    n = batch_size * (iters + window)     # one epoch covers the whole run
    x = r.randn(n, 16).astype(np.float32)
    y = r.randint(0, 2, n).astype(np.int32)
    mesh = create_mesh(drop_trivial_axes=True)
    rows = {}
    for k in (1, 2, 4, 8, 16):
        # the smallest honest train step: per-step device time on the
        # 8-way-emulated 1-core mesh is ~#HLO-ops-bound, and it is the
        # floor the amortization win is measured against
        model = nn.Sequential(nn.Linear(16, 2), nn.LogSoftMax())
        ds = ArrayDataSet(x, y, batch_size, drop_last=True, shuffle=False)
        opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(), SGD(0.1),
                              mesh=mesh, seed=0, steps_per_call=k)
        opt._log_every = window
        w = _Windows()
        opt.set_train_summary(w)
        opt.set_end_when(Trigger.max_iteration(iters))
        opt.optimize()
        post = w.rates[window:]           # first window eats compile
        rows[k] = round(max(post), 1)
    return rows


def _bench_input(batch_size=32, k=8, warm_iters=16, iters=256,
                 workers_on=8):
    """Input-service bench: data-wait span fraction with the streaming
    input service ON vs OFF, at the dispatch bench's K=8 record rate on
    the 8-virtual-device CPU mesh. The workload is record-shard
    ingestion (ShardedRecordDataset over synthetic raw records) whose
    per-record decode carries a calibrated sleep emulating remote-
    storage fetch latency — the IO-bound regime the service exists for,
    and the only host-pipeline cost a 1-core host can honestly overlap
    (CPU-bound decode overlap needs real cores next to a real chip;
    the sleep releases the GIL exactly like a storage read does).

    Calibration: an unthrottled service-on pass measures the device-side
    demand R rec/s; the throttle is then set so ONE decode worker feeds
    R/4 (the service-off path starves 4x) while `workers_on` workers
    feed 2R (the service keeps the chip fed). The echoing run throttles
    4x harder — even the full worker pool starves — and compares
    DATA_ECHO=1 vs 2 trained-records/sec (Choi et al.: each fetched
    batch trains twice, halving the IO demand per trained record).

    Per mode: a warmup pass eats every compile, then the metrics
    registry is reset and a fresh measured pass (same trainer — the
    built-step cache keeps it at zero fresh compiles) yields the
    data-wait fraction (observe.metrics.data_wait_fraction — data_wait /
    step-loop time) and the trainer's own throughput meter."""
    import shutil
    import tempfile
    import numpy as np
    import bigdl_tpu.nn as nn
    from bigdl_tpu import observe
    from bigdl_tpu.dataset.sharded import (ShardedRecordDataset,
                                           generate_synthetic)
    from bigdl_tpu.observe.metrics import data_wait_fraction
    from bigdl_tpu.optim.method import SGD
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.parallel import DistriOptimizer, create_mesh

    class _Windows:
        def __init__(self):
            self.rates = []

        def add_scalar(self, name, v, step):
            if name == "Throughput":
                self.rates.append(v)

    mesh = create_mesh(drop_trivial_axes=True)
    shard_dir = tempfile.mkdtemp(prefix="bigdl_input_bench_")
    # one long epoch covers warmup + measured pass per mode: epoch
    # turnover re-primes the pipeline, and that fill must amortize, not
    # dominate, the measured data-wait
    generate_synthetic(shard_dir, batch_size * 512, num_shards=8,
                       height=16, width=16, classes=2)
    feat = 16 * 16 * 3

    def make_transform(sleep_s):
        def fn(img, label):
            if sleep_s:
                time.sleep(sleep_s)
            return (img.astype(np.float32).reshape(feat) / 255.0 - 0.5,
                    np.int32(label % 2))
        return fn

    _KNOBS = ("BIGDL_TPU_DATA_SERVICE", "BIGDL_TPU_DATA_WORKERS",
              "BIGDL_TPU_DATA_ECHO", "BIGDL_TPU_PREFETCH_SIZE")

    def run(env, sleep_s, workers):
        saved = {kk: os.environ.get(kk) for kk in _KNOBS}
        os.environ.update(env)
        try:
            ds = ShardedRecordDataset(
                shard_dir, batch_size, transform=make_transform(sleep_s),
                shuffle=False, num_workers=workers)
            # enough device compute per step that the feed, not python
            # dispatch, is the contended resource (the dispatch bench
            # already covers the tiny-step regime)
            model = nn.Sequential(nn.Linear(feat, 128), nn.ReLU(),
                                  nn.Linear(128, 2), nn.LogSoftMax())
            opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(),
                                  SGD(0.1), mesh=mesh, seed=0,
                                  steps_per_call=k)
            w = _Windows()
            opt.set_train_summary(w)
            opt._log_every = iters // 4
            # warmup pass pays every compile; the measured pass below
            # reuses the built programs (retrace-hygiene contract)
            opt.set_end_when(Trigger.max_iteration(warm_iters))
            opt.optimize()
            observe.registry().reset()
            w.rates.clear()
            opt.set_end_when(Trigger.max_iteration(warm_iters + iters))
            t0 = time.time()
            opt.optimize()
            wall = time.time() - t0
            dw = data_wait_fraction(observe.registry().snapshot())
            return {
                "data_wait_frac": round(dw["fraction"], 4) if dw else None,
                "data_wait_s": round(dw["data_wait_s"], 3) if dw else None,
                "step_loop_s": round(dw["step_loop_s"], 3) if dw else None,
                "rec_per_sec": round(max(w.rates), 1) if w.rates
                else round(iters * batch_size / max(wall, 1e-9), 1),
                "wall_s": round(wall, 2),
            }
        finally:
            for kk, v in saved.items():
                if v is None:
                    os.environ.pop(kk, None)
                else:
                    os.environ[kk] = v

    try:
        # calibrate the device-side demand with no throttle, service on
        cal = run({"BIGDL_TPU_DATA_SERVICE": "1",
                   "BIGDL_TPU_DATA_WORKERS": str(workers_on)}, 0.0,
                  workers_on)
        rate = max(cal["rec_per_sec"], 1.0)
        # one worker feeds rate/4; `workers_on` workers feed 2x rate
        sleep_s = (workers_on / 2.0) / rate
        off = run({"BIGDL_TPU_DATA_SERVICE": "0"}, sleep_s, 1)
        on = run({"BIGDL_TPU_DATA_SERVICE": "1",
                  "BIGDL_TPU_DATA_WORKERS": str(workers_on)}, sleep_s,
                 workers_on)
        # IO-throttled regime: even the pool starves — echoing's win
        heavy = 4.0 * sleep_s
        e1 = run({"BIGDL_TPU_DATA_SERVICE": "1",
                  "BIGDL_TPU_DATA_WORKERS": str(workers_on)}, heavy,
                 workers_on)
        e2 = run({"BIGDL_TPU_DATA_SERVICE": "1",
                  "BIGDL_TPU_DATA_WORKERS": str(workers_on),
                  "BIGDL_TPU_DATA_ECHO": "2"}, heavy, workers_on)
        off_frac = off["data_wait_frac"] or 1e-9
        on_frac = on["data_wait_frac"] or 1e-9
        return {
            "calibration_rec_per_sec": rate,
            "throttle_ms_per_record": round(sleep_s * 1e3, 3),
            "off": off, "on": on,
            "data_wait_frac_ratio": round(off_frac / on_frac, 2),
            "on_frac_of_off": round(on_frac / off_frac, 4),
            "throttled": {
                "throttle_ms_per_record": round(heavy * 1e3, 3),
                "echo1": e1, "echo2": e2,
                "echo_speedup": round(
                    e2["rec_per_sec"] / max(e1["rec_per_sec"], 1e-9), 2),
            },
        }
    finally:
        shutil.rmtree(shard_dir, ignore_errors=True)


def _bench_checkpoint(batch_size=32, hidden=1024, iters=24, every=4):
    """Checkpoint-induced step-time stall: the blocking time the train
    loop pays per snapshot, sync v1 (gather-to-host-0 npz) vs async v2
    (device-side clone + background shard write — resilience/). Same
    model (~1M params, ~13 MB snapshot with Adam slots), same
    DistriOptimizer.optimize() loop on the 8-virtual-device CPU mesh,
    same snapshot cadence — only the writer differs. Stall samples come
    from the trainer's own `_ckpt_stalls` meter (optim/local.py); the
    first sample per mode eats the writer's jit/compile warmup and is
    dropped. Total optimize() wall time rides along (on a 1-core host
    the background serialization still competes for the CPU — the stall
    number is what the STEP BOUNDARY pays, the wall number keeps the
    total cost honest)."""
    import shutil
    import tempfile
    import numpy as np
    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import ArrayDataSet
    from bigdl_tpu.optim.method import Adam
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.parallel import DistriOptimizer, create_mesh

    r = np.random.RandomState(0)
    n = batch_size * (iters + 2)
    x = r.randn(n, 16).astype(np.float32)
    y = r.randint(0, 2, n).astype(np.int32)
    mesh = create_mesh(drop_trivial_axes=True)
    modes = {"sync_v1": {"BIGDL_TPU_CHECKPOINT_FORMAT": "1"},
             "sync_v2": {"BIGDL_TPU_CHECKPOINT_ASYNC": "0"},
             "async_v2": {}}
    rows = {}
    for mode, env in modes.items():
        saved = {k: os.environ.get(k) for k in
                 ("BIGDL_TPU_CHECKPOINT_FORMAT",
                  "BIGDL_TPU_CHECKPOINT_ASYNC")}
        os.environ.update(env)
        ckdir = tempfile.mkdtemp(prefix=f"bigdl_ckpt_bench_{mode}_")
        try:
            model = nn.Sequential(nn.Linear(16, hidden), nn.ReLU(),
                                  nn.Linear(hidden, hidden), nn.ReLU(),
                                  nn.Linear(hidden, 2), nn.LogSoftMax())
            ds = ArrayDataSet(x, y, batch_size, drop_last=True,
                              shuffle=False)
            opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(),
                                  Adam(1e-3), mesh=mesh, seed=0)
            opt.set_checkpoint(ckdir, Trigger.several_iteration(every))
            opt.set_end_when(Trigger.max_iteration(iters))
            t0 = time.time()
            opt.optimize()
            wall = time.time() - t0
            stalls = list(opt._ckpt_stalls)[1:]   # [0] eats writer warmup
            rows[mode] = {
                "stall_ms_median": round(
                    1e3 * float(np.median(stalls)), 2),
                "stall_ms_mean": round(1e3 * float(np.mean(stalls)), 2),
                "n_saves": len(opt._ckpt_stalls),
                "wall_s": round(wall, 2),
            }
            snaps = [s for s in os.listdir(ckdir)
                     if s.startswith("snapshot-")]
            snap = os.path.join(ckdir, sorted(snaps)[0])
            rows[mode]["snapshot_bytes"] = sum(
                os.path.getsize(os.path.join(snap, f))
                for f in os.listdir(snap))
        finally:
            shutil.rmtree(ckdir, ignore_errors=True)
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
    return rows


def _bench_overhead(batch_size=32, window=128, iters=1280, k=8):
    """Flight-recorder overhead microbench: the SAME small-model
    DistriOptimizer.optimize() loop as `dispatch` (8-virtual-device CPU
    mesh, steps_per_call=k — the hottest dispatch path in the tree),
    run with observability fully off vs fully on. Since the live
    telemetry plane (ISSUE 10), "on" means EVERYTHING: span tracing to
    a tmpdir + JSONL + Prometheus exporters on a 1s flush + the statusz
    HTTP server with a background client scraping /statusz + /metrics
    ~5x/s under load + the step-time watchdog armed. Modes alternate
    off/on/off/on and each takes its BEST post-compile flush window
    (the dispatch-bench convention — single windows on a 1-core host
    swing with scheduler noise). Headline = percent throughput lost
    with everything enabled; the ≤2% acceptance bar for the observe/
    subsystem. Scrapes read host-side registry state only — the
    no-added-host-sync contract is asserted separately by
    tests/test_observe.py + tests/test_statusz.py."""
    import shutil
    import socket
    import tempfile
    import threading
    import urllib.request
    import numpy as np
    import bigdl_tpu.nn as nn
    from bigdl_tpu import observe
    from bigdl_tpu.dataset import ArrayDataSet
    from bigdl_tpu.optim.method import SGD
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.parallel import DistriOptimizer, create_mesh

    class _Windows:                       # summary stub: collect rates only
        def __init__(self):
            self.rates = []

        def add_scalar(self, name, v, step):
            if name == "Throughput":
                self.rates.append(v)

    r = np.random.RandomState(0)
    n = batch_size * (iters + window)
    x = r.randn(n, 16).astype(np.float32)
    y = r.randint(0, 2, n).astype(np.int32)
    mesh = create_mesh(drop_trivial_axes=True)
    _KNOBS = ("BIGDL_TPU_TRACE", "BIGDL_TPU_METRICS_JSONL",
              "BIGDL_TPU_METRICS_PROM", "BIGDL_TPU_METRICS_FLUSH_S",
              "BIGDL_TPU_STATUSZ_PORT", "BIGDL_TPU_WATCHDOG_PCT",
              "BIGDL_TPU_FLEET_PEERS", "BIGDL_TPU_FLEET_POLL_S",
              "BIGDL_TPU_SERVE_WATCHDOG_PCT",
              "BIGDL_TPU_MEM_WATCHDOG_PCT", "BIGDL_TPU_MEM_LIMIT_BYTES",
              "BIGDL_TPU_MEM_LEDGER")
    scrape_counts = []

    def run_once(instrumented):
        from bigdl_tpu.observe import doctor as obs_doctor
        saved = {kk: os.environ.get(kk) for kk in _KNOBS}
        tmp = tempfile.mkdtemp(prefix="bigdl_obs_bench_")
        for kk in _KNOBS:
            os.environ.pop(kk, None)
        port = None
        peer_srv = None
        if instrumented:
            os.environ["BIGDL_TPU_TRACE"] = os.path.join(tmp, "trace")
            os.environ["BIGDL_TPU_METRICS_JSONL"] = \
                os.path.join(tmp, "run.jsonl")
            os.environ["BIGDL_TPU_METRICS_PROM"] = \
                os.path.join(tmp, "metrics.prom")
            os.environ["BIGDL_TPU_METRICS_FLUSH_S"] = "1.0"
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
            s.close()
            os.environ["BIGDL_TPU_STATUSZ_PORT"] = str(port)
            os.environ["BIGDL_TPU_WATCHDOG_PCT"] = "50"
            # FULL fleet plane (ISSUE 12): a second in-process statusz
            # peer + the aggregator polling both every flush + the
            # serve-SLO watchdog's background poller live
            from bigdl_tpu.observe.statusz import StatuszServer
            peer_srv = StatuszServer(0)
            os.environ["BIGDL_TPU_FLEET_PEERS"] = \
                f"127.0.0.1:{port},127.0.0.1:{peer_srv.port}"
            os.environ["BIGDL_TPU_FLEET_POLL_S"] = "1.0"
            os.environ["BIGDL_TPU_SERVE_WATCHDOG_PCT"] = "50"
            obs_doctor.arm_serve_watchdog()
            # memory plane fully armed (ISSUE 15): the buffer ledger is
            # on by default; a capacity limit arms the memory-watchdog
            # poller (1 GiB >> this loop's footprint, so it never
            # fires), and /memz joins the scrape mix below
            os.environ["BIGDL_TPU_MEM_WATCHDOG_PCT"] = "85"
            os.environ["BIGDL_TPU_MEM_LIMIT_BYTES"] = str(1 << 30)
        else:
            os.environ["BIGDL_TPU_WATCHDOG_PCT"] = "0"
            # the OFF mode disables the buffer ledger too, so the
            # headline covers the WHOLE memory plane's cost (register
            # calls become no-op handles)
            os.environ["BIGDL_TPU_MEM_LEDGER"] = "0"
        obs_doctor.reset_watchdog()       # re-read the knob per mode
        from bigdl_tpu.observe import memz as _memz_mod
        _memz_mod.reset()                 # fresh ledger + watchdog per mode
        if instrumented:
            assert _memz_mod.arm_memory_watchdog()
        stop_scraper = threading.Event()

        def scraper():
            # a live Prometheus scraper + an operator polling /statusz,
            # the merged /fleetz AND the /memz memory plane: same
            # ~10 req/s total as the r14 methodology, round-robined so
            # every endpoint is exercised under load
            count = 0
            eps = ("/statusz", "/metrics", "/fleetz", "/memz")
            i = 0
            while not stop_scraper.wait(0.2):
                for ep in (eps[i % 4], eps[(i + 1) % 4]):
                    try:
                        with urllib.request.urlopen(
                                f"http://127.0.0.1:{port}{ep}",
                                timeout=5) as resp:
                            resp.read()
                        count += 1
                    except Exception:      # noqa: BLE001 — server not up yet
                        pass
                i += 1
            scrape_counts.append(count)

        scraper_thread = None
        try:
            model = nn.Sequential(nn.Linear(16, 2), nn.LogSoftMax())
            ds = ArrayDataSet(x, y, batch_size, drop_last=True,
                              shuffle=False)
            opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(),
                                  SGD(0.1), mesh=mesh, seed=0,
                                  steps_per_call=k)
            opt._log_every = window
            w = _Windows()
            opt.set_train_summary(w)
            opt.set_end_when(Trigger.max_iteration(iters))
            if instrumented:
                scraper_thread = threading.Thread(target=scraper,
                                                  daemon=True)
                scraper_thread.start()
            opt.optimize()
            post = w.rates[window:]       # first window eats compile
            return max(post)
        finally:
            stop_scraper.set()
            if scraper_thread is not None:
                scraper_thread.join(timeout=10)
            # tear the global recorder down so the next (off) pass runs
            # genuinely uninstrumented (shutdown also joins the fleet
            # poller + serve-SLO watchdog)
            observe.shutdown()
            if peer_srv is not None:
                peer_srv.close()
            shutil.rmtree(tmp, ignore_errors=True)
            for kk, v in saved.items():
                if v is None:
                    os.environ.pop(kk, None)
                else:
                    os.environ[kk] = v

    rows = {"off": [], "on": []}
    for _ in range(3):                    # alternate to decorrelate noise
        rows["off"].append(run_once(False))
        rows["on"].append(run_once(True))
    best_off, best_on = max(rows["off"]), max(rows["on"])
    return {
        "off_rec_per_sec": round(best_off, 1),
        "on_rec_per_sec": round(best_on, 1),
        "off_runs": [round(v, 1) for v in rows["off"]],
        "on_runs": [round(v, 1) for v in rows["on"]],
        "statusz_scrapes": scrape_counts,
        "overhead_pct": round(100.0 * (1.0 - best_on / best_off), 2),
    }


# the compile bench's measured trainer run: executed in FRESH grandchild
# processes (cold vs warm must not share jax's in-memory caches; only the
# persistent cache directory is shared). An 18-layer narrow MLP: XLA
# optimization work (what the cache elides) dominates trace/lower work
# (what a warm start still pays), so the cold/warm gap isolates the
# cache's win. K=4 + accum=2 + ZeRO-1 + validation compiles the full
# program menu; 5-batch epochs end in a tail, so the single-variant
# bucketing claim covers tail epochs.
_COMPILE_CHILD = r'''
import json, os, sys, time
import numpy as np
import bigdl_tpu.nn as nn
from bigdl_tpu import compilecache, observe
from bigdl_tpu.dataset import ArrayDataSet
from bigdl_tpu.optim.method import SGD
from bigdl_tpu.optim.metrics import Top1Accuracy
from bigdl_tpu.optim.trigger import Trigger
from bigdl_tpu.parallel import DistriOptimizer, create_mesh

observe.ensure_started()
root = compilecache.enable()         # the parent's JAX_COMPILATION_CACHE_DIR
r = np.random.RandomState(0)
x = r.randn(80, 64).astype(np.float32)
y = r.randint(0, 2, 80).astype(np.int32)
mesh = create_mesh(drop_trivial_axes=True)
layers = [nn.Linear(64, 64), nn.ReLU()]
for _ in range(24):
    layers += [nn.Linear(64, 64), nn.ReLU()]
layers += [nn.Linear(64, 2), nn.LogSoftMax()]
model = nn.Sequential(*layers)
ds = ArrayDataSet(x, y, 16, drop_last=True, shuffle=False)  # 5 batches: 4+1 tail
opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(), SGD(0.1),
                      mesh=mesh, zero1=True, seed=0, steps_per_call=4,
                      accum_steps=2)
opt.set_validation(Trigger.several_iteration(5),
                   ArrayDataSet(x, y, 16, shuffle=False), [Top1Accuracy()])
opt._log_every = 1
first = []


class S:
    def add_scalar(self, name, v, step):
        if name == "Loss" and not first:
            first.append(time.perf_counter())


opt.set_train_summary(S())
opt.set_end_when(Trigger.max_iteration(10))
t0 = time.perf_counter()
opt.optimize()
wall = time.perf_counter() - t0
s = compilecache.stats(root)
print(json.dumps({
    "startup_s": round(first[0] - t0, 3), "wall_s": round(wall, 3),
    "compiles": observe.counter("jit/compiles").value,
    "cache_hit_compiles": observe.counter("jit/cache_hit_compiles").value,
    "fused_variants": s["programs"].get("jit_bigdl_fused_train_step", 0),
    "eval_variants": s["programs"].get("jit_bigdl_eval_step", 0),
}))
'''


def _bench_compile():
    """Compile-latency bench (docs/compile_cache.md): the SAME
    DistriOptimizer.optimize() run twice in fresh processes sharing one
    persistent-cache root — cold (empty cache: every program XLA-
    compiles) vs warm (every program deserializes). `startup_s` is
    optimize()-entry to the first flushed loss: trace + compile/retrieve
    + first fused stride. The warm floor is trace/lower time, which the
    cache cannot elide. `fused_variants` counts distinct compiled
    train-step programs in the cache — the single-variant bucketing
    acceptance (epochs here END in a padded tail)."""
    import shutil
    import tempfile

    def run_pair():
        root = tempfile.mkdtemp(prefix="bigdl_cc_bench_")
        try:
            runs = {}
            for mode in ("cold", "warm"):
                r = subprocess.run(
                    [sys.executable, "-c", _COMPILE_CHILD],
                    capture_output=True, text=True, timeout=480,
                    env=_cache_env(root))
                line = next((ln for ln in reversed(r.stdout.splitlines())
                             if ln.startswith("{")), None)
                if r.returncode != 0 or line is None:
                    raise RuntimeError(f"compile bench {mode} run "
                                       f"failed: {r.stderr[-800:]}")
                runs[mode] = json.loads(line)
            return runs
        finally:
            shutil.rmtree(root, ignore_errors=True)

    # two independent cold/warm pairs, best taken per side — single runs
    # on the 1-core host swing with scheduler noise (the dispatch bench's
    # best-window convention)
    pairs = [run_pair() for _ in range(2)]
    cold = min(p["cold"]["startup_s"] for p in pairs)
    warm = min(p["warm"]["startup_s"] for p in pairs)
    c0, w0 = pairs[0]["cold"], pairs[0]["warm"]
    return {
        "cold_s": cold,
        "warm_s": warm,
        "speedup": round(cold / warm, 2),
        "cold_runs": [p["cold"]["startup_s"] for p in pairs],
        "warm_runs": [p["warm"]["startup_s"] for p in pairs],
        "cold_wall_s": c0["wall_s"],
        "warm_wall_s": w0["wall_s"],
        "programs_compiled": int(c0["compiles"]),
        "warm_cache_hit_compiles": int(w0["cache_hit_compiles"]),
        "fused_train_step_variants": int(w0["fused_variants"]),
        "eval_step_variants": int(w0["eval_variants"]),
    }


# the serve bench's warm-start probe: executed in FRESH grandchild
# processes sharing one persistent-cache root (in-memory jax caches must
# not leak between cold and warm). Registers a model with the bucket-set
# AOT precompile and serves one request per bucket; `fresh` counts XLA
# compiles that were NOT persistent-cache deserializations — the warm
# run's acceptance is fresh == 0 (every bucket an AOT cache hit).
_SERVE_CHILD = r'''
import json, sys
import numpy as np
import jax
import bigdl_tpu.nn as nn
from bigdl_tpu import compilecache, observe
from bigdl_tpu.parallel import create_mesh
from bigdl_tpu.serve import ServeEngine

observe.ensure_started()
compilecache.enable()                # the parent's JAX_COMPILATION_CACHE_DIR
mesh = create_mesh(drop_trivial_axes=True)
model = nn.Sequential(nn.Linear(16, 64), nn.Tanh(), nn.Linear(64, 8))
params, state = model.init(jax.random.PRNGKey(0))
r = np.random.RandomState(0)
c0 = observe.counter("jit/compiles").value
h0 = observe.counter("jit/cache_hit_compiles").value
eng = ServeEngine()
entry = eng.register("m", model, params, state, mesh=mesh, max_batch=64,
                     precompile_input=((16,), "float32"))
compiled = observe.counter("jit/compiles").value - c0
served_c0 = observe.counter("jit/compiles").value
for b in entry.buckets:
    eng.predict("m", r.randn(max(1, b - 1), 16).astype(np.float32),
                timeout=60)
eng.shutdown()
c1 = observe.counter("jit/compiles").value
h1 = observe.counter("jit/cache_hit_compiles").value
print(json.dumps({
    "buckets": list(entry.buckets),
    "precompile_compiles": compiled,
    "serving_compiles": c1 - served_c0,
    "compiles": c1 - c0,
    "cache_hit_compiles": h1 - h0,
    "fresh_compiles": (c1 - c0) - (h1 - h0),
}))
'''


def _bench_serve(n_requests=600, feat=16, max_batch=64, queue_rows=256):
    """Online-serving bench (ISSUE 8 acceptance): Poisson OPEN-LOOP load
    against the ServeEngine on the 8-virtual-device CPU mesh — arrival
    times are fixed up front (closed-form from one seeded exponential
    stream), so a slow server cannot throttle its own offered load.

    Modes share the model, the mesh, the request trace, and the offered
    rate (calibrated to ~3x the measured batch-size-1 service rate, i.e.
    the baseline is saturated):

      * batch1  — coalescing off: every request dispatches alone
                  (the pre-continuous-batching behavior);
      * dynamic — continuous batching, 2 ms max-wait deadline.

    Both run with the same bounded queue + Overloaded shedding, so the
    saturated baseline sheds instead of queueing unboundedly; throughput
    counts COMPLETED requests over the wall clock and p50/p99 come from
    the per-model serve latency histograms. Acceptance: dynamic >= 2x
    batch1 requests/sec at equal-or-better p99."""
    import numpy as np
    import jax
    import bigdl_tpu.nn as nn
    from bigdl_tpu.parallel import create_mesh
    from bigdl_tpu.serve import Overloaded, ServeEngine

    mesh = create_mesh(drop_trivial_axes=True)
    model = nn.Sequential(nn.Linear(feat, 64), nn.Tanh(),
                          nn.Linear(64, 8))
    params, state = model.init(jax.random.PRNGKey(0))  # tpu-lint: disable=004
    r = np.random.RandomState(0)
    sizes = r.randint(1, 9, n_requests)
    reqs = [r.randn(int(n), feat).astype(np.float32) for n in sizes]

    # calibrate the batch-1 service rate: serial single-request dispatch
    # through the real entry (padded smallest bucket, warm program)
    cal = ServeEngine()
    entry = cal.register("cal", model, params, state, mesh=mesh,
                         max_batch=max_batch)
    entry.precompile_for((feat,), "float32")
    lo = entry.buckets[0]
    probe = np.zeros((lo, feat), np.float32)
    for _ in range(5):                      # warmup
        entry.dispatch(probe, 1)
    t0 = time.perf_counter()
    n_cal = 40
    for _ in range(n_cal):
        entry.dispatch(probe, 1)
    base_rate = n_cal / (time.perf_counter() - t0)
    cal.shutdown()
    offered = 3.0 * base_rate
    arrivals = np.cumsum(
        np.random.RandomState(1).exponential(1.0 / offered, n_requests))

    def run_mode(tag, coalesce):
        eng = ServeEngine()
        e = eng.register(tag, model, params, state, mesh=mesh,
                         max_batch=max_batch,
                         max_wait_ms=2.0 if coalesce else 0.0,
                         max_queue_rows=queue_rows, coalesce=coalesce)
        e.precompile_for((feat,), "float32")
        replies, shed = [], 0
        t0 = time.perf_counter()
        for i, q in enumerate(reqs):
            now = time.perf_counter() - t0
            if arrivals[i] > now:
                time.sleep(arrivals[i] - now)
            try:
                rep = eng.submit(tag, q)
            except Overloaded:
                shed += 1
                continue
            replies.append(rep)
        for rep in replies:
            rep.result(timeout=300)
        wall = time.perf_counter() - t0
        st = eng.stats()
        eng.shutdown()
        return {
            "completed": len(replies),
            "shed": shed,
            "wall_s": round(wall, 3),
            "req_per_sec": round(len(replies) / wall, 1),
            "p50_ms": st[tag]["p50_ms"],
            "p99_ms": st[tag]["p99_ms"],
        }

    rows = {"batch1": run_mode("batch1", False),
            "dynamic": run_mode("dynamic", True)}
    rows["base_rate_req_per_sec"] = round(base_rate, 1)
    rows["offered_req_per_sec"] = round(offered, 1)
    rows["speedup"] = round(rows["dynamic"]["req_per_sec"]
                            / max(rows["batch1"]["req_per_sec"], 1e-9), 2)
    rows["p99_ok"] = bool(rows["dynamic"]["p99_ms"]
                          <= rows["batch1"]["p99_ms"])

    # warm-start probe: cold/warm grandchildren sharing one cache root
    import shutil
    import tempfile
    root = tempfile.mkdtemp(prefix="bigdl_serve_bench_")
    try:
        for mode in ("cold", "warm"):
            res = subprocess.run(
                [sys.executable, "-c", _SERVE_CHILD],
                capture_output=True, text=True, timeout=300,
                env=_cache_env(root))
            line = next((ln for ln in reversed(res.stdout.splitlines())
                         if ln.startswith("{")), None)
            if res.returncode != 0 or line is None:
                rows[f"{mode}_start"] = {
                    "error": (res.stderr or res.stdout)[-300:]}
            else:
                rows[f"{mode}_start"] = json.loads(line)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return rows


def _bench_decode(n_requests=36, slots_legs=(1, 4, 8)):
    """Iteration-level decode bench (ISSUE 14 acceptance): open-loop
    Poisson arrivals of mixed-length generate requests against three
    serving strategies sharing the model, params, request trace and
    offered rate:

      * baseline — the whole-request strategy PR 8's batcher implies
        for generates: each request is ONE unit processed to
        completion (mixed (P, new) combos have distinct signatures, so
        the stateless batcher cannot co-batch them), decoded by the
        recompute-prefix `generate(kv_cache=False, beam_size=1)` — the
        prefix is recomputed every token, tokens arrive only at
        completion (TTFT = completion latency), and a long sequence
        head-of-line blocks everything behind it;
      * slots1/4/8 — the iteration-level DecodeEngine with S KV slots:
        chunked prefill into slot caches, one fused greedy step per
        iteration, join/retire every step.

    The offered rate is calibrated to ~12x the baseline's serial
    service rate, saturating every leg: tokens/s measures each leg's
    CAPACITY (the slot-scaling curve), and the baseline's queue shows
    the head-of-line cost as a runaway TTFT.
    Every leg runs warm (baseline programs pre-jitted per combo;
    engine legs AOT-precompiled). Acceptance: slots8 aggregate decode
    tokens/s >= 3x baseline at equal-or-better p99 TTFT."""
    import numpy as np
    import jax
    from bigdl_tpu.parallel import create_mesh
    from bigdl_tpu.serve import ServeEngine
    from bigdl_tpu.serve.decode import decode_demo_model

    mesh = create_mesh(drop_trivial_axes=True)
    # the regime iteration-level decode targets: prefixes long enough
    # that recomputing them every token (the whole-request strategy)
    # actually costs — with toy 8-token prompts the fully-jitted
    # recompute scan wins on pure dispatch overhead and the comparison
    # says nothing about the architecture
    VOCAB, EOS, L = 256, 255, 160
    model, params, state = decode_demo_model(
        vocab_size=VOCAB, n_positions=256, d_model=128, num_heads=4,
        num_layers=3, eos_id=EOS)
    combos = [(32, 32), (64, 32), (64, 64), (96, 64)]
    r = np.random.RandomState(0)
    picks = r.randint(0, len(combos), n_requests)
    reqs = [(r.randint(2, VOCAB - 1, combos[i][0]).astype(np.int32),
             combos[i][1]) for i in picks]

    def tokens_of(seq_tail):
        """Generated tokens until (and incl.) EOS, like the engine."""
        idx = np.where(seq_tail == EOS)[0]
        return int(idx[0]) + 1 if idx.size else seq_tail.shape[0]

    # whole-request recompute programs, one per (P, new) combo, warmed:
    # greedy decode where EVERY token pays a full fixed-shape forward
    # over the whole buffer (the causal mask hides the zero tail) —
    # generate(kv_cache=False, beam_size=1)'s recompute-prefix
    # semantics as one fully-jitted scan, the strongest whole-request
    # baseline
    import jax.numpy as jnp

    def make_recompute_prog(P, new):
        def fn(prompt):                          # (1, P) int32
            buf0 = jnp.zeros((1, P + new), jnp.int32).at[:, :P].set(
                prompt)

            def body(carry, t):
                buf, fin = carry
                logits, _ = model.apply(params, state, buf)
                pos = P - 1 + t
                lg = jax.lax.dynamic_index_in_dim(logits, pos, axis=1,
                                                  keepdims=False)
                nxt = jnp.argmax(lg, -1).astype(jnp.int32)
                nxt = jnp.where(fin, jnp.int32(EOS), nxt)
                fin = fin | (nxt == EOS)
                buf = jax.lax.dynamic_update_slice(
                    buf, nxt[:, None], (0, pos + 1))
                return (buf, fin), nxt

            (_, _), toks = jax.lax.scan(
                body, (buf0, jnp.zeros((1,), bool)), jnp.arange(new))
            return toks[:, 0]                    # (new,)
        return jax.jit(fn)

    base_prog = {}
    for P, new in combos:
        prog = make_recompute_prog(P, new)
        np.asarray(prog(np.zeros((1, P), np.int32) + 2))   # compile
        base_prog[(P, new)] = prog
    # serial service-rate calibration on the real request mix
    t0 = time.perf_counter()
    for prompt, new in reqs[:12]:
        np.asarray(base_prog[(prompt.shape[0], new)](prompt[None, :]))
    cal_wall = time.perf_counter() - t0
    base_rate_req = 12 / cal_wall
    offered_req = 12.0 * base_rate_req
    arrivals = np.cumsum(np.random.RandomState(1).exponential(
        1.0 / offered_req, n_requests))

    def percentiles(vals):
        a = np.asarray(vals, np.float64)
        return (round(float(np.percentile(a, 50)), 1),
                round(float(np.percentile(a, 99)), 1))

    def run_baseline():
        done_t, toks, ttft = [], 0, []
        t0 = time.perf_counter()
        for i, (prompt, new) in enumerate(reqs):
            now = time.perf_counter() - t0
            if arrivals[i] > now:
                time.sleep(arrivals[i] - now)
            # FIFO, one request at a time: the whole-request unit
            toks_out = np.asarray(base_prog[(prompt.shape[0], new)]
                                  (prompt[None, :]))
            t_done = time.perf_counter() - t0
            n = tokens_of(toks_out)
            toks += n
            ttft.append((t_done - arrivals[i]) * 1e3)
            done_t.append(t_done)
        wall = done_t[-1]
        p50, p99 = percentiles(ttft)
        return {"tokens": toks, "wall_s": round(wall, 3),
                "tokens_per_s": round(toks / wall, 1),
                "ttft_p50_ms": p50, "ttft_p99_ms": p99,
                "completed": len(done_t)}

    def run_engine(S):
        from bigdl_tpu import observe
        tag = f"dec{S}"
        eng = ServeEngine()
        # no mesh on the decode legs: the slot batch is latency-bound
        # and a REPLICATED pinning would make all 8 virtual devices
        # (sharing one physical core here) each execute the full step —
        # 8x the work for bit-identical results. The mesh stays the
        # baseline environment; sharded decode is a real-chip question.
        eng.register(tag, model, params, state, decode=True,
                     num_slots=S, max_seq_len=L, prefill_chunk=32)
        toks = 0
        replies = []
        t0 = time.perf_counter()
        for i, (prompt, new) in enumerate(reqs):
            now = time.perf_counter() - t0
            if arrivals[i] > now:
                time.sleep(arrivals[i] - now)
            replies.append(eng.submit_generate(tag, prompt, new))
        for rep in replies:
            toks += rep.result(timeout=600).shape[0]
        wall = time.perf_counter() - t0
        from bigdl_tpu.serve.batcher import (BATCH_FILL_BOUNDS,
                                             LATENCY_MS_BOUNDS)
        reg = observe.registry()
        ttft = reg.histogram(f"serve/{tag}/decode/ttft_ms",
                             LATENCY_MS_BOUNDS)
        step = reg.histogram(f"serve/{tag}/decode/step_ms",
                             LATENCY_MS_BOUNDS)
        occ = reg.histogram(f"serve/{tag}/decode/slot_occupancy",
                            BATCH_FILL_BOUNDS)
        rec = {
            "tokens": toks, "wall_s": round(wall, 3),
            "tokens_per_s": round(toks / wall, 1),
            "ttft_p50_ms": round(ttft.quantile(0.50), 1),
            "ttft_p99_ms": round(ttft.quantile(0.99), 1),
            "step_p50_ms": round(step.quantile(0.50), 2),
            "step_p99_ms": round(step.quantile(0.99), 2),
            "slot_occupancy_mean": round(occ.sum / occ.count, 3)
            if occ.count else 0.0,
            "completed": len(replies),
        }
        eng.shutdown()
        return rec

    rows = {"baseline": run_baseline()}
    for S in slots_legs:
        rows[f"slots{S}"] = run_engine(S)
    base_tps = max(rows["baseline"]["tokens_per_s"], 1e-9)
    for S in slots_legs:
        rows[f"speedup_slots{S}"] = round(
            rows[f"slots{S}"]["tokens_per_s"] / base_tps, 2)
    top = f"slots{slots_legs[-1]}"
    rows["speedup"] = rows[f"speedup_{top}"]
    rows["ttft_p99_ok"] = bool(rows[top]["ttft_p99_ms"]
                               <= rows["baseline"]["ttft_p99_ms"])
    rows["offered_req_per_sec"] = round(offered_req, 2)
    rows["base_rate_req_per_sec"] = round(base_rate_req, 2)
    return rows


def _bench_decode_paged(n_requests=32, S=8):
    """Paged-KV decode-economics bench (ISSUE 20 acceptance): the same
    model, slot count, and saturating burst of mixed-length generates
    against two KV residency strategies:

      * dense — the per-slot bucket: every slot pre-reserves
        max_seq_len tokens of K/V whether the request uses them or not
        (HBM = S x L x layers x 2 x d x 4B);
      * paged — the block pool sized to the workload's LIVE footprint
        (~40% of dense at this mix), slots acquiring 16-token blocks
        lazily as the frontier crosses block boundaries.

    tokens/s-per-HBM-byte is the headline: decode is memory-bound, so
    serving the same token stream (bit-identical — tests/test_decode)
    out of less resident KV is capacity you can spend on more slots.
    A third leg replays a shared-prefix trace (one long system prompt,
    unique tails) with the prefix cache on vs off: hits skip the whole
    shared prefill region per request (fed jumps to the cached
    frontier), measured as prefill_ms_total and TTFT deltas."""
    import numpy as np
    from bigdl_tpu import observe
    from bigdl_tpu.serve import ServeEngine
    from bigdl_tpu.serve.decode import decode_demo_model

    VOCAB, EOS, L, BLOCK = 256, 255, 384, 16
    model, params, state = decode_demo_model(
        vocab_size=VOCAB, n_positions=512, d_model=128, num_heads=4,
        num_layers=3, eos_id=EOS)
    # mixed-length mix: long max_seq_len, mostly-short requests — the
    # regime where dense per-slot reservation wastes the most HBM
    combos = [(32, 32), (64, 32), (96, 48), (160, 64)]
    r = np.random.RandomState(0)
    picks = r.randint(0, len(combos), n_requests)
    reqs = [(r.randint(2, VOCAB - 1, combos[i][0]).astype(np.int32),
             combos[i][1]) for i in picks]
    # worst-case concurrent live blocks: S slots all running the
    # largest combo — the pool never refuses this trace
    worst = max(-(-(p + n) // BLOCK) for p, n in combos)
    pool_blocks = S * worst                       # 80 vs dense 192

    def run(tag, trace, **reg_kw):
        eng = ServeEngine()
        # no mesh (BENCH_r18 rationale): 8 virtual devices sharing one
        # core would each run the full replicated step
        eng.register(tag, model, params, state, decode=True,
                     num_slots=S, max_seq_len=L, prefill_chunk=32,
                     **reg_kw)
        dec = eng.registry.get(tag).decode
        kv_bytes = dec.kv_cache_bytes
        t0 = time.perf_counter()
        replies = [eng.submit_generate(tag, p, new) for p, new in trace]
        toks = sum(rep.result(timeout=600).shape[0] for rep in replies)
        wall = time.perf_counter() - t0
        from bigdl_tpu.serve.batcher import LATENCY_MS_BOUNDS
        reg = observe.registry()
        ttft = reg.histogram(f"serve/{tag}/decode/ttft_ms",
                             LATENCY_MS_BOUNDS)
        pf = reg.histogram(f"serve/{tag}/decode/prefill_ms",
                           LATENCY_MS_BOUNDS)
        sched = eng._decoders[tag]
        st = sched.stats()
        rec = {
            "tokens": toks, "wall_s": round(wall, 3),
            "tokens_per_s": round(toks / wall, 1),
            "kv_hbm_bytes": int(kv_bytes),
            "tokens_per_s_per_hbm_gib":
                round(toks / wall / (kv_bytes / 2**30), 1),
            "ttft_p50_ms": round(ttft.quantile(0.50), 1),
            "ttft_p99_ms": round(ttft.quantile(0.99), 1),
            "prefill_ms_total": round(pf.sum, 1),
            "completed": len(replies),
        }
        if st.get("paged"):
            rec.update({k: st[k] for k in
                        ("kv_block", "kv_blocks_total", "kv_pool_util")})
            if "prefix_hit_rate" in st:
                rec["prefix_hit_rate"] = st["prefix_hit_rate"]
                rec["prefix_hits"] = st["prefix_hits"]
                # every hit block is kv_block prompt tokens NOT
                # re-prefilled
                rec["prefill_tokens_saved"] = st["prefix_hits"] * BLOCK
        eng.shutdown()
        return rec

    rows = {
        "dense": run("pgd_dense", reqs, paged=False),
        "paged": run("pgd_paged", reqs, paged=True, kv_block=BLOCK,
                     kv_pool_blocks=pool_blocks, prefix_cache=False),
    }
    # shared-prefix trace: one 128-token system prompt, unique tails
    sys_prompt = r.randint(2, VOCAB - 1, 128).astype(np.int32)
    shared_reqs = [(np.concatenate([sys_prompt,
                                    r.randint(2, VOCAB - 1, 24)
                                    .astype(np.int32)]), 32)
                   for _ in range(n_requests)]
    rows["shared_prefix_off"] = run(
        "pgd_pfx0", shared_reqs, paged=True, kv_block=BLOCK,
        kv_pool_blocks=pool_blocks, prefix_cache=False)
    rows["shared_prefix_on"] = run(
        "pgd_pfx1", shared_reqs, paged=True, kv_block=BLOCK,
        kv_pool_blocks=pool_blocks, prefix_cache=True)
    d, p = rows["dense"], rows["paged"]
    rows["hbm_efficiency"] = round(
        p["tokens_per_s_per_hbm_gib"]
        / max(d["tokens_per_s_per_hbm_gib"], 1e-9), 2)
    rows["kv_hbm_ratio"] = round(p["kv_hbm_bytes"] / d["kv_hbm_bytes"],
                                 3)
    on, off = rows["shared_prefix_on"], rows["shared_prefix_off"]
    rows["prefix_prefill_savings"] = round(
        1.0 - on["prefill_ms_total"]
        / max(off["prefill_ms_total"], 1e-9), 3)
    rows["prefix_ttft_p50_ratio"] = round(
        on["ttft_p50_ms"] / max(off["ttft_p50_ms"], 1e-9), 3)
    rows["hbm_efficiency_ok"] = bool(rows["hbm_efficiency"] >= 2.0)
    return rows


def _bench_serve_net(n_requests=120, kill_requests=30):
    """Network-front bench (ISSUE 18 acceptance): the same open-loop
    Poisson methodology as the serve/decode legs (BENCH_r12), now
    through REAL sockets.

      * inproc — open-loop predict load straight into ServeEngine
        (thread-per-request blocking `predict`, the PR-8 in-process
        dispatch path);
      * http — the IDENTICAL request trace and arrival times POSTed
        to /v1/predict through ServeFront's socket. The headline is
        http/inproc requests-per-second at matched load — the wire +
        JSON codec overhead of the network front (acceptance >= 0.85,
        i.e. <= 15% overhead);
      * replica_kill — generate traffic (every third request an SSE
        stream) through ServeFront(ReplicaRouter) over TWO replica
        subprocesses, SIGKILLing the most-recently-placed replica
        mid-run: zero accepted requests lost (failover retries +
        stream resume), p99 stays bounded, and streamed tokens arrive
        incrementally (inter-token gap stats prove iteration cadence,
        not buffer-to-EOS)."""
    import http.client as http_client
    import numpy as np
    import jax
    from bigdl_tpu import observe
    from bigdl_tpu.serve import ServeEngine
    from bigdl_tpu.serve.net import LocalBackend, ServeFront
    from bigdl_tpu.utils.threads import spawn
    import bigdl_tpu.nn as nn

    # a model whose forward actually costs (the serve-leg regime):
    # with a null model the wire/codec term IS the measurement and the
    # ratio says nothing about fronting a real workload. Narrow input
    # (64 features), wide trunk: per-request compute dominates the
    # per-request wire term the way a real served model does.
    dim = 64
    model = nn.Sequential(nn.Linear(dim, 4096), nn.Tanh(),
                          nn.Linear(4096, 4096), nn.Tanh(),
                          nn.Linear(4096, 4096), nn.Tanh(),
                          nn.Linear(4096, 8))
    params, state = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(install_sigterm=False)
    engine.register("m", model, params, state, max_batch=16,
                    max_wait_ms=2.0,
                    precompile_input=((dim,), np.dtype(np.float32)))

    r = np.random.RandomState(0)
    reqs = [r.randn(int(n), dim).astype(np.float32)
            for n in r.randint(4, 17, n_requests)]
    # serial batch-1 service-rate calibration on REAL request sizes,
    # then offer 3x (the serve-leg convention): both legs saturated at
    # the SAME load
    for x in reqs[:3]:
        engine.predict("m", x, timeout=60)      # warm
    t0 = time.perf_counter()
    for x in reqs[:16]:
        engine.predict("m", x, timeout=60)
    base_rate = 16 / (time.perf_counter() - t0)
    offered = 3.0 * base_rate
    arrivals = np.cumsum(np.random.RandomState(1).exponential(
        1.0 / offered, n_requests))

    def percentiles(vals):
        a = np.asarray(vals, np.float64)
        return (round(float(np.percentile(a, 50)), 1),
                round(float(np.percentile(a, 99)), 1))

    from bigdl_tpu.serve.batcher import Overloaded

    def open_loop(call):
        """Dispatch `call(i)` on its own thread at each arrival time;
        returns (latencies_ms, shed, errors, wall_s). Overloaded/429
        is SHED, not an error — expected at open-loop saturation and
        identical policy on both legs."""
        lat, errors = [], []
        shed = [0]
        t0 = time.perf_counter()

        def one(i):
            try:
                call(i)
                lat.append((time.perf_counter() - t0 - arrivals[i])
                           * 1e3)
            except Overloaded:
                shed[0] += 1
            except Exception as e:       # noqa: BLE001 — in the JSON
                errors.append(f"req {i}: {e!r}")

        ts = []
        for i in range(n_requests):
            now = time.perf_counter() - t0
            if arrivals[i] > now:
                time.sleep(arrivals[i] - now)
            ts.append(spawn(one, name=f"bench-net-{i}", args=(i,)))
        for t in ts:
            t.join()
        return lat, shed[0], errors, time.perf_counter() - t0

    def leg(call):
        lat, shed, errors, wall = open_loop(call)
        p50, p99 = percentiles(lat) if lat else (0.0, 0.0)
        return {"completed": len(lat), "shed": shed,
                "errors": len(errors),
                "wall_s": round(wall, 3),
                "rps": round(len(lat) / wall, 1),
                "p50_ms": p50, "p99_ms": p99}

    rows = {"offered_req_per_sec": round(offered, 1),
            "inproc": leg(lambda i: engine.predict("m", reqs[i],
                                                   timeout=60))}

    front = ServeFront(LocalBackend(engine), port=0)

    # load-generator discipline: bodies pre-encoded before the clock
    # (wrk/vegeta-style — the bench measures the FRONT, not the
    # client's encoder) and a FIXED pool of keep-alive connections
    # (wrk -c N) reused across requests, as any real client stack
    # would; requests beyond the pool wait for a free connection and
    # that wait counts in their latency
    bodies = [json.dumps({"model": "m", "inputs": reqs[i].tolist(),
                          "dtype": "float32", "client": "bench"})
              for i in range(n_requests)]
    import queue as queue_mod
    conn_pool = queue_mod.Queue()
    for _ in range(16):
        conn_pool.put(http_client.HTTPConnection(
            front.host, front.port, timeout=60))

    def http_predict(i):
        conn = conn_pool.get(timeout=60)
        try:
            conn.request("POST", "/v1/predict", bodies[i],
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = json.loads(resp.read().decode())
            if resp.status == 429:
                raise Overloaded(body.get("error", "shed"))
            if resp.status != 200:
                raise RuntimeError(f"HTTP {resp.status}: {body}")
        except Exception:
            conn.close()                 # keep the pool at full size
            conn_pool.put(http_client.HTTPConnection(
                front.host, front.port, timeout=60))
            raise
        conn_pool.put(conn)

    rows["http"] = leg(http_predict)
    while not conn_pool.empty():
        conn_pool.get().close()
    front.close()
    engine.shutdown()
    ratio = round(rows["http"]["rps"]
                  / max(rows["inproc"]["rps"], 1e-9), 3)
    rows["overhead_ratio"] = ratio
    rows["overhead_ok"] = bool(ratio >= 0.85)

    # ------------------------------- replica-kill leg (real processes)
    from bigdl_tpu.serve.router import (ReplicaRouter, launch_replicas,
                                        stop_replicas)
    procs, urls = launch_replicas(
        2, ["--decode", "--slots", "8", "--max-seq-len", "256",
            "--prefill-chunk", "16", "--seed", "0"],
        ready_timeout_s=300)
    router = ReplicaRouter(urls, retries=2, health_ttl_s=0.1)
    kfront = ServeFront(router, port=0)
    killed = {"done": False}
    gen_r = np.random.RandomState(2)
    prompts = [[int(t) for t in gen_r.randint(2, 48,
                                              int(gen_r.randint(4, 17)))]
               for _ in range(kill_requests)]
    karrivals = np.cumsum(np.random.RandomState(3).exponential(
        0.08, kill_requests))
    GEN_NEW = 64                         # long enough that the SIGKILL
    # lands while streams are mid-flight (resume, not just re-place)
    lat, errors, gaps, streams = [], [], [], [0]

    def gen_one(i, t0):
        stream = i % 3 == 0
        body = {"model": "default", "prompt": prompts[i],
                "max_new_tokens": GEN_NEW, "eos_id": -1,
                "client": "bench"}
        conn = http_client.HTTPConnection(kfront.host, kfront.port,
                                          timeout=120)
        try:
            conn.request("POST", "/v1/generate",
                         json.dumps({**body, "stream": True}
                                    if stream else body),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            if stream:
                streams[0] += 1
                n, last_t = 0, None
                for raw in resp.fp:
                    line = raw.decode().strip()
                    if line.startswith("data:") and '"token"' in line:
                        now = time.perf_counter()
                        if last_t is not None:
                            gaps.append((now - last_t) * 1e3)
                        last_t = now
                        n += 1
                    elif line.startswith("event: done"):
                        break
                    elif line.startswith("event: error"):
                        raise RuntimeError("SSE error event")
                if n != GEN_NEW:
                    raise RuntimeError(
                        f"stream returned {n}/{GEN_NEW} tokens")
            else:
                payload = json.loads(resp.read().decode())
                if resp.status != 200 or payload.get("count") != \
                        GEN_NEW:
                    raise RuntimeError(
                        f"HTTP {resp.status}: {payload}")
            lat.append((time.perf_counter() - t0 - karrivals[i]) * 1e3)
        except Exception as e:           # noqa: BLE001 — in the JSON
            errors.append(f"req {i}: {e!r}")
        finally:
            conn.close()

    t0 = time.perf_counter()
    ts = []
    for i in range(kill_requests):
        now = time.perf_counter() - t0
        if karrivals[i] > now:
            time.sleep(karrivals[i] - now)
        ts.append(spawn(gen_one, name=f"bench-kill-{i}", args=(i, t0)))
        if i >= kill_requests // 2 and i % 3 == 0 \
                and not killed["done"]:
            # kill right after dispatching a STREAM so the victim dies
            # with that stream mid-flight — the resume path, not just
            # re-placement of queued work
            time.sleep(0.05)
            victim = router.last_placement or 0
            os.kill(procs[victim].pid, 9)     # SIGKILL mid-run
            killed["done"] = True
            killed["victim"] = victim
    for t in ts:
        t.join()
    wall = time.perf_counter() - t0
    p50, p99 = percentiles(lat) if lat else (0.0, 0.0)
    kill_rows = {
        "requests": kill_requests,
        "completed": len(lat),
        "lost": len(errors),
        "lost_detail": errors[:4],
        "streams": streams[0],
        "wall_s": round(wall, 3),
        "p50_ms": p50, "p99_ms": p99,
        "failovers": int(router.m_failovers.value),
        "stream_resumes": int(router.m_resumes.value),
        "stream_gap_p50_ms": percentiles(gaps)[0] if gaps else None,
        "stream_gap_p95_ms": round(float(np.percentile(
            np.asarray(gaps), 95)), 1) if gaps else None,
        "incremental_streams": bool(gaps and max(gaps) > 0.0),
    }
    kfront.close()
    stop_replicas(procs)
    kill_rows["zero_lost_ok"] = kill_rows["lost"] == 0
    kill_rows["p99_bounded_ok"] = bool(p99 and p99 < 15000.0)
    rows["replica_kill"] = kill_rows
    rows["speedup"] = ratio                  # headline: overhead ratio
    return rows


def _bench_chaos(batch_size=32, hidden=128, iters=48, k=8):
    """Slice-failover chaos bench: DistriOptimizer on a 2 slices × 4
    devices CPU mesh, kill slice 1 mid-run via the `slice:1@step:N`
    injector, and measure the wall-clock lost to the in-run failover
    against the budget of one K-window plus re-shard + recompile
    overhead (ISSUE 6 acceptance; docs/resilience.md "Slice failover").

    Two (control, chaos) passes share one persistent compile cache: the
    first pays the cold compiles for BOTH topologies and publishes them;
    the second is the measurement — its post-failover recompile for the
    survivor mesh is served warm from the cache. Deltas of the observe
    registry (jit/compile_seconds, phase/failover/reshard,
    failover/slice_losses) attribute where the lost time went."""
    import numpy as np
    import jax
    # main() enabled the persistent cache; here even the tiny programs
    # of this leg must reach it
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import bigdl_tpu.nn as nn
    from bigdl_tpu import observe
    from bigdl_tpu.dataset import ArrayDataSet
    from bigdl_tpu.optim.method import Adam
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.parallel import DistriOptimizer, create_mesh
    from bigdl_tpu.resilience import faults

    r = np.random.RandomState(0)
    n = batch_size * iters
    x = r.randn(n, 16).astype(np.float32)
    y = (x.sum(1) > 0).astype(np.int32)

    def run(fault):
        faults.configure(fault)
        observe.registry().reset()        # per-run telemetry isolation
        mesh = create_mesh(jax.devices()[:8], slices=2,
                           drop_trivial_axes=True)
        model = nn.Sequential(nn.Linear(16, hidden), nn.ReLU(),
                              nn.Linear(hidden, 2), nn.LogSoftMax())
        ds = ArrayDataSet(x, y, batch_size, drop_last=True, shuffle=False)
        opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(),
                              Adam(1e-3), mesh=mesh, zero1=True, seed=3,
                              steps_per_call=k)
        opt.set_end_when(Trigger.max_iteration(iters))
        t0 = time.perf_counter()
        opt.optimize()
        wall = time.perf_counter() - t0
        snap = observe.registry().snapshot()
        faults.configure("")
        if opt.state["neval"] != iters:
            raise RuntimeError(
                f"chaos bench run stopped at {opt.state['neval']}/{iters}")

        def hist(name):
            return snap["histograms"].get(name) or {
                "sum": 0.0, "count": 0, "max": 0.0}

        disp = hist("phase/train/dispatch")
        disp_mean = disp["sum"] / max(disp["count"], 1)
        return {
            "wall_s": round(wall, 3),
            "compile_s": round(
                snap["counters"].get("jit/compile_seconds", 0.0), 3),
            "compiles": int(snap["counters"].get("jit/compiles", 0)),
            "cache_hit_compiles": int(
                snap["counters"].get("jit/cache_hit_compiles", 0)),
            "reshard_s": round(hist("phase/failover/reshard")["sum"], 4),
            # the post-failover program rebuild (retrace + cache-warm
            # deserialize + first execution) lands inside ONE dispatch
            # span — its excess over the mean dispatch is the rebuild
            "dispatch_max_s": round(disp["max"], 4),
            "dispatch_mean_s": round(disp_mean, 4),
            "slice_losses": int(
                snap["counters"].get("failover/slice_losses", 0)),
            "failover_counters": {
                name: v for name, v in snap["counters"].items()
                if name.startswith("failover/")},
            "survivor_devices": int(opt.mesh.size),
        }

    fault_spec = f"slice:1@step:{iters // 2}"
    passes = []
    for _ in range(2):
        passes.append({"control": run(""), "chaos": run(fault_spec)})
    ctrl, chaos = passes[1]["control"], passes[1]["chaos"]
    k_window_s = ctrl["wall_s"] / (iters / k)
    time_lost_s = max(0.0, chaos["wall_s"] - ctrl["wall_s"])
    rebuild_s = max(0.0, chaos["dispatch_max_s"]
                    - chaos["dispatch_mean_s"])
    budget_s = k_window_s + chaos["reshard_s"] + rebuild_s
    return {
        "time_lost_s": round(time_lost_s, 3),
        "budget_s": round(budget_s, 3),
        "k_window_s": round(k_window_s, 4),
        "reshard_s": chaos["reshard_s"],
        "rebuild_s": round(rebuild_s, 4),
        "within_budget": time_lost_s <= budget_s,
        "warm_failover_cache_hits": chaos["cache_hit_compiles"],
        "cold_pass": passes[0],
        "warm_pass": passes[1],
        "failover_counters": chaos["failover_counters"],
    }


def _bench_dcn(batch_size=32, hidden=256, iters=160, warmup=8, k=4,
               latency_s=0.010, bandwidth_bps=5e6):
    """DCN-tier exchange bench (ISSUE 13; docs/parallelism.md): the
    accumulate-locally / exchange-every-T leg under a SIMULATED
    data-center-network throttle, T∈{1,4,8} × {bf16, int8-EF}, on the
    2 slices × 4 devices CPU mesh.

    Throttle: the chaos-harness trick of charging the fault path real
    wall-clock — every exchange-bearing dispatch sleeps
    `latency + wire_bytes/bandwidth` on the training thread (wire bytes
    from parallel/dcn.wire_bytes_per_exchange for the leg's compression
    mode), so `trained rec/s` is measured wall including the simulated
    DCN stalls. T=1 pays the stall every step; T=8 every 8th, with int8
    cutting the byte term ~4x vs fp32.

    Quality: every leg trains the SAME model/data/seed for warmup +
    iters steps; `final_loss` is the full-dataset training loss of the
    final params (one jitted eval), so the communication win is shown
    at matched step count with the convergence cost on the record.
    T>1 legs run the DiLoCo-style Nesterov outer update
    (BIGDL_TPU_SLICE_OUTER=nesterov), which is what makes low-frequency
    exchange competitive at equal steps."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import bigdl_tpu.nn as nn
    from bigdl_tpu import observe
    from bigdl_tpu.dataset import ArrayDataSet
    from bigdl_tpu.optim.method import Adam
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.parallel import DistriOptimizer, create_mesh
    from bigdl_tpu.parallel import dcn as _dcn

    r = np.random.RandomState(0)
    n = batch_size * 40
    x = r.randn(n, 16).astype(np.float32)
    y = (x.sum(1) > 0).astype(np.int32)

    class _Throttled(DistriOptimizer):
        """Exchange-throttled trainer: wraps the built step programs so
        every window boundary charges the simulated DCN stall."""
        bench_T = 1
        throttle_s = 0.0
        throttle_on = False
        sleep_total = 0.0

        def _get_built(self, kind):
            entry = super()._get_built(kind)
            if kind == "eval_jit" or getattr(entry, "_dcn_throttle", False):
                return entry
            outer = self

            class _Proxy:
                _dcn_throttle = True
                jitted = entry.jitted

                def __call__(self, *args):
                    out = entry(*args)
                    if outer.throttle_on and outer.throttle_s > 0:
                        start = outer.state["neval"]
                        kv = (int(np.asarray(args[-1]).sum())
                              if kind.endswith("fused") else 1)
                        n_ex = sum(1 for i in range(start + 1,
                                                    start + kv + 1)
                                   if i % outer.bench_T == 0)
                        if n_ex:
                            time.sleep(n_ex * outer.throttle_s)
                            outer.sleep_total += n_ex * outer.throttle_s
                    return out

            proxy = _Proxy()
            self._built_steps[self._step_key(kind)] = proxy
            return proxy

    def eval_loss(model, params, state):
        crit = nn.ClassNLLCriterion()

        @jax.jit
        def lf(p, s, xx, yy):
            out, _ = model.apply(p, s, xx, training=False)
            return crit.forward(out, yy)

        return float(jax.device_get(lf(params, state,
                                       jnp.asarray(x), jnp.asarray(y))))

    def run_leg(T, compress):
        for env, val in (("BIGDL_TPU_SLICE_EXCHANGE_EVERY", str(T)),
                         ("BIGDL_TPU_SLICE_GRAD_COMPRESS",
                          compress if T > 1 or compress == "int8" else ""),
                         ("BIGDL_TPU_SLICE_GRAD_DTYPE",
                          "bfloat16" if T == 1 and compress == "bfloat16"
                          else ""),
                         ("BIGDL_TPU_SLICE_OUTER",
                          "nesterov" if T > 1 else "")):
            if val:
                os.environ[env] = val
            else:
                os.environ.pop(env, None)
        observe.registry().reset()
        mesh = create_mesh(jax.devices()[:8], slices=2,
                           drop_trivial_axes=True)
        model = nn.Sequential(nn.Linear(16, hidden), nn.ReLU(),
                              nn.Linear(hidden, 2), nn.LogSoftMax())
        ds = ArrayDataSet(x, y, batch_size, drop_last=True, shuffle=False)
        opt = _Throttled(model, ds, nn.ClassNLLCriterion(), Adam(1e-2),
                         mesh=mesh, zero1=True, seed=3, steps_per_call=k)
        params_shape, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        wire = _dcn.wire_bytes_per_exchange(params_shape, compress)
        opt.bench_T = T
        opt.throttle_s = latency_s + wire / bandwidth_bps
        # warmup pass eats every compile with the throttle off
        opt.set_end_when(Trigger.max_iteration(warmup))
        opt.optimize()
        opt.throttle_on = True
        opt.set_end_when(Trigger.max_iteration(warmup + iters))
        t0 = time.perf_counter()
        params, state = opt.optimize()
        wall = time.perf_counter() - t0
        snap = observe.registry().snapshot()
        return {
            "trained_rec_s": round(iters * batch_size / wall, 1),
            "wall_s": round(wall, 3),
            "simulated_dcn_stall_s": round(opt.sleep_total, 3),
            "stall_per_exchange_ms": round(opt.throttle_s * 1e3, 2),
            "wire_bytes_per_exchange": wire,
            "exchanges": int(snap["counters"].get("exchange/count",
                                                  iters if T == 1 else 0)
                             or (iters if T == 1 else 0)),
            "final_loss": round(eval_loss(model, params, state), 4),
        }

    legs = {}
    for T in (1, 4, 8):
        for compress in ("bfloat16", "int8"):
            legs[f"t{T}_{'bf16' if compress == 'bfloat16' else 'int8'}"] \
                = run_leg(T, compress)
    for env in ("BIGDL_TPU_SLICE_EXCHANGE_EVERY",
                "BIGDL_TPU_SLICE_GRAD_COMPRESS", "BIGDL_TPU_SLICE_OUTER",
                "BIGDL_TPU_SLICE_GRAD_DTYPE"):
        os.environ.pop(env, None)
    base = legs["t1_bf16"]
    head = legs["t8_int8"]
    loss_tol = max(0.05, 0.25 * base["final_loss"])
    return {
        "legs": legs,
        "throttle_model": {"latency_s": latency_s,
                           "bandwidth_bps": bandwidth_bps},
        "speedup_t8_int8_vs_t1": round(
            head["trained_rec_s"] / base["trained_rec_s"], 2),
        "loss_delta_t8_int8_vs_t1": round(
            head["final_loss"] - base["final_loss"], 4),
        "loss_tolerance": round(loss_tol, 4),
        "loss_within_tolerance":
            head["final_loss"] - base["final_loss"] <= loss_tol,
    }


def main():
    import jax
    import jax.numpy as jnp
    # persistent compile cache: JAX_COMPILATION_CACHE_DIR, else the
    # fixed <checkout>/.jax_cache (docs/compile_cache.md)
    from bigdl_tpu import compilecache
    compilecache.enable()

    which = sys.argv[1] if len(sys.argv) > 1 else "resnet50"
    if which not in _METRICS:
        raise SystemExit(f"unknown leg {which!r}; one of {sorted(_METRICS)}")
    dev = jax.devices()[0]
    backend = jax.default_backend()
    peak = None
    if which in _CHIP_LEGS:
        if backend != "tpu":
            raise SystemExit(
                f"bench.py {which} measures the chip and found backend "
                f"{backend!r} ({len(jax.devices())} x {dev.device_kind}) — "
                f"no TPU, no number")
        peak = _peak_flops(dev.device_kind)

    if which == "dispatch":
        # CPU-mesh microbench by design (written for an 8-device
        # host platform): the win being measured is Python
        # dispatch amortization, which a fast chip would only mask
        metric, unit = _METRICS[which]
        rows = _bench_dispatch()
        base = rows.get(1) or 1e-9
        speedups = {f"speedup_k{k}": round(v / base, 2)
                    for k, v in rows.items() if k != 1}
        # headline: best speedup among K >= 4 (the amortized regime; the
        # per-K columns keep the full curve honest)
        best = max(v / base for k, v in rows.items() if k >= 4)
        print(json.dumps({
            "metric": metric,
            "value": round(best, 2),
            "unit": unit,
            "vs_baseline": 1.0,
            "backend": backend,
            "n_devices": len(jax.devices()),
            "batch_size": 32,
            "rec_per_sec": {f"k{k}": v for k, v in rows.items()},
            **speedups,
            "host": _host_provenance(),
            "note": "small-model DistriOptimizer.optimize() on the "
                    "8-virtual-device CPU mesh; K=1 runs the pre-fusion "
                    "per-step dispatch path unchanged (bit-identical "
                    "program)",
        }))
        return
    if which == "input":
        # CPU-mesh microbench (written for 8 virtual CPU
        # devices): what the streaming input service buys the feed path
        # — host pipeline scheduling + IO-wait overlap, backend-agnostic
        metric, unit = _METRICS[which]
        rows = _bench_input()
        print(json.dumps({
            "metric": metric,
            "value": rows["data_wait_frac_ratio"],
            "unit": unit,
            "vs_baseline": 1.0,
            "backend": backend,
            "n_devices": len(jax.devices()),
            "batch_size": 32,
            **rows,
            "host": _host_provenance(),
            "note": "data-wait span fraction (train/data_wait over the "
                    "step loop's accounted phases), small-model "
                    "DistriOptimizer.optimize() K=8 over record shards "
                    "on the 8-virtual-device CPU mesh; per-record decode "
                    "carries a calibrated sleep emulating remote-storage "
                    "fetch (one worker feeds 1/4 of device demand, the "
                    "service's 8 workers feed 2x). off = "
                    "BIGDL_TPU_DATA_SERVICE=0 legacy prefetch, on = "
                    "read-ahead + 8 decode workers + double-buffered "
                    "H2D. Acceptance: on-fraction <= 20% of off "
                    "(value = off/on >= 5); 'throttled' starves even "
                    "the pool and shows the DATA_ECHO=2 win "
                    "(echo_speedup, Choi et al. data echoing). Warmup "
                    "pass per mode eats every compile; measured pass "
                    "is steady-state",
        }))
        return
    if which == "serve":
        # CPU-mesh microbench (written for 8 virtual CPU
        # devices): what continuous batching buys over batch-size-1
        # dispatch is host scheduling + program-count amortization,
        # backend-agnostic plumbing
        metric, unit = _METRICS[which]
        rows = _bench_serve()
        print(json.dumps({
            "metric": metric,
            "value": rows["speedup"],
            "unit": unit,
            "vs_baseline": 1.0,
            "backend": backend,
            "n_devices": len(jax.devices()),
            **rows,
            "host": _host_provenance(),
            "note": "Poisson open-loop load (closed-form arrival times, "
                    "offered = 3x the calibrated batch-1 service rate) "
                    "against ServeEngine on the 8-virtual-device CPU "
                    "mesh, mixed 1-8-row requests, bounded queue with "
                    "Overloaded shedding in both modes; batch1 = "
                    "coalescing off, dynamic = continuous batching with "
                    "a 2ms max-wait deadline, both AOT-precompiled. "
                    "Acceptance: speedup >= 2 with p99_ok (dynamic p99 "
                    "<= batch1 p99) and warm_start.fresh_compiles == 0 "
                    "(every bucket served from the persistent-cache-"
                    "warmed AOT set)",
        }))
        return
    if which == "decode":
        # CPU-mesh microbench (written for 8 virtual CPU
        # devices): the iteration-level win is O(L) cached steps +
        # slot concurrency vs whole-request recompute — host/program
        # structure, backend-agnostic
        metric, unit = _METRICS[which]
        rows = _bench_decode()
        print(json.dumps({
            "metric": metric,
            "value": rows["speedup"],
            "unit": unit,
            "vs_baseline": 1.0,
            "backend": backend,
            "n_devices": len(jax.devices()),
            **rows,
            "host": _host_provenance(),
            "note": "open-loop Poisson arrivals of mixed-length "
                    "generate requests (prompts 32-96, max_new 32/64, "
                    "3-layer d=128 GPT-2 — prefixes long enough that "
                    "recomputing them per token actually costs) at "
                    "~12x the whole-request baseline's serial "
                    "service rate (every leg saturated => tokens/s = "
                    "capacity); baseline = recompute-prefix greedy decode "
                    "(generate(kv_cache=False) semantics as one "
                    "fully-jitted scan) one request at a time (the "
                    "whole-request batcher unit: mixed shapes cannot "
                    "co-batch, TTFT = completion), slots1/4/8 = "
                    "iteration-level DecodeEngine with S KV slots on "
                    "the 8-virtual-device mesh, chunked prefill + "
                    "fused greedy step, all legs warm/AOT. "
                    "Acceptance: slots8 decode tokens/s >= 3x "
                    "baseline with ttft_p99_ok (engine p99 TTFT <= "
                    "baseline's); parity + zero-fresh-compile proofs "
                    "live in tests/test_decode.py",
        }))
        return
    if which == "decode_paged":
        # CPU-mesh microbench: the paged-pool win is a RESIDENCY ratio
        # (same token stream out of less HBM) — structure, not FLOPs,
        # so the CPU mesh measures it faithfully
        metric, unit = _METRICS[which]
        rows = _bench_decode_paged()
        print(json.dumps({
            "metric": metric,
            "value": rows["hbm_efficiency"],
            "unit": unit,
            "vs_baseline": 1.0,
            "backend": backend,
            "n_devices": len(jax.devices()),
            **rows,
            "host": _host_provenance(),
            "note": "saturating burst of mixed-length generates "
                    "(prompts 32-160, max_new 32-64, max_seq_len 384, "
                    "3-layer d=128 GPT-2, 8 slots) against the dense "
                    "per-slot KV bucket vs the paged 16-token block "
                    "pool sized to the live worst case (~40% of "
                    "dense); headline = tokens/s-per-HBM-GiB ratio "
                    "(decode is memory-bound: equal tokens/s out of "
                    "less resident KV), acceptance >= 2.0. "
                    "shared_prefix_{off,on}: identical "
                    "128-token-system-prompt trace with the prefix "
                    "cache off/on — hits skip the shared prefill "
                    "region (prefill_tokens_saved, "
                    "prefix_prefill_savings, TTFT p50 ratio). "
                    "Bit-parity with dense lives in "
                    "tests/test_decode.py",
        }))
        return
    if which == "serve_net":
        # CPU-mesh microbench (written for 8 virtual CPU
        # devices): the wire/codec overhead of the HTTP front and the
        # router's failover are host plumbing, backend-agnostic
        metric, unit = _METRICS[which]
        rows = _bench_serve_net()
        print(json.dumps({
            "metric": metric,
            "value": rows["overhead_ratio"],
            "unit": unit,
            "vs_baseline": 1.0,
            "backend": backend,
            "n_devices": len(jax.devices()),
            **rows,
            "host": _host_provenance(),
            "note": "open-loop Poisson predict load (BENCH_r12 "
                    "methodology: closed-form arrival times, offered "
                    "= 3x the calibrated batch-1 service rate), the "
                    "IDENTICAL trace driven in-process "
                    "(engine.predict) and through ServeFront's real "
                    "socket (/v1/predict JSON) — overhead_ratio = "
                    "http rps / inproc rps, acceptance >= 0.85 "
                    "(network front costs <= 15%). replica_kill: "
                    "generate traffic (every 3rd an SSE stream) "
                    "through ServeFront(ReplicaRouter) over 2 replica "
                    "subprocesses with a mid-run SIGKILL — acceptance "
                    "zero_lost_ok (every accepted request answered "
                    "via failover retry / stream resume), "
                    "p99_bounded_ok, incremental_streams (nonzero "
                    "inter-token gaps = iteration cadence, not "
                    "buffered-to-EOS)",
        }))
        return
    if which == "chaos":
        # CPU-mesh microbench (written for 8 virtual CPU
        # devices as 2 slices × 4): in-run slice failover cost — host
        # re-shard + recompile plumbing, backend-agnostic
        metric, unit = _METRICS[which]
        rows = _bench_chaos()
        headroom = rows["budget_s"] / max(rows["time_lost_s"], 1e-3)
        print(json.dumps({
            "metric": metric,
            "value": round(min(headroom, 99.0), 2),
            "unit": unit,
            "vs_baseline": 1.0,
            "backend": backend,
            "n_devices": len(jax.devices()),
            "batch_size": 32,
            **rows,
            "host": _host_provenance(),
            "note": "kill-slice-1-mid-run on a 2x4 two-tier mesh, "
                    "small-MLP DistriOptimizer.optimize() K=8; "
                    "time_lost = chaos wall - control wall (warm pass; "
                    "the cold pass seeds the persistent compile cache "
                    "so the failover recompile is served warm); budget "
                    "= one K-window + failover re-shard + program "
                    "rebuild (retrace + warm deserialize, the max-over-"
                    "mean dispatch span). Acceptance: value >= 1 (time "
                    "lost within budget)",
        }))
        return
    if which == "dcn":
        # CPU-mesh microbench (written for 8 virtual CPU
        # devices as 2 slices × 4): the DCN win is a communication-
        # frequency/bytes property, simulated by charging real wall
        # clock per exchange — backend-agnostic plumbing
        metric, unit = _METRICS[which]
        rows = _bench_dcn()
        print(json.dumps({
            "metric": metric,
            "value": rows["speedup_t8_int8_vs_t1"],
            "unit": unit,
            "vs_baseline": 1.0,
            "backend": backend,
            "n_devices": len(jax.devices()),
            "batch_size": 32,
            **rows,
            "host": _host_provenance(),
            "note": "accumulate-locally / exchange-every-T on the 2x4 "
                    "two-tier mesh under a simulated-DCN throttle "
                    "(every exchange sleeps latency + wire_bytes/"
                    "bandwidth on the training thread), T in {1,4,8} x "
                    "{bf16, int8-EF} wire compression, MLP-256 "
                    "DistriOptimizer K=4, identical data/seed/step "
                    "count per leg, final_loss = full-dataset loss of "
                    "the final params; T>1 legs use the Nesterov outer "
                    "update. Acceptance: t8_int8 trained rec/s >= 1.5x "
                    "t1_bf16 with final loss within loss_tolerance",
        }))
        return
    if which == "compile":
        # CPU-mesh microbench (written for 8 virtual CPU
        # devices): cold-vs-warm startup is a host-side compile-latency
        # property; the measured runs are fresh grandchild processes so
        # only the persistent cache directory is shared
        metric, unit = _METRICS[which]
        rows = _bench_compile()
        print(json.dumps({
            "metric": metric,
            "value": rows["speedup"],
            "unit": unit,
            "vs_baseline": 1.0,
            "backend": backend,
            "n_devices": len(jax.devices()),
            "batch_size": 16,
            **rows,
            "host": _host_provenance(),
            "note": "optimize() startup (entry to first flushed loss), "
                    "26-layer MLP DistriOptimizer (ZeRO-1, K=4, accum=2, "
                    "validation) on the 8-virtual-device CPU mesh, 5-batch "
                    "epochs ending in a padded tail; cold = empty "
                    "persistent cache, warm = same cache root in a fresh "
                    "process. Acceptance: speedup >= 3x and exactly 1 "
                    "fused train-step variant, tails included",
        }))
        return
    if which == "overhead":
        # CPU-mesh microbench (written for 8 virtual CPU
        # devices): what the flight recorder costs the hottest dispatch
        # path with every sink enabled — host plumbing, backend-agnostic
        metric, unit = _METRICS[which]
        rows = _bench_overhead()
        print(json.dumps({
            "metric": metric,
            "value": rows["overhead_pct"],
            "unit": unit,
            "vs_baseline": 1.0,
            "backend": backend,
            "n_devices": len(jax.devices()),
            "batch_size": 32,
            **rows,
            "host": _host_provenance(),
            "note": "throughput lost with the FULL telemetry plane on "
                    "vs fully off: span tracing + JSONL + Prometheus "
                    "exporters + statusz HTTP server scraped ~5x/s "
                    "(/statusz + /metrics + merged /fleetz + the /memz "
                    "device-memory plane) under load + step-time "
                    "watchdog armed + FLEET aggregator polling a "
                    "second in-process statusz peer every 1s + the "
                    "serve-SLO watchdog poller live + the memory "
                    "plane fully armed (buffer ledger accounting every "
                    "trainer tree + staging batch, memory-watchdog "
                    "poller live against a 1 GiB limit); same "
                    "small-model DistriOptimizer.optimize() K=8 loop "
                    "as the dispatch bench, best post-compile window "
                    "per mode, modes alternated. Scrapes read "
                    "host-side registry state only (no added host "
                    "syncs — tests/test_statusz.py). Acceptance "
                    "bar: <= 2%",
        }))
        return
    if which == "checkpoint":
        # CPU-mesh microbench (written for 8 virtual CPU
        # devices): the number is the step-boundary stall a snapshot
        # costs the train loop, which is backend-independent plumbing
        metric, unit = _METRICS[which]
        rows = _bench_checkpoint()
        sync_ms = rows["sync_v1"]["stall_ms_median"]
        async_ms = rows["async_v2"]["stall_ms_median"] or 1e-3
        print(json.dumps({
            "metric": metric,
            "value": round(sync_ms / async_ms, 1),
            "unit": unit,
            "vs_baseline": 1.0,
            "backend": backend,
            "n_devices": len(jax.devices()),
            "batch_size": 32,
            "modes": rows,
            "host": _host_provenance(),
            "note": "median checkpoint-induced step-time stall, "
                    "DistriOptimizer.optimize() on the 8-virtual-device "
                    "CPU mesh, ~1M-param MLP + Adam slots, snapshot "
                    "every 4 iterations; sync_v1 = legacy gather-to-"
                    "host-0 npz, async_v2 = resilience/ device-clone + "
                    "background sharded write (equal snapshot payload)",
        }))
        return
    if which == "lenet":
        ips = _bench_lenet()
        metric, unit = _METRICS["lenet"]
        print(json.dumps({
            "metric": metric,
            "value": round(ips, 1),
            "unit": unit,
            "vs_baseline": 1.0,
            "backend": backend,
        }))
        return
    if which == "llama":
        metric, unit = _METRICS[which]
        tps, flops, sec = _bench_llama()
        print(json.dumps({
            "metric": metric,
            "value": round(tps, 1),
            "unit": unit,
            "vs_baseline": 1.0,
            "backend": backend,
            "mfu_bf16": round(flops / sec / peak, 4),
            "model_flops_per_step": flops,
        }))
        return
    if which in ("lstm", "transformer"):
        tps = _bench_lm(which)
        metric, unit = _METRICS[which]
        print(json.dumps({
            "metric": metric,
            "value": round(tps, 1),
            "unit": unit,
            "vs_baseline": 1.0,
            "backend": backend,
        }))
        return
    if which == "resnet50_sweep":
        # bf16 batch sweep for the MFU-optimal point:
        # per-batch imgs/sec + MFU, headline = best MFU
        metric, unit = _METRICS[which]
        rows = {}
        best = (0.0, None)
        for bs in (64, 128, 256):
            try:
                ips, flops, sec, _runs = _bench_resnet50(
                    compute_dtype=jnp.bfloat16, batch_size=bs)
            except Exception as e:                      # OOM at 256 etc.
                rows[f"batch_{bs}"] = {"error": str(e)[:200]}
                continue
            mfu = flops / sec / peak
            rows[f"batch_{bs}"] = {
                "imgs_per_sec": round(ips, 1),
                "mfu": round(mfu, 4),
            }
            if mfu > best[0]:
                best = (mfu, bs)
        print(json.dumps({
            "metric": metric,
            "value": round(best[0], 4),
            "unit": unit,
            "vs_baseline": 1.0,
            "backend": backend,
            "device_kind": dev.device_kind,
            "best_batch": best[1],
            **rows,
        }))
        return
    if which == "kernels":
        metric, unit = _METRICS["kernels"]
        # fused-update + autotune warm-start run on ANY backend (the
        # fused comparison is the 8-virtual-device dispatch bench; the
        # autotuner is host-side table plumbing). The Mosaic kernel-vs-
        # XLA ratios additionally need a live TPU — interpret-mode
        # timings say nothing about Mosaic, so they stay TPU-gated.
        fused_rows = _bench_fused_update()
        fu_speedup = round(fused_rows["fused"]
                           / max(fused_rows["unfused"], 1e-9), 3)
        fu_flat_speedup = round(fused_rows["fused_flat"]
                                / max(fused_rows["unfused"], 1e-9), 3)
        fu_z1_speedup = round(fused_rows["fused_zero1"]
                              / max(fused_rows["unfused_zero1"], 1e-9), 3)
        tuned = _bench_autotune_warm()
        rec = {
            "metric": metric,
            "unit": unit,
            "vs_baseline": 1.0,
            "backend": backend,
            "n_devices": len(jax.devices()),
            "device_kind": dev.device_kind,
            "fused_update_rec_per_sec": fused_rows,
            "fused_update_speedup": fu_speedup,
            "fused_update_flat_speedup": fu_flat_speedup,
            "fused_update_zero1_speedup": fu_z1_speedup,
            "autotune": tuned,
            "host": _host_provenance(),
            "note": "fused_update_*: Adam on a 24-layer MLP through "
                    "DistriOptimizer.optimize() K=8 on the 8-virtual-"
                    "device mesh, best post-compile window. 'fused' is "
                    "the shipping auto layout (leaf on CPU — bitwise the "
                    "same math XLA fuses per leaf, so CPU parity is the "
                    "honest expectation; the flat+Pallas+donation form "
                    "this kernel exists for needs the real chip, see "
                    "fused_update_flat_speedup for what the assembly "
                    "copies cost when forced on CPU). autotune: cold "
                    "sweep in this process vs a fresh process resolving "
                    "the same shapes from the published table "
                    "(acceptance: warm_hit_rate == 1.0, warm_searches "
                    "== 0)",
        }
        ratios = _bench_kernels()
        rec.update(ratios)
        rec["value"] = round(min(ratios.values()), 3)      # worst ratio
        print(json.dumps(rec))
        return

    ips_bf16, flops_bf16, sec_bf16, runs_bf16 = _bench_resnet50(
        compute_dtype=jnp.bfloat16, n_runs=2)
    ips_fp32, flops_fp32, sec_fp32, _runs_fp32 = _bench_resnet50(
        compute_dtype=None)
    mfu_bf16 = flops_bf16 / sec_bf16 / peak
    mfu_fp32 = flops_fp32 / sec_fp32 / peak
    best = max(ips_bf16, ips_fp32)
    print(json.dumps({
        "metric": "resnet50_imagenet_train_throughput_per_chip",
        "value": round(best, 1),
        "unit": "images/sec",
        "vs_baseline": round(best / PROXY_BASELINE_IPS, 2),
        "backend": backend,
        "device_kind": dev.device_kind,
        "batch_size": 128,
        "spatial": 224,
        "imgs_per_sec_bf16": round(ips_bf16, 1),
        "imgs_per_sec_bf16_runs": runs_bf16,
        "imgs_per_sec_fp32": round(ips_fp32, 1),
        "host": _host_provenance(),
        "model_flops_per_step": flops_bf16,
        "mfu_bf16": round(mfu_bf16, 4),
        "mfu_fp32": round(mfu_fp32, 4),
        "vs_baseline_note":
            f"ratio vs ~{PROXY_BASELINE_IPS:.0f} imgs/sec fp32 proxy for the "
            "reference's 2-socket Xeon (whitepaper.md:160; no absolute "
            "numbers published in-tree)",
    }))


if __name__ == "__main__":
    main()
