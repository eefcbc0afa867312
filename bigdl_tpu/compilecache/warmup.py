"""AOT warmup helpers: compile-from-specs plumbing shared by the
trainers' `precompile()` (optim/local.py).

`jit(...).lower(specs).compile()` produces a ready executable before any
real batch exists — the first training iteration then dispatches instead
of paying trace + XLA compile. With the persistent cache enabled
(cache.py) the compile itself is also skipped on warm starts, so
`precompile()` on a warm machine costs milliseconds.

The compiled object's XLA cost analysis (flops, bytes accessed, peak
memory) is routed into the observe metrics registry under
`compile/<program>/...`, available for every trainer program at warmup
time.
"""

from __future__ import annotations

import logging
from typing import Dict, Optional

log = logging.getLogger("bigdl_tpu")


def sds_like(x):
    """ShapeDtypeStruct mirroring a concrete array / numpy batch."""
    import jax
    import numpy as np
    if not hasattr(x, "shape") or not hasattr(x, "dtype"):
        x = np.asarray(x)
    return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)


def key_sds():
    """Spec of a raw PRNG key (derived from a real key so the typed-key
    config, if ever flipped, stays consistent)."""
    import jax
    k = jax.random.PRNGKey(0)  # tpu-lint: disable=004
    return jax.ShapeDtypeStruct(tuple(k.shape), k.dtype)


def scalar_sds(dtype):
    import jax
    return jax.ShapeDtypeStruct((), dtype)


def cost_summary(compiled) -> Dict[str, Optional[float]]:
    """Flops / bytes-accessed / peak-memory of a compiled executable.
    Every field is best-effort: backends differ in what they report."""
    out: Dict[str, Optional[float]] = {
        "flops": None, "bytes_accessed": None, "peak_memory_bytes": None,
        "generated_code_bytes": None}
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        cost = cost or {}
        if "flops" in cost:
            out["flops"] = float(cost["flops"])
        if "bytes accessed" in cost:
            out["bytes_accessed"] = float(cost["bytes accessed"])
    except Exception:                    # noqa: BLE001 — backend-specific
        pass
    try:
        mem = compiled.memory_analysis()
        if mem is not None:
            peak = sum(
                float(getattr(mem, f, 0) or 0)
                for f in ("temp_size_in_bytes", "output_size_in_bytes",
                          "argument_size_in_bytes"))
            out["peak_memory_bytes"] = peak
            out["generated_code_bytes"] = float(
                getattr(mem, "generated_code_size_in_bytes", 0) or 0)
    except Exception:                    # noqa: BLE001
        pass
    return out


def precompile_buckets(jitted, params, state, feature_shape, dtype,
                       buckets, *, name: str = "serve", mesh=None):
    """AOT-lower one inference program per shape bucket — the serving
    subsystem's warmup entry point (bigdl_tpu/serve/registry.py).

    `jitted` is a `jax.jit` of `fn(params, state, x, valid)` where `x`
    is `(bucket,) + feature_shape` and `valid` a `(bucket,)` bool mask;
    every bucket in `buckets` is lowered + compiled from eval-shape
    specs (zero device work), its XLA cost analysis logged under
    `compile/<name>/bucket<B>/...`. With a mesh, the batch specs carry
    the composed batch-axis sharding and params/state replicate — the
    same pinning discipline as DistriOptimizer._annotate_aot_specs, so
    the executables accept the live placed arrays.

    Returns `(results, executables)`: per-bucket cost summaries and the
    compiled executables keyed by bucket size, ready for dispatch."""
    import time as _time
    import jax
    import numpy as np

    sh = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from bigdl_tpu.parallel.sharding import batch_spec
        rep = NamedSharding(mesh, P())
        sh = {"rep": rep,
              "x": lambda nd: NamedSharding(mesh, batch_spec(mesh, nd))}

    def spec(x, sharding=None):
        s = sds_like(x)
        if sharding is None:
            return s
        return jax.ShapeDtypeStruct(tuple(s.shape), s.dtype,
                                    sharding=sharding)

    p_s = jax.tree.map(lambda a: spec(a, sh and sh["rep"]), params)
    s_s = jax.tree.map(lambda a: spec(a, sh and sh["rep"]), state)
    dtype = np.dtype(dtype)
    results: Dict[int, Dict] = {}
    executables: Dict[int, object] = {}
    for b in sorted(set(int(v) for v in buckets)):
        x_s = jax.ShapeDtypeStruct((b,) + tuple(feature_shape), dtype,
                                   **({"sharding": sh["x"](
                                       1 + len(feature_shape))}
                                      if sh else {}))
        v_s = jax.ShapeDtypeStruct((b,), np.bool_,
                                   **({"sharding": sh["x"](1)}
                                      if sh else {}))
        t0 = _time.perf_counter()
        compiled = jitted.lower(p_s, s_s, x_s, v_s).compile()
        executables[b] = compiled
        results[b] = log_cost(f"{name}/bucket{b}", compiled,
                              _time.perf_counter() - t0)
    return results, executables


def precompile_fixed(jitted, args_specs, *, name: str):
    """AOT-lower ONE program with an arbitrary (already spec'd) argument
    tuple — the decode-serving warmup entry point (serve/decode.py):
    unlike `precompile_buckets` the signature is not the bucket-forward
    `(params, state, x, valid)`, so the caller supplies the full spec
    tuple (ShapeDtypeStructs, shardings pinned if meshed). Cost analysis
    is logged under `compile/<name>/...`; returns (cost_summary,
    executable)."""
    import time as _time
    t0 = _time.perf_counter()
    compiled = jitted.lower(*args_specs).compile()
    summary = log_cost(name, compiled, _time.perf_counter() - t0)
    return summary, compiled


def log_cost(name: str, compiled, elapsed_s: float) -> Dict:
    """Record a precompiled program's cost analysis into the metrics
    registry (`compile/<name>/...` gauges) and the log."""
    from bigdl_tpu import observe
    summary = cost_summary(compiled)
    g = observe.gauge
    for field, value in summary.items():
        if value is not None:
            g(f"compile/{name}/{field}").set(value)
    g(f"compile/{name}/compile_seconds").set(elapsed_s)
    observe.counter("compile/precompiled_programs").inc()
    flops = summary.get("flops")
    by = summary.get("bytes_accessed")
    peak = summary.get("peak_memory_bytes")
    log.info(
        "precompiled %s in %.2fs: %s flops, %s bytes accessed, "
        "%s peak bytes", name, elapsed_s,
        f"{flops:.3g}" if flops is not None else "?",
        f"{by:.3g}" if by is not None else "?",
        f"{peak:.3g}" if peak is not None else "?")
    summary["compile_seconds"] = elapsed_s
    return summary
