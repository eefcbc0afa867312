"""Profiling / per-module timing (reference: AbstractModule forward/backward
nanosecond timers + getTimes/getTimesGroupByModuleType,
nn/abstractnn/AbstractModule.scala:168-190,255-299; per-iteration phase
metrics optim/Metrics.scala; perf CLI nn/mkldnn/Perf.scala:37-126).

Two tools:
  * `module_times` — eager per-child wall time (the reference's getTimes):
    runs each direct child separately, syncing via host fetch. Under jit XLA
    fuses across modules, so this measures the un-fused upper bound — use it
    to find the hot module, then `xla_profile` for the fused truth.
  * `xla_profile` — wraps jax.profiler around a jitted fn; the trace opens
    in TensorBoard/Perfetto with per-op attribution (module names appear via
    the `jax.named_scope` each Module.apply installs).
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp


from bigdl_tpu.utils.sync import chain_dep, force_completion as _sync


def module_times(model, params, state, *inputs, repeats: int = 3,
                 training: bool = False, rng=None) -> List[Tuple[str, float]]:
    """Per-direct-child forward wall time in seconds, sorted descending
    (reference: getTimesGroupByModuleType). Works on containers whose
    children execute sequentially (Sequential); for others it times the
    whole module."""
    from bigdl_tpu.core.container import Sequential

    results: List[Tuple[str, float]] = []
    # completing a tiny op has a floor of its own (dispatch + wait; a tiny
    # op plus a host fetch measured 1.7 ms on the v5e — my chip run,
    # PR 21): measure it here and subtract it, so that small modules do
    # not all report that floor
    probe = jnp.zeros((1,))
    _sync(probe + 1.0)                     # compile the probe add untimed
    t0 = time.perf_counter()
    for _ in range(3):
        _sync(probe + 1.0)
    rtt = (time.perf_counter() - t0) / 3
    children = model.children()
    # only Sequential runs children as a chain; time anything else whole
    if not children or not isinstance(model, Sequential):
        children = {model.name: model}
        params = {model.name: params}
        state = {model.name: state}

    h = inputs
    for cname, child in children.items():
        cp = params.get(cname, {}) if isinstance(params, dict) else {}
        cs = state.get(cname, {}) if isinstance(state, dict) else {}

        def run(hh):
            out, _ = child.apply(cp, cs, *hh, training=training, rng=rng)
            return out

        out = run(h)                       # warm up / get next input
        _sync(out)
        t0 = time.perf_counter()
        hh, last = h, out
        for _ in range(repeats):
            last = run(hh)
            # a data-dependent chain: repeat i+1 starts when repeat i
            # is done (utils/sync.py)
            hh = (chain_dep(h[0], last),) + tuple(h[1:])
        _sync(last)                        # floor paid once, subtracted below
        dt = max(0.0, (time.perf_counter() - t0 - rtt)) / max(1, repeats)
        results.append((f"{cname}:{child.name}", dt))
        h = out if isinstance(out, tuple) else (out,)
    return sorted(results, key=lambda kv: -kv[1])


def format_times(times: List[Tuple[str, float]]) -> str:
    total = sum(t for _, t in times) or 1e-12
    lines = [f"{'module':<40} {'ms':>10} {'%':>6}"]
    for name, t in times:
        lines.append(f"{name:<40} {t * 1e3:>10.3f} {t / total:>6.1%}")
    return "\n".join(lines)


def xla_profile(fn: Callable, *args, logdir: str = "/tmp/bigdl_tpu_profile",
                iters: int = 3):
    """Trace `iters` calls of (jitted) `fn` into a TensorBoard profile dir
    (reference analogue: the Metrics phase timers; here XLA's own profiler
    carries per-fusion timing)."""
    out = fn(*args)                        # compile outside the trace
    _sync(out)
    with jax.profiler.trace(logdir):
        cur = args
        for _ in range(iters):
            out = fn(*cur)
            # chain the iterations (utils/sync.py), so that each shows
            # as its own stretch of the trace
            cur = (chain_dep(cur[0], out),) + tuple(cur[1:])
        _sync(out)
    return logdir


# IterationMetrics was absorbed by the flight recorder (PR 4): the same
# reference-shaped facade now lives in observe/metrics.py, optionally
# mirroring every sample into the process-wide registry so ad-hoc users
# ride the same exporters as the trainers. Re-exported here for the
# pre-existing import sites.
from bigdl_tpu.observe.metrics import IterationMetrics  # noqa: E402,F401


def device_memory_summary(device=None):
    """Per-device memory stats dict (bytes_in_use, peak_bytes_in_use,
    bytes_limit when the backend reports them — TPU/GPU do; host CPU
    returns {}). Historically this was the tree's ONLY memory reader;
    the device-memory plane absorbed it (observe/memz.py — the buffer
    ledger, /memz, watchdog, and OOM forensics all read the same
    backend probe), and this name stays as a thin shim for the
    pre-existing call sites."""
    from bigdl_tpu.observe import memz
    return memz.device_memory_summary(device)


def memory_profile(path: str) -> str:
    """Write a pprof-format device-memory profile (open with `pprof` or
    xprof). Returns the path. Routed through the memory plane's
    best-effort saver (observe/memz.py — the same writer OOM forensics
    uses for `memory.prof`); raises when the profiler cannot write."""
    from bigdl_tpu.observe import memz
    out = memz.save_memory_profile(path)
    if out is None:
        raise RuntimeError(
            f"jax.profiler.save_device_memory_profile({path!r}) failed "
            f"(see the bigdl_tpu log for the cause)")
    return out
