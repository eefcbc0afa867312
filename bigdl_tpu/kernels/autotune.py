"""Shape-keyed persistent kernel autotuner.

Every Pallas kernel in this package ships with hard-coded block-size
defaults (`flash_attention` 128/128, `int8_matmul` 256^3, ...) — guesses
that are paid per shape per process: a wrong guess costs MXU/VPU
utilization on every step, and re-deriving a better one by hand does not
survive the process. The reference framework shipped its equivalents
(MKL/bigquant block choices) baked into native code (SURVEY §2.14); the
TPU-native answer is to SEARCH the small block-size space once per
(kernel, shape, device) and persist the winner.

By default the table lives NEXT TO the XLA compile cache
(`<compile cache dir>/autotune/`):

  * committed entries are one JSON file each
    (``tune_<kernel>-<key16>.json``), written into a per-process staging
    dir and published via ``os.replace`` — a reader sees a whole entry
    or no entry, never a torn one;
  * staging dirs of dead processes are adopted (finished entries
    published) and swept on the next attach;
  * same key == same winner, so concurrent writers racing on one entry
    are idempotent — last rename wins, both files are complete.

Call sites consult the table at TRACE time (shapes are concrete there),
so a lookup is paid once per compiled program, never per step. On a
table miss with BIGDL_TPU_AUTOTUNE=1 the search runs inside
``jax.ensure_compile_time_eval()`` — candidate kernels execute eagerly
even when the caller is mid-trace — and the winner is recorded; with
the knob off, lookups return the caller's defaults untouched (bit-for-
bit the pre-autotuner behavior).

Observability (rides the flush cadence, no per-step host syncs):
``autotune/hits``, ``autotune/misses``, ``autotune/search_seconds``
counters plus an ``autotune/search/<kernel>`` duration span per search.

CLI: ``python -m bigdl_tpu.kernels {tune,stats,clear}``.
"""

from __future__ import annotations

import atexit
import hashlib
import json
import logging
import os
import shutil
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from bigdl_tpu.utils.threads import make_lock

log = logging.getLogger("bigdl_tpu")

_PREFIX = "tune_"
_SUFFIX = ".json"
_STAGING_PREFIX = ".staging-p"

_state: Dict = {"root": None, "staging": None, "table": {},
                "loaded_root": None, "searches": 0}
# _state is shared by every Pallas call site AND the autotune-search
# thread hop — writes go under this lock (lockset-checked by the
# concurrency sanitizer, analysis/sancov.py)
_table_lock = make_lock("autotune.table")
_atexit_registered = False


# ------------------------------------------------------------------ keys
def canonical_key(kernel: str, shape: Dict) -> str:
    """Stable string key for one (kernel, shape) point: sorted k=v pairs.
    `shape` values must be ints/strs/bools — the caller's static call
    signature, not arrays."""
    parts = ",".join(f"{k}={shape[k]}" for k in sorted(shape))
    return f"{kernel}({parts})"


def _entry_name(key: str) -> str:
    h = hashlib.sha1(key.encode()).hexdigest()[:16]
    kernel = key.split("(", 1)[0]
    return f"{_PREFIX}{kernel}-{h}{_SUFFIX}"


def device_signature() -> str:
    """The hardware the tuning is valid for — block-size winners for one
    chip generation must not leak onto another (or onto the CPU
    interpreter)."""
    import jax
    try:
        dev = jax.devices()[0]
        return f"{jax.default_backend()}:{getattr(dev, 'device_kind', '?')}"
    except Exception:                    # noqa: BLE001 — backend init failed
        return "unknown"


# ------------------------------------------------------------- persistence
def _default_root() -> Optional[str]:
    from bigdl_tpu.utils import config
    root = config.get("AUTOTUNE_CACHE")
    if root:
        return root
    from bigdl_tpu import compilecache
    if compilecache.enabled():
        return os.path.join(compilecache.cache_dir(), "autotune")
    return None


def _entries(d: str) -> List[str]:
    try:
        names = os.listdir(d)
    except OSError:
        return []
    return sorted(n for n in names
                  if n.startswith(_PREFIX) and n.endswith(_SUFFIX))


def _staging_dirs(root: str) -> List[str]:
    try:
        names = os.listdir(root)
    except OSError:
        return []
    return sorted(n for n in names if n.startswith(_STAGING_PREFIX))


def _staging_pid(name: str) -> Optional[int]:
    try:
        return int(name.rsplit("-", 1)[1])
    except (IndexError, ValueError):
        return None


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except OSError:
        return True
    return True


def _publish(staging: str, root: str) -> int:
    """Atomically commit finished staging entries into the root: the
    ``os.replace`` IS the commit. The
    newer file wins on a racing key — both racers hold a complete entry
    for the same (kernel, shape, device), so either winner is valid."""
    published = 0
    for name in _entries(staging):
        src = os.path.join(staging, name)
        dst = os.path.join(root, name)
        try:
            tmp = f"{dst}.tmp.{os.getpid()}"
            shutil.copy2(src, tmp)
            os.replace(tmp, dst)
            os.unlink(src)
            published += 1
        except OSError as e:             # best-effort, never fatal
            log.warning("autotune publish of %s failed: %s", name, e)
    return published


def _sweep_dead_staging(root: str) -> int:
    swept = 0
    for name in _staging_dirs(root):
        pid = _staging_pid(name)
        if pid is None or _pid_alive(pid):
            continue
        d = os.path.join(root, name)
        _publish(d, root)                # adopt finished entries
        shutil.rmtree(d, ignore_errors=True)
        swept += 1
    return swept


def _attach(root: Optional[str] = None) -> Optional[str]:
    """Point this process at a table root (idempotent per root): sweep
    dead staging dirs, create our own, load the committed entries."""
    root = root if root is not None else _default_root()
    if not root:
        return None
    root = os.path.abspath(root)
    if _state["root"] == root:
        return root
    os.makedirs(root, exist_ok=True)
    _sweep_dead_staging(root)
    from bigdl_tpu.utils.runtime import process_index
    staging = os.path.join(
        root, f"{_STAGING_PREFIX}{process_index()}-{os.getpid()}")
    os.makedirs(staging, exist_ok=True)
    with _table_lock:
        _state.update(root=root, staging=staging)
    global _atexit_registered
    if not _atexit_registered:
        atexit.register(sync)
        _atexit_registered = True
    _load(root)
    return root


def _load(root: str) -> int:
    """(Re)load the committed table into the in-memory dict. Entries are
    whole files (atomic rename publish), so a parse failure means real
    corruption — skip it loudly rather than die."""
    table = {}
    for name in _entries(root):
        path = os.path.join(root, name)
        try:
            with open(path) as fh:
                rec = json.load(fh)
            table[rec["key"]] = rec
        except (OSError, ValueError, KeyError) as e:
            log.warning("autotune table entry %s unreadable: %s", name, e)
    with _table_lock:
        _state["table"] = table
        _state["loaded_root"] = root
    return len(table)


def refresh() -> int:
    """Re-scan the root (another process may have published since)."""
    root = _state["root"]
    return _load(root) if root else 0


def _record(key: str, rec: Dict) -> None:
    """Commit one winner: in-memory immediately, on disk via a staged
    temp file + ONE atomic `os.replace` into the root — the rename IS
    the commit, so a concurrent reader sees a whole entry or no entry.
    The temp name carries pid AND thread id: two threads of one process
    racing on a key must not publish each other's half-written files."""
    with _table_lock:
        from bigdl_tpu.analysis import sancov
        if sancov.LOCKS_ON:        # lockset seed: the autotune table
            sancov.check_owned(_table_lock, "autotune.table")
        _state["table"][key] = rec
    root, staging = _state["root"], _state["staging"]
    if root is None or staging is None:
        return
    import threading
    name = _entry_name(key)
    tmp = os.path.join(
        staging, f"{name}.tmp.{os.getpid()}.{threading.get_ident()}")
    try:
        with open(tmp, "w") as fh:
            json.dump(rec, fh)
        os.replace(tmp, os.path.join(root, name))
    except OSError as e:
        log.warning("autotune record of %s failed: %s", key, e)
        try:
            os.unlink(tmp)
        except OSError:
            pass


def sync() -> int:
    """Publish any unpublished staging entries (atexit / explicit)."""
    root, staging = _state["root"], _state["staging"]
    if root is None or staging is None or not os.path.isdir(staging):
        return 0
    return _publish(staging, root)


def detach() -> None:
    """Drop the root binding and this process's staging dir (tests)."""
    sync()
    staging = _state["staging"]
    with _table_lock:
        _state.update(root=None, staging=None, table={}, loaded_root=None,
                      searches=0)
    if staging:
        shutil.rmtree(staging, ignore_errors=True)


def stats(root: Optional[str] = None) -> Dict:
    """Inventory of a table root: entries per kernel + staging dirs."""
    root = os.path.abspath(root or _default_root() or "")
    out: Dict = {"root": root, "entries": 0, "kernels": {}, "staging": [],
                 "device_signatures": {}}
    if not root or not os.path.isdir(root):
        return out
    for name in _entries(root):
        try:
            with open(os.path.join(root, name)) as fh:
                rec = json.load(fh)
        except (OSError, ValueError):
            continue
        out["entries"] += 1
        kern = rec.get("kernel", name)
        out["kernels"][kern] = out["kernels"].get(kern, 0) + 1
        dev = rec.get("device", "?")
        out["device_signatures"][dev] = \
            out["device_signatures"].get(dev, 0) + 1
    for name in _staging_dirs(root):
        pid = _staging_pid(name)
        out["staging"].append({
            "dir": name, "pid": pid,
            "alive": bool(pid and _pid_alive(pid)),
            "pending": len(_entries(os.path.join(root, name)))})
    return out


def clear(root: Optional[str] = None) -> int:
    """Remove every committed entry + staging dir under the root."""
    root = os.path.abspath(root or _default_root() or "")
    if not root or not os.path.isdir(root):
        return 0
    removed = len(_entries(root))
    for name in os.listdir(root):
        path = os.path.join(root, name)
        if name.startswith(_STAGING_PREFIX):
            shutil.rmtree(path, ignore_errors=True)
        elif ((name.startswith(_PREFIX) and _SUFFIX in name)
              or ".tmp." in name):
            try:
                os.unlink(path)
            except OSError:
                pass
    if _state["loaded_root"] == root:
        with _table_lock:
            _state["table"] = {}
    return removed


# ------------------------------------------------------------------ search
def _enabled() -> bool:
    from bigdl_tpu.utils import config
    return bool(config.get("AUTOTUNE"))


def _time_once(fn: Callable, iters: int = 3) -> float:
    """Best-of-iters wall time of `fn()` (after one warmup call that
    eats compile), each call waited to completion — the discipline of
    utils/sync.time_steps, sized for a
    block-size comparison rather than a publishable benchmark."""
    import jax
    jax.block_until_ready(fn())          # compile + warm
    best = float("inf")
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _try_candidates(kernel, shape, candidates, make_runner):
    """Time every candidate; returns (best_cfg, best_s, tried). MUST run
    with a clean jax trace state — the candidates execute eagerly."""
    best_cfg, best_s, tried = None, None, 0
    ops = None
    for cfg in candidates(shape):
        try:
            runner, ops = make_runner(shape, cfg, ops)
            sec = _time_once(runner)
        except Exception as e:           # noqa: BLE001 — cfg invalid here
            log.debug("autotune %s %s candidate %s failed: %s",
                      kernel, shape, cfg, e)
            continue
        tried += 1
        if best_s is None or sec < best_s:
            best_cfg, best_s = dict(cfg), sec
    return best_cfg, best_s, tried


def _search(kernel: str, shape: Dict, defaults: Dict) -> Dict:
    """Run the registered searcher: time every candidate config, return
    the winner record. Call sites usually sit INSIDE a jit trace (shapes
    are concrete at trace time); jax's trace state is thread-local, so
    the search always hops to a worker thread, whose state is clean, and
    the candidates run eagerly there."""
    from bigdl_tpu import observe
    searcher = _SEARCHERS.get(kernel)
    key = canonical_key(kernel, shape)
    t0 = time.perf_counter()
    best_cfg, best_s, tried = dict(defaults), None, 0
    if searcher is not None:
        candidates, make_runner = searcher
        with observe.phase(f"autotune/search/{kernel}", cat="kernel"):
            box: Dict = {}

            def run():
                try:
                    box["out"] = _try_candidates(
                        kernel, shape, candidates, make_runner)
                except Exception as e:       # noqa: BLE001
                    box["err"] = e
            from bigdl_tpu.utils.threads import spawn
            # joined immediately: the hop exists only for a clean
            # thread-local jax trace state, so non-daemon is safe
            t = spawn(run, name="autotune-search", daemon=False)
            t.join()
            if "err" in box:
                log.warning("autotune search for %s failed: %s",
                            key, box["err"])
                got, best_s, tried = None, None, 0
            else:
                got, best_s, tried = box["out"]
            if got is not None:
                best_cfg = got
    search_s = time.perf_counter() - t0
    with _table_lock:
        _state["searches"] += 1
    observe.counter("autotune/search_seconds").inc(search_s)
    rec = {"key": key, "kernel": kernel, "shape": dict(shape),
           "config": best_cfg, "device": device_signature(),
           "best_seconds": best_s, "candidates_tried": tried,
           "search_seconds": round(search_s, 4),
           "created": time.time()}
    log.info("autotune %s: %d candidates in %.2fs -> %s",
             key, tried, search_s, best_cfg)
    return rec


def lookup(kernel: str, shape: Dict, defaults: Dict) -> Dict:
    """The call-site entry point: tuned config for (kernel, shape) or
    `defaults`. With BIGDL_TPU_AUTOTUNE unset this IS `defaults` —
    zero behavioral change. Enabled: consult the table (hit), else
    search-and-record (miss). Only config keys present in `defaults`
    are returned, so a stale table schema cannot inject garbage."""
    if not _enabled():
        return dict(defaults)
    from bigdl_tpu import observe
    _attach()
    shape = dict(shape, device=device_signature())
    key = canonical_key(kernel, shape)
    rec = _state["table"].get(key)
    if rec is not None:
        observe.counter("autotune/hits").inc()
        cfg = rec.get("config", {})
        return {k: cfg.get(k, v) for k, v in defaults.items()}
    observe.counter("autotune/misses").inc()
    rec = _search(kernel, shape, defaults)
    _record(key, rec)
    cfg = rec["config"]
    return {k: cfg.get(k, v) for k, v in defaults.items()}


def tune(kernel: str, shape: Dict, defaults: Optional[Dict] = None,
         force: bool = False) -> Dict:
    """Offline sweep for one (kernel, shape) — the CLI/bench entry.
    Unlike `lookup` this ignores the BIGDL_TPU_AUTOTUNE gate (calling
    it IS the opt-in) and can `force` a re-search of a present key."""
    from bigdl_tpu import observe
    _attach()
    defaults = dict(defaults or _DEFAULTS.get(kernel, {}))
    shape = dict(shape, device=device_signature())
    key = canonical_key(kernel, shape)
    if not force and key in _state["table"]:
        observe.counter("autotune/hits").inc()
        return _state["table"][key]
    observe.counter("autotune/misses").inc()
    rec = _search(kernel, shape, defaults)
    _record(key, rec)
    return rec


def process_search_count() -> int:
    """Searches performed by THIS process (the warm-start acceptance
    probe: a fresh process on a warm table must report 0)."""
    return _state["searches"]


# ----------------------------------------------------- kernel search spaces
def _pow2_leq(cap: int, lo: int = 32, hi: int = 512) -> List[int]:
    out = [b for b in (32, 64, 128, 256, 512) if lo <= b <= min(cap, hi)]
    return out or [lo]


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def _interpret() -> bool:
    import jax
    return jax.default_backend() != "tpu"


def _flash_candidates(shape: Dict) -> List[Dict]:
    qs = _pow2_leq(_round_up(shape["tq"], 32))
    ks = _pow2_leq(_round_up(shape["tk"], 32))
    return [{"block_q": bq, "block_k": bk} for bq in qs for bk in ks]


def _flash_runner(shape: Dict, cfg: Dict, ops):
    import jax
    import numpy as np
    import jax.numpy as jnp
    if ops is None:
        r = np.random.RandomState(0)
        dt = shape.get("dtype", "float32")
        ops = tuple(jnp.asarray(
            r.randn(shape["b"], shape["h"], t, shape["d"]), dt)
            for t in (shape["tq"], shape["tk"], shape["tk"]))
    from bigdl_tpu.kernels.flash_attention import _flash_attention
    q, k, v = ops
    interp = _interpret()
    fn = jax.jit(lambda q, k, v: _flash_attention(
        q, k, v, cfg["block_q"], cfg["block_k"], bool(shape["causal"]),
        None, interp))
    return (lambda: fn(q, k, v)), ops


def _cce_candidates(shape: Dict) -> List[Dict]:
    ns = [b for b in (32, 64, 128, 256) if shape["n"] % b == 0]
    vs = _pow2_leq(_round_up(shape["v"], 128), lo=128, hi=2048) \
        + ([1024, 2048] if shape["v"] >= 1024 else [])
    vs = sorted({b for b in vs if b <= _round_up(shape["v"], 128)})
    return [{"block_n": bn, "block_v": bv}
            for bn in (ns or [min(shape["n"], 128)]) for bv in vs]


def _cce_runner(shape: Dict, cfg: Dict, ops):
    import jax
    import numpy as np
    import jax.numpy as jnp
    if ops is None:
        r = np.random.RandomState(0)
        h = jnp.asarray(r.randn(shape["n"], shape["d"]), jnp.float32)
        w = jnp.asarray(r.randn(shape["v"], shape["d"]) * 0.1, jnp.float32)
        lab = jnp.asarray(r.randint(0, shape["v"], shape["n"]), jnp.int32)
        ops = (h, w, lab)
    from bigdl_tpu.kernels.cut_cross_entropy import _cut_cross_entropy
    h, w, lab = ops
    interp = _interpret()
    fn = jax.jit(lambda h, w, lab: _cut_cross_entropy(
        h, w, lab, cfg["block_n"], cfg["block_v"], interp))
    return (lambda: fn(h, w, lab)), ops


def _qmm_candidates(shape: Dict) -> List[Dict]:
    ms = _pow2_leq(_round_up(shape["m"], 32), hi=512)
    ns = _pow2_leq(_round_up(shape["n"], 128), lo=128, hi=512)
    ks = _pow2_leq(_round_up(shape["k"], 128), lo=128, hi=512)
    return [{"block_m": bm, "block_n": bn, "block_k": bk}
            for bm in ms for bn in ns for bk in ks]


def _qmm_runner(shape: Dict, cfg: Dict, ops):
    import jax
    import numpy as np
    import jax.numpy as jnp
    if ops is None:
        r = np.random.RandomState(0)
        ops = (jnp.asarray(r.randint(-127, 128, (shape["m"], shape["k"])),
                           jnp.int8),
               jnp.asarray(r.randint(-127, 128, (shape["k"], shape["n"])),
                           jnp.int8),
               jnp.asarray((r.rand(shape["m"], 1) + 0.5) / 100, jnp.float32),
               jnp.asarray((r.rand(1, shape["n"]) + 0.5) / 100, jnp.float32))
    from bigdl_tpu.kernels.quantized_matmul import int8_matmul
    xq, wq, sx, sw = ops
    interp = _interpret()
    fn = jax.jit(lambda a, b, s1, s2: int8_matmul(
        a, b, s1, s2, block_m=cfg["block_m"], block_n=cfg["block_n"],
        block_k=cfg["block_k"], interpret=interp))
    return (lambda: fn(xq, wq, sx, sw)), ops


def _fused_update_candidates(shape: Dict) -> List[Dict]:
    rows = max(8, _round_up(shape["n"], 128) // 128)
    cands = [b for b in (64, 256, 1024, 4096) if b <= _round_up(rows, 8)]
    return [{"block_rows": b} for b in (cands or [8])]


def _fused_update_runner(shape: Dict, cfg: Dict, ops):
    import jax
    import numpy as np
    import jax.numpy as jnp
    from bigdl_tpu.kernels import fused_update as _fu
    kind = shape["kind"]
    n = shape["n"]
    if ops is None:
        r = np.random.RandomState(0)
        mk = lambda: jnp.asarray(r.randn(n) * 0.01, jnp.float32)  # noqa: E731
        nslots = {"adam": 2, "adamw": 2}.get(kind, 1)
        ops = (mk(), mk()) + tuple(mk() for _ in range(nslots))
    hyper = _fu.bench_hyper(kind)
    use_pallas = not _interpret()
    fn = jax.jit(lambda p, g, *s: _fu.flat_update(
        kind, hyper, p, g, s, jnp.float32(1e-3), jnp.int32(3),
        block_rows=cfg["block_rows"], use_pallas=use_pallas,
        interpret=False))
    p, g = ops[0], ops[1]
    slots = ops[2:]
    return (lambda: fn(p, g, *slots)), ops


# candidate generator + runner factory per kernel; a runner factory takes
# (shape, cfg, cached_ops) and returns (zero-arg runner, cached_ops) so
# the synthetic operands are materialized once per search
_SEARCHERS: Dict[str, Tuple[Callable, Callable]] = {
    "flash_attention": (_flash_candidates, _flash_runner),
    "cut_cross_entropy": (_cce_candidates, _cce_runner),
    "int8_matmul": (_qmm_candidates, _qmm_runner),
    "fused_update": (_fused_update_candidates, _fused_update_runner),
}

# the hard-coded call-site defaults each kernel falls back to — also what
# the CLI sweeps start from
_DEFAULTS: Dict[str, Dict] = {
    "flash_attention": {"block_q": 128, "block_k": 128},
    "cut_cross_entropy": {"block_n": 128, "block_v": 512},
    "int8_matmul": {"block_m": 256, "block_n": 256, "block_k": 256},
    "fused_update": {"block_rows": 512},
}

# named shape sets for the offline CLI sweep (python -m bigdl_tpu.kernels
# tune SET): "smoke" is CPU-interpreter-sized, "bench" holds shapes of
# real-hardware size
SHAPE_SETS: Dict[str, Sequence[Tuple[str, Dict]]] = {
    "smoke": (
        ("flash_attention", {"b": 2, "h": 2, "tq": 64, "tk": 64, "d": 32,
                             "causal": 1, "dtype": "float32"}),
        ("cut_cross_entropy", {"n": 32, "d": 16, "v": 64,
                               "dtype": "float32"}),
        ("int8_matmul", {"m": 32, "k": 64, "n": 32}),
        ("fused_update", {"kind": "adam", "n": 4096, "dtype": "float32"}),
    ),
    "bench": (
        ("flash_attention", {"b": 4, "h": 8, "tq": 2048, "tk": 2048,
                             "d": 64, "causal": 1, "dtype": "float32"}),
        ("cut_cross_entropy", {"n": 4096, "d": 512, "v": 50257,
                               "dtype": "float32"}),
        ("int8_matmul", {"m": 1024, "k": 4096, "n": 4096}),
        ("fused_update", {"kind": "adam", "n": 1 << 20,
                          "dtype": "float32"}),
    ),
}


def tune_set(name: str, force: bool = False) -> List[Dict]:
    """Sweep every (kernel, shape) of a named set; returns the records."""
    if name not in SHAPE_SETS:
        raise KeyError(f"unknown shape set {name!r}; "
                       f"have {sorted(SHAPE_SETS)}")
    return [tune(kernel, shape, force=force)
            for kernel, shape in SHAPE_SETS[name]]
