"""What both kinds of cell share: the device and its peaks, the count of
compilations inside a window, the profiler trace, and the checks' record."""

import gc
import json
import os
import shutil
import time

import reduce as R

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(ROOT, "benchmark_out")      # listed in .gitignore
TRACE_SECONDS = 3.0


class NoChip(Exception):
    pass


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def peaks_for(device_kind):
    """The row of `benchmark/peaks/` that answers to this `device_kind`; a
    device that is in no row is an error, not a default."""
    folder = os.path.join(HERE, "peaks")
    for name in sorted(os.listdir(folder)):
        row = load_json(folder, name)
        if row["device_kind"] == device_kind:
            return row
    raise NoChip(f"device kind {device_kind!r} is in no file of "
                 f"benchmark/peaks/: its peaks are not known")


class Compiles:
    """Counts the programs JAX builds or loads (a persistent-cache hit is a
    program that was not warm, too), through `jax.monitoring`."""

    def __init__(self):
        from jax import monitoring
        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event.endswith("backend_compile_duration"):
            self.n += 1


def start_trace(name):
    import jax
    path = os.path.join(OUT, "trace", name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # the device planes are what is read
    opts.host_tracer_level = 1
    jax.profiler.start_trace(path, profiler_options=opts)
    return path, now()


def stop_trace(started):
    """Stop, read the device planes, and delete the files: a trace is tens
    of MB and the machine keeps every block once written. The traced span
    runs from when `start_trace` returned to this call, on the host's
    clock: the device may have been idle at either end of it."""
    import jax
    path, t_started = started
    span_s = now() - t_started
    jax.profiler.stop_trace()
    pb = R.find_xplane(path)
    planes = R.read_device_lines(pb) if pb else {}
    keep = os.environ.get("BENCH_KEEP_TRACE")
    if keep and pb:
        os.makedirs(os.path.dirname(keep) or ".", exist_ok=True)
        shutil.copy(pb, keep)
    shutil.rmtree(path, ignore_errors=True)
    return R.reduce_trace(planes, span_s)


def device_block(dev, count, trace=None):
    stats = dev.memory_stats() or {}
    out = {"platform": dev.platform, "kind": dev.device_kind, "count": count,
           "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    if trace is not None:
        out["busy_s"] = trace["busy_s"]
        out["window_s"] = trace["window_s"]
    return out


def free_device_memory():
    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()


# What the benchmark's model builder, weights and plain reference implement.
# A configuration that states anything else is refused: a new family, a new
# activation or an untied head is code (a builder and a reference), not data.
IMPLEMENTED = {"family": "gpt2", "activation_function": "gelu_new",
               "tie_word_embeddings": True}


def build_model(cfg):
    """The program's model for a configuration's sizes, and its eos id."""
    from bigdl_tpu.interop.huggingface import GPT2LM
    for key, have in IMPLEMENTED.items():
        if cfg.get(key) != have:
            raise ValueError(f"configuration states {key}={cfg.get(key)!r}; "
                             f"the benchmark implements {have!r} only")
    eos = cfg["vocab_size"] - 1
    return GPT2LM(cfg["vocab_size"], cfg["n_positions"], cfg["n_embd"],
                  cfg["n_head"], cfg["n_layer"],
                  ln_eps=cfg["layer_norm_epsilon"], eos_id=eos), eos


def named(table, key, what):
    """`table[key]`, or an error that names what the benchmark knows."""
    if key not in table:
        raise ValueError(f"{what} {key!r} is not one of {sorted(table)}")
    return table[key]


def layout_matches(model, params):
    """Fail loudly where the program's parameter tree is no longer the one
    `benchmark/weights.py` makes (shapes and dtypes, by `eval_shape`)."""
    import jax
    want, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    a = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), want)
    b = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), params)
    if a != b:
        raise RuntimeError("the program's parameter layout is not the one "
                           "benchmark/weights.py makes")


class Checks:
    """Each number compared, beside its limit. `correct` is their
    conjunction; the record goes last on the result line and on stderr."""

    def __init__(self):
        self.rows = {}

    def at_most(self, name, value, limit):
        ok = value is not None and value == value and value <= limit
        self.rows[name] = {"value": value, "limit": limit, "ok": bool(ok)}

    @property
    def correct(self):
        return bool(self.rows) and all(r["ok"] for r in self.rows.values())


def now():
    return time.monotonic()
