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


class Refused(ValueError):
    """A name that the benchmark's directories do not hold, or a
    configuration that asks its family for what it does not implement: the
    run ends with this message and prints no result."""


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def peaks_for(device_kind, dirs=(HERE,)):
    """The row of `benchmark/peaks/` that answers to this `device_kind`; a
    device that is in no row is an error, not a default."""
    for name in names_in(dirs, "peaks"):
        row = load_json(found(dirs, "peaks", name + ".json", "chip"))
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
    trace = R.reduce_trace(planes, span_s)
    if trace is not None:
        trace["span_at"], trace["span_s"] = t_started, span_s
    return trace


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


def find(dirs, folder, filename):
    """The first `<dir>/<folder>/<filename>` that exists, over the
    directories `BENCHMARK.json` lists under `paths`; None if none does."""
    for d in dirs:
        path = os.path.join(d, folder, filename)
        if os.path.isfile(path):
            return path
    return None


def names_in(dirs, folder):
    """The names (without endings) that `<dir>/<folder>/` hold."""
    return sorted({os.path.splitext(f)[0] for d in dirs
                   if os.path.isdir(os.path.join(d, folder))
                   for f in os.listdir(os.path.join(d, folder))
                   if not f.startswith(("_", "."))})


def found(dirs, folder, filename, what):
    """`find`, or the refusal that lists what `<folder>/` holds."""
    path = find(dirs, folder, filename)
    if path is None:
        raise Refused(f"{what} {os.path.splitext(filename)[0]!r} is not in "
                      f"{folder}/ of the benchmark (it holds "
                      f"{names_in(dirs, folder)})")
    return path


_LOADED = {}        # path -> module: a plug-in is executed once a process


def plug_in(dirs, folder, name, what):
    """The module `<folder>/<name>` (one `.py` file, or a package with an
    `__init__.py`) of the benchmark's directories, loaded from its file: a
    family, a layer reader. A name they do not hold is refused with the
    list of those they do."""
    import importlib.util
    path = find(dirs, os.path.join(folder, name), "__init__.py") \
        or found(dirs, folder, name + ".py", what)
    if path not in _LOADED:
        spec = importlib.util.spec_from_file_location(
            f"benchmark_{folder}_{name}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _LOADED[path] = module
    return _LOADED[path]


def family_of(cfg, dirs=(HERE,)):
    """The family a configuration names: the module that holds its model
    builder, weights, plain reference and shape functions (PERF.md, section
    3). It refuses a configuration that states what it does not implement."""
    family = plug_in(dirs, "families", str(cfg.get("family")), "family")
    try:
        family.check(cfg)
    except ValueError as e:
        raise Refused(str(e)) from e
    return family


def control_of(family, control, faults=()):
    """`--control <mode>` as the cell can run it: an arithmetic mode that the
    family's reference implements, or one of the cell's own `faults`."""
    if control is None or control in faults or control in family.MODES:
        return control
    raise Refused(f"control {control!r}: the family's reference has the "
                  f"modes {list(family.MODES)}"
                  + (f" and the cell the faults {list(faults)}"
                     if faults else ""))


def named(table, key, what):
    """`table[key]`, or an error that names what the benchmark knows."""
    if key not in table:
        raise ValueError(f"{what} {key!r} is not one of {sorted(table)}")
    return table[key]


def layout_matches(model, params):
    """Fail loudly where the program's parameter tree is no longer the one
    the family's `program_params` makes (shapes and dtypes, by
    `eval_shape`)."""
    import jax
    want, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    a = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), want)
    b = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), params)
    if a != b:
        raise RuntimeError("the program's parameter layout is not the one "
                           "the family's program_params makes")


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
