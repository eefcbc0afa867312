"""Process-wide metrics registry — counters, gauges, log-bucket histograms.

The reference accumulates per-phase driver metrics in
`optim/Metrics.scala` (set/add per phase, summary string). Here the
registry is the single sink every subsystem reports into — trainers,
placement, the snapshot writer, fault injection — and the exporters
(observe/export.py) read consistent snapshots from it on a background
cadence.

Cadence contract: instrumentation only ever records values that are
ALREADY on host (wall-clock phase timings, byte counts, the loss floats
`_flush_metrics` fetched on its existing cadence). Nothing in this module
touches a device value, so enabling metrics adds **no host syncs** to the
train loop — asserted by tests/test_observe.py.

Histograms are log-bucketed (geometric boundaries), so a week-long run's
latency distribution lives in ~40 ints instead of an unbounded sample
list — this is what absorbs the `_ckpt_stalls: List[float]` that used to
grow forever (optim/local.py).
"""

from __future__ import annotations

import math
import time
from typing import Callable, Dict, List, Optional, Tuple

from bigdl_tpu.utils.threads import make_lock

_lock = make_lock("observe.metrics")

# concurrency-sanitizer hook (analysis/sancov.py): when the sync mode is
# on it installs a fn(name, entering) here so device->host fetches can
# be attributed to the innermost live phase span; None costs one load
_phase_hook: Optional[Callable[[str, bool], None]] = None


def set_phase_hook(fn: Optional[Callable[[str, bool], None]]) -> None:
    global _phase_hook
    _phase_hook = fn


class Counter:
    """Monotonic accumulator (events, bytes, seconds-of-X)."""

    __slots__ = ("name", "_value")

    def __init__(self, name: str):
        self.name = name
        self._value = 0.0

    def inc(self, v: float = 1.0) -> None:
        with _lock:
            self._value += v

    @property
    def value(self) -> float:
        return self._value

    def snapshot(self):
        return self._value


class Gauge:
    """Last-written value (queue depth, current loss, current step)."""

    __slots__ = ("name", "_value")

    def __init__(self, name: str):
        self.name = name
        self._value = 0.0

    def set(self, v: float) -> None:
        self._value = float(v)

    @property
    def value(self) -> float:
        return self._value

    def snapshot(self):
        return self._value


# default bounds: 1 µs .. ~137 s, ×2 per bucket (28 buckets + overflow) —
# wide enough for dispatch latencies and checkpoint stalls alike
_DEFAULT_BOUNDS = tuple(1e-6 * 2 ** i for i in range(28))


class Histogram:
    """Log-bucket histogram: counts per geometric bucket + running
    sum/min/max. Bounded memory for any run length; quantiles are
    bucket-resolution approximations (a factor-2 grid resolves p50/p99
    to within 2x, plenty for "where did the step go")."""

    __slots__ = ("name", "bounds", "counts", "_sum", "_sumsq", "_count",
                 "_min", "_max")

    def __init__(self, name: str, bounds: Optional[Tuple[float, ...]] = None):
        self.name = name
        self.bounds = tuple(bounds) if bounds is not None else _DEFAULT_BOUNDS
        if list(self.bounds) != sorted(self.bounds):
            raise ValueError(f"histogram bounds must ascend: {self.bounds}")
        self.counts = [0] * (len(self.bounds) + 1)   # +1 = overflow bucket
        self._sum = 0.0
        self._sumsq = 0.0
        self._count = 0
        self._min = math.inf
        self._max = -math.inf

    def _bucket(self, v: float) -> int:
        # binary search: bucket i holds v <= bounds[i]
        lo, hi = 0, len(self.bounds)
        while lo < hi:
            mid = (lo + hi) // 2
            if v <= self.bounds[mid]:
                hi = mid
            else:
                lo = mid + 1
        return lo

    def record(self, v: float) -> None:
        v = float(v)
        with _lock:
            self.counts[self._bucket(v)] += 1
            self._sum += v
            self._sumsq += v * v
            self._count += 1
            if v < self._min:
                self._min = v
            if v > self._max:
                self._max = v

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    def quantile(self, q: float) -> float:
        """Approximate q-quantile: upper bound of the bucket where the
        cumulative count crosses q (0 observations -> 0.0). This is the
        CONSERVATIVE (upper) edge of the true quantile's bucket — see
        `quantile_bounds` for the bracketing error bar the /statusz SLO
        numbers quote (docs/observability.md 'Percentile accuracy')."""
        if self._count == 0:
            return 0.0
        return quantile_from_snapshot(
            {"count": self._count, "counts": self.counts,
             "bounds": self.bounds, "max": self._max}, q)

    def quantile_bounds(self, q: float) -> Tuple[float, float]:
        """(lo, hi) bracketing the TRUE q-quantile: hi is `quantile()`'s
        bucket upper edge, lo the bucket's lower edge (clamped to the
        observed min/max). On the default x2 geometric grid hi/lo <= 2,
        i.e. every quoted percentile is exact to within one bucket — at
        most a factor of the grid ratio, and conservative (never an
        underestimate). Asserted by tests/test_observe.py."""
        if self._count == 0:
            return (0.0, 0.0)
        snap = {"count": self._count, "counts": self.counts,
                "bounds": self.bounds, "max": self._max}
        return quantile_bounds_from_snapshot(snap, self._min, q)

    def snapshot(self) -> dict:
        with _lock:
            return {
                "count": self._count,
                "sum": self._sum,
                "sum_squares": self._sumsq,
                "min": self._min if self._count else 0.0,
                "max": self._max if self._count else 0.0,
                "bounds": list(self.bounds),
                "counts": list(self.counts),
            }


class MetricsRegistry:
    """Name → instrument map with get-or-create accessors. One process
    -wide instance lives in this module; tests may build private ones."""

    def __init__(self):
        self._metrics: Dict[str, object] = {}

    def _get(self, name: str, cls, *args):
        m = self._metrics.get(name)
        if m is None:
            with _lock:
                from bigdl_tpu.analysis import sancov
                if sancov.LOCKS_ON:     # lockset seed: registry map
                    sancov.check_owned(_lock, "metrics.registry")
                m = self._metrics.get(name)
                if m is None:
                    m = cls(name, *args)
                    self._metrics[name] = m
        if not isinstance(m, cls):
            raise TypeError(f"metric {name!r} already registered as "
                            f"{type(m).__name__}, wanted {cls.__name__}")
        return m

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name: str,
                  bounds: Optional[Tuple[float, ...]] = None) -> Histogram:
        if bounds is not None:
            return self._get(name, Histogram, bounds)
        return self._get(name, Histogram)

    def snapshot(self) -> dict:
        """Consistent-enough point-in-time view, grouped by kind — the
        exporters' input format."""
        counters, gauges, hists = {}, {}, {}
        for name, m in sorted(self._metrics.items()):
            if isinstance(m, Counter):
                counters[name] = m.snapshot()
            elif isinstance(m, Gauge):
                gauges[name] = m.snapshot()
            elif isinstance(m, Histogram):
                hists[name] = m.snapshot()
        return {"counters": counters, "gauges": gauges,
                "histograms": hists}

    def reset(self) -> None:
        """Drop every registered metric (tests; a fresh optimize() keeps
        accumulating — a flight recorder spans the process)."""
        with _lock:
            self._metrics.clear()
            _phase_cache.clear()  # else phase() keeps orphaned histograms


_REGISTRY = MetricsRegistry()


def registry() -> MetricsRegistry:
    return _REGISTRY


def counter(name: str) -> Counter:
    return _REGISTRY.counter(name)


def gauge(name: str) -> Gauge:
    return _REGISTRY.gauge(name)


def histogram(name: str,
              bounds: Optional[Tuple[float, ...]] = None) -> Histogram:
    return _REGISTRY.histogram(name, bounds)


# -------------------------------------------------- phase timing (spans)
class _Phase:
    """One clock read per edge feeding BOTH sinks: the phase histogram
    (always, host-side floats only) and the tracer ring (when enabled).
    This is the instrumentation primitive the trainers use."""

    __slots__ = ("_hist", "_name", "_cat", "_t0")

    def __init__(self, hist: Histogram, name: str, cat: str):
        self._hist, self._name, self._cat = hist, name, cat

    def __enter__(self):
        if _phase_hook is not None:
            _phase_hook(self._name, True)
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur_ns = time.perf_counter_ns() - self._t0
        if _phase_hook is not None:
            _phase_hook(self._name, False)
        self._hist.record(dur_ns * 1e-9)
        from bigdl_tpu.observe import trace
        t = trace._TRACER
        if t.enabled:
            t.record(self._name, self._cat, self._t0, dur_ns)
        return False


# ------------------------------------------- serialized-bucket quantiles
def quantile_from_snapshot(h: dict, q: float) -> float:
    """q-quantile from a SERIALIZED histogram (snapshot/JSONL form):
    the upper bound of the bucket where the cumulative count crosses q.
    Shared by the live Histogram, the report CLI, and the serve SLO
    section so every surface quotes the same number."""
    count = h.get("count", 0)
    if not count:
        return 0.0
    target = q * count
    cum = 0
    for i, c in enumerate(h["counts"]):
        cum += c
        if cum >= target:
            return (h["bounds"][i] if i < len(h["bounds"]) else h["max"])
    return h["max"]


def quantile_bounds_from_snapshot(h: dict, lo_clamp: float,
                                  q: float) -> Tuple[float, float]:
    """(lo, hi) bracket of the true q-quantile from serialized buckets
    (`lo_clamp` = the observed min, which tightens bucket 0's open
    lower edge)."""
    count = h.get("count", 0)
    if not count:
        return (0.0, 0.0)
    target = q * count
    cum = 0
    for i, c in enumerate(h["counts"]):
        cum += c
        if cum >= target:
            if i < len(h["bounds"]):
                hi = min(h["bounds"][i], h["max"])
            else:
                hi = h["max"]
            lo = h["bounds"][i - 1] if i > 0 else 0.0
            return (max(lo, min(lo_clamp, hi)), hi)
    return (h["max"], h["max"])


def histogram_window(prev: Optional[dict], cur: Optional[dict]) -> Optional[dict]:
    """Snapshot-shaped DELTA between two cumulative histogram snapshots
    of the same instrument — the poll-window view the serve-SLO
    watchdog quantiles over (observe/doctor.py): a week of healthy
    cumulative counts cannot dilute the last window's regression.
    `prev=None` means "first poll" (the whole cumulative history IS the
    window). The window's max is approximated by the cumulative max —
    conservative, and irrelevant to bucket-edge quantiles unless the
    window crosses the overflow bucket."""
    if cur is None:
        return None
    if prev is None or list(prev.get("bounds", ())) != list(cur["bounds"]):
        return dict(cur)
    counts = [max(0, c - p) for c, p in zip(cur["counts"],
                                            prev["counts"])]
    return {"count": max(0, cur["count"] - prev["count"]),
            "sum": cur["sum"] - prev["sum"],
            "counts": counts, "bounds": list(cur["bounds"]),
            "min": cur.get("min", 0.0), "max": cur.get("max", 0.0)}


def merge_histogram_snapshots(hs: List[dict]) -> Optional[dict]:
    """Sum histogram snapshots with identical bounds (the fleet report
    merges per-peer `phase/...` histograms into one table —
    observe/report.py --fleet). Mismatched grids are skipped rather
    than misaligned; None when nothing merged."""
    out: Optional[dict] = None
    for h in hs:
        if not h:
            continue
        if out is None:
            out = {"count": h["count"], "sum": h["sum"],
                   "counts": list(h["counts"]),
                   "bounds": list(h["bounds"]),
                   "min": h.get("min", 0.0), "max": h.get("max", 0.0)}
            continue
        if list(h["bounds"]) != out["bounds"]:
            continue
        out["count"] += h["count"]
        out["sum"] += h["sum"]
        out["counts"] = [a + b for a, b in zip(out["counts"],
                                               h["counts"])]
        out["min"] = min(out["min"], h.get("min", out["min"]))
        out["max"] = max(out["max"], h.get("max", out["max"]))
    return out


_phase_cache: Dict[str, Histogram] = {}


def phase(name: str, cat: str = "train") -> _Phase:
    """`with phase("train/dispatch"): ...` — records seconds into the
    `phase/<name>` histogram and, when tracing is on, a matching span.
    The histogram lookup is cached by name, so the steady-state cost is
    two perf_counter reads + one locked bucket increment."""
    h = _phase_cache.get(name)
    if h is None:
        h = _REGISTRY.histogram(f"phase/{name}")
        with _lock:              # miss path only; hits stay lock-free
            _phase_cache[name] = h
    return _Phase(h, name, cat)


def phase_table(snapshot: dict) -> List[dict]:
    """Rows for the report CLI: every `phase/...` histogram in a registry
    snapshot as {phase, count, total_s, avg_ms, p50_ms, max_ms, share}."""
    hists = snapshot.get("histograms", {})
    rows = []
    total = sum(h["sum"] for n, h in hists.items()
                if n.startswith("phase/")) or 1e-12
    for name, h in hists.items():
        if not name.startswith("phase/") or not h["count"]:
            continue
        p50 = quantile_from_snapshot(h, 0.5)
        rows.append({
            "phase": name[len("phase/"):],
            "count": h["count"],
            "total_s": h["sum"],
            "avg_ms": 1e3 * h["sum"] / h["count"],
            "p50_ms": 1e3 * p50,
            "max_ms": 1e3 * h["max"],
            "share": h["sum"] / total,
        })
    rows.sort(key=lambda r: -r["total_s"])
    return rows


def data_wait_fraction(snapshot: dict) -> Optional[dict]:
    """Feed-health headline: the fraction of the training step loop the
    trainer spent WAITING on the input pipeline (`train/data_wait` — the
    span `_observed_batches` wraps around each batch fetch) over the
    loop's total accounted time (data_wait + dispatch + flush +
    checkpoint, the disjoint sibling phases of the step loop). This is
    the number the input service exists to drive to ~0; None when the
    snapshot has no step-loop phases."""
    hists = snapshot.get("histograms", {})

    def total(name):
        h = hists.get(f"phase/{name}")
        return (float(h["sum"]), int(h["count"])) \
            if h and h.get("count") else (0.0, 0)

    wait_s, wait_n = total("train/data_wait")
    # denominator: the true loop wall (train/step_wall_s — the full
    # period between successive batch requests, optim/local.py
    # _observed_batches); older run logs without it fall back to the
    # sum of the instrumented step-loop phases (an overestimate of the
    # fraction — uninstrumented loop time is dropped)
    wall = hists.get("train/step_wall_s")
    if wall and wall.get("count"):
        loop_s = max(float(wall["sum"]), wait_s)
    else:
        loop_s = sum(total(n)[0] for n in (
            "train/data_wait", "train/dispatch", "train/flush",
            "train/checkpoint"))
    if not wait_n or loop_s <= 0:
        return None
    return {"data_wait_s": wait_s, "step_loop_s": loop_s,
            "fraction": wait_s / loop_s, "waits": wait_n}


def serve_slo(snapshot: dict) -> Optional[dict]:
    """The serving subsystem's SLO view from a registry snapshot (live
    /statusz or a JSONL run log): per-model p50/p99 latency, shed count,
    batch fill. Model names are recovered from the `serve/<model>/
    latency_ms` histograms the batchers record; None when the snapshot
    carries no serve traffic at all."""
    hists = snapshot.get("histograms", {})
    counters = snapshot.get("counters", {})
    models: Dict[str, dict] = {}
    for name, h in sorted(hists.items()):
        if not (name.startswith("serve/") and name.endswith("/latency_ms")):
            continue
        model = name[len("serve/"):-len("/latency_ms")]
        if not model:        # the combined serve/latency_ms histogram
            continue
        models[model] = {
            "requests": h["count"],
            "p50_ms": round(quantile_from_snapshot(h, 0.50), 3),
            "p99_ms": round(quantile_from_snapshot(h, 0.99), 3),
        }
    total_req = counters.get("serve/requests", 0)
    if not models and not total_req:
        return None
    fill = hists.get("serve/batch_fill")
    return {
        "models": models,
        "totals": {
            "requests": total_req,
            "rows": counters.get("serve/rows", 0),
            "batches": counters.get("serve/batches", 0),
            "shed": counters.get("serve/shed", 0),
            "mean_batch_fill": round(fill["sum"] / fill["count"], 4)
            if fill and fill["count"] else 0.0,
            "queued_rows": snapshot.get("gauges", {}).get(
                "serve/queue_depth", 0),
        },
    }


# ------------------------------------------------ reference-style facade
class IterationMetrics:
    """Phase-timing accumulator (reference: optim/Metrics.scala:31-123 —
    set/add per phase, summary string). Historically lived in
    utils/profile.py; the flight recorder absorbed it — `utils.profile`
    re-exports this class, and `mirror` additionally feeds each sample
    into the process-wide registry so ad-hoc users show up in the same
    exports as the trainers."""

    def __init__(self, mirror: bool = False, prefix: str = ""):
        self._sums: Dict[str, float] = {}
        self._counts: Dict[str, int] = {}
        self._mirror = mirror
        self._prefix = prefix

    def add(self, phase: str, seconds: float):
        with _lock:
            self._sums[phase] = self._sums.get(phase, 0.0) + seconds
            self._counts[phase] = self._counts.get(phase, 0) + 1
        if self._mirror:
            _REGISTRY.histogram(
                f"phase/{self._prefix}{phase}").record(seconds)

    def time(self, phase: str):
        metrics = self

        class _Ctx:
            def __enter__(self):
                self.t0 = time.perf_counter()

            def __exit__(self, *a):
                metrics.add(phase, time.perf_counter() - self.t0)

        return _Ctx()

    def summary(self) -> str:
        lines = []
        for phase_name, s in sorted(self._sums.items(), key=lambda kv: -kv[1]):
            n = self._counts[phase_name]
            lines.append(f"{phase_name}: total {s:.3f}s over {n} "
                         f"(avg {s / n * 1e3:.2f}ms)")
        return "\n".join(lines)
