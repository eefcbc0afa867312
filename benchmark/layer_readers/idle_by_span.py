"""The share of the traced span in which the device idled while the host
was in a given span of the program: device idle time put down to what the
host was doing, on the device's clock.

`read(ctx, spans, dispatch, fetch=None)` runs in the benchmark's process
after the window and reads the program's span ring
(`bigdl_tpu.observe.get_tracer().events()`, perf_counter nanoseconds)
beside `ctx["trace"]` (device events in nanoseconds from the capture's
start). It returns 100 x (idle nanoseconds put down to a span whose name
`spans` matches in full) / (the traced window's length, as `trace_idle`
divides), or None.

**The mapping** (host = device + K). The device runs programs in the order
the host enqueued them, so the chip's `XLA Modules` runs of the programs
that the `dispatch` spans name (their `program` arg, `jit_<fn>`) are paired
with those spans in order: module i with dispatch j + i, for each offset j
whose names agree all along. A candidate passes three checks. (1) Each
kind of dispatch (its `program` and carried `chunk`) pairs with one
executable (the module's name with its hash) and each executable with
one kind. (2) A program starts after it was enqueued: K >= lo =
max(dispatch start - module start). (3) Its tokens come back after it
ended: where `fetch` names the span that waited for a step's tokens (it
carries its dispatch's args, the same dict), K <= hi = min(fetch end -
module end). K is the least (dispatch end - module start) over the pairs,
the enqueue taken at the dispatch's end, or hi where that is smaller.
The coarse anchor: the device plane records from about when `start_trace`
returns (on a v5e its first event lies 46-49 ms after the capture's time
zero, and `start_trace` took 42-45 ms), so the chip's first event is put
at the span's start on the host (`trace["span_at"]`). Of the candidates
within a second of it that pass, the nearest is taken, if the next
nearest lies at least twice as far (the runs of one program are an
iteration apart, 12-140 ms in the cells: an anchor off by up to a third
of that still finds the pairing, where the checks leave more than one).

**The checks.** None where the run has no trace, the ring holds no
dispatch span with a program (a program older than these spans), no
candidate passes, two are about as near the anchor, or the ring's oldest
event ended after the traced window began (the ring no longer holds the
whole span): a bad mapping is a missing number, never a wrong one.

**The attribution.** The idle of the window is what `trace_idle` counts:
every gap between the union of the chip's `XLA Ops` events inside their
extent (ops and modules), and the ends, `window_s` less that extent, put
before and after it as the mapped host span start says. Each idle
nanosecond goes to `process/gc` if a collection ran then (it stops every
thread), else to the innermost span then open on the thread that recorded
the dispatches, else to "none".

`attribute` returns the split by span and what the mapping found (the
pairs, the offset from the anchor, the bounds, and how late a module began
after its dispatch ended), for PERF.md.
"""

import re

import numpy as np

GC_SPAN = "process/gc"
ANCHOR_NS = 1e9             # how far K may lie from the span's start
GAP_NS = 50e3               # a module "after an idle gap": one of 50 us+


def read(ctx, spans, dispatch, fetch=None):
    found = attribute(ctx, dispatch, fetch)
    if found is None:
        return None
    pattern = re.compile(spans)
    matched = sum(ns for name, ns in found["by_span"].items()
                  if pattern.fullmatch(name))
    return 100.0 * matched / found["window_ns"]


def ring_events():
    """The program's span ring, or None where the program has none."""
    try:
        from bigdl_tpu.observe import get_tracer
        return get_tracer().events()
    except (ImportError, AttributeError):
        return None


def attribute(ctx, dispatch, fetch=None):
    """{"by_span": {name: idle ns}, "window_ns", "idle_ns", and what the
    mapping found} summed over the chips, or None (the checks above)."""
    trace = ctx.get("trace")
    events = ring_events()
    if trace is None or not events or "span_at" not in trace:
        return None
    spans = [e for e in events if e[0] == "X"]
    sent = [e for e in spans if e[1] == dispatch
            and isinstance(e[6], dict) and e[6].get("program")]
    if not sent:
        return None
    tids = [e[3] for e in sent]
    tid = max(set(tids), key=tids.count)      # the device-driving thread
    sent = sorted((e for e in sent if e[3] == tid), key=lambda e: e[4])
    waited = {}
    if fetch is not None:
        by_args = {id(e[6]): i for i, e in enumerate(sent)}
        for e in spans:
            if e[1] == fetch and e[3] == tid and id(e[6]) in by_args:
                waited[by_args[id(e[6])]] = e[4] + e[5]
    out = {"by_span": {}, "window_ns": 0.0, "idle_ns": 0.0, "chips": []}
    for chip in trace["chips"]:
        one = _chip(chip, trace, spans, sent, waited, tid, events)
        if one is None:
            return None
        for name, ns in one.pop("by_span").items():
            out["by_span"][name] = out["by_span"].get(name, 0.0) + ns
        out["window_ns"] += one["window_ns"]
        out["idle_ns"] += one["idle_ns"]
        out["chips"].append(one)
    return out if out["window_ns"] > 0 else None


def _base(module):
    """`jit__step(1234)` -> `jit__step`."""
    return module.split("(", 1)[0]


def _mapping(mods, sent, waited, anchor):
    """(K, j, lo, hi) of the passing candidate nearest the anchor (K's
    value there), or None where none passes or two are about as near."""
    programs = [e[6]["program"] for e in sent]
    kinds = [(e[6]["program"], e[6].get("chunk")) for e in sent]
    names = [_base(m[0]) for m in mods]
    start = np.array([m[1] for m in mods])
    end = start + np.array([m[2] for m in mods])
    d0 = np.array([float(e[4]) for e in sent])
    d1 = d0 + np.array([float(e[5]) for e in sent])
    n, found = len(mods), []
    for j in range(len(sent) - n + 1):
        if abs(d1[j] - start[0] - anchor) > ANCHOR_NS \
                or programs[j:j + n] != names:
            continue
        pairs = set(zip(kinds[j:j + n], (m[0] for m in mods)))
        if len(pairs) != len({p[0] for p in pairs}) \
                or len(pairs) != len({p[1] for p in pairs}):
            continue
        lo = float(np.max(d0[j:j + n] - start))
        k = float(np.max(d1[j:j + n] - start))
        ends = [(waited[j + i], end[i]) for i in range(n) if j + i in waited]
        hi = min((w - e for w, e in ends), default=float("inf"))
        k = min(k, hi)
        if k >= lo:
            found.append((abs(k - anchor), k, j, lo, hi))
    found.sort()
    if not found or (len(found) > 1 and found[1][0] < 2 * found[0][0]):
        return None
    return found[0][1:]


def _union(ops):
    """The busy union of the ops as sorted disjoint (starts, ends)."""
    iv = sorted((e[1], e[1] + e[2]) for e in ops)
    starts, ends = [], []
    for s, e in iv:
        if ends and s <= ends[-1]:
            ends[-1] = max(ends[-1], e)
        else:
            starts.append(s)
            ends.append(e)
    return np.array(starts), np.array(ends)


def _idle(chip, head_at):
    """The chip's idle intervals (device clock), `trace_idle`'s idle: the
    gaps of the ops' union inside the extent of ops and modules, and the
    window's rest before and after that extent, split where the host's span
    started (`head_at`, device clock)."""
    every = chip["ops"] + chip["modules"]
    lo = min(e[1] for e in every)
    hi = max(e[1] + e[2] for e in every)
    bs, be = _union(chip["ops"])
    gs = np.concatenate([[lo], be])
    ge = np.concatenate([bs, [hi]])
    rest = max(0.0, chip["window_s"] * 1e9 - (hi - lo))
    head = min(max(lo - head_at, 0.0), rest)
    gs = np.concatenate([[lo - head], gs, [hi]])
    ge = np.concatenate([[lo], ge, [hi + rest - head]])
    keep = ge > gs
    return gs[keep], ge[keep], (bs, be)


def _chip(chip, trace, spans, sent, waited, tid, events):
    programs = {e[6]["program"] for e in sent}
    mods = sorted((m for m in chip["modules"] if _base(m[0]) in programs),
                  key=lambda m: m[1])
    if not mods:
        return None
    # K that puts the chip's first event at the span's start on the host
    first = min(e[1] for e in chip["ops"] + chip["modules"])
    anchor = trace["span_at"] * 1e9 - first
    found = _mapping(mods, sent, waited, anchor)
    if found is None:
        return None
    k, j, lo, hi = found
    gs, ge, (bs, be) = _idle(chip, trace["span_at"] * 1e9 - k)
    gs, ge = gs + k, ge + k                      # on the host's clock
    oldest = events[0]
    if oldest[4] + oldest[5] > gs[0]:
        return None                              # the ring lost the start
    # how late a module began after its dispatch ended, for the modules
    # that the device had waited for (an idle gap before them)
    late = []
    for i, m in enumerate(mods):
        u = np.searchsorted(bs, m[1], side="left")
        if u > 0 and m[1] - be[u - 1] > GAP_NS:
            late.append((m[1] + k - sent[j + i][4] - sent[j + i][5]) * 1e-3)
    return {"by_span": _split(gs, ge, spans, tid),
            "window_ns": chip["window_s"] * 1e9,
            "idle_ns": float(np.sum(ge - gs)), "pairs": len(mods),
            "anchor_ms": (k - anchor) * 1e-6,
            "first_event_ms": first * 1e-6,
            "bounds_ms": [(lo - k) * 1e-6,
                          (hi - k) * 1e-6 if hi != float("inf") else None],
            "late_after_gap_us": {
                "n": len(late),
                "median_us": float(np.median(late)) if late else None,
                "p99_us": float(np.percentile(late, 99)) if late else None}}


def _split(gs, ge, spans, tid):
    """{label: idle ns} over the idle intervals [gs, ge) (host clock)."""
    w0, w1 = gs[0], ge[-1]
    own = [e for e in spans if e[3] == tid and e[1] != GC_SPAN
           and e[4] < w1 and e[4] + e[5] > w0]
    gcs = [e for e in spans if e[1] == GC_SPAN
           and e[4] < w1 and e[4] + e[5] > w0]
    cuts = np.unique(np.clip(np.array(
        [w0, w1] + [t for e in own + gcs for t in (e[4], e[4] + e[5])],
        dtype=np.float64), w0, w1))
    names = ["none"]
    label = np.zeros(len(cuts) - 1, np.int64)
    # outer spans first, so that an inner one paints over its parent
    for e in sorted(own, key=lambda e: (e[4], -e[5])) + gcs:
        if e[1] not in names:
            names.append(e[1])
        a = np.searchsorted(cuts, max(e[4], w0))
        b = np.searchsorted(cuts, min(e[4] + e[5], w1))
        label[a:b] = names.index(e[1])
    # idle time before each cut: the lengths of the intervals before it,
    # and of the one it falls in, the part before it
    lengths = ge - gs
    before = np.concatenate([[0.0], np.cumsum(lengths)])
    k = np.searchsorted(gs, cuts, side="right")
    cum = before[np.maximum(k - 1, 0)] + np.where(
        k > 0, np.minimum(cuts - gs[np.maximum(k - 1, 0)],
                          lengths[np.maximum(k - 1, 0)]), 0.0)
    per = np.diff(cum)
    out = {}
    for i, name in enumerate(names):
        ns = float(per[label == i].sum())
        if ns:
            out[name] = ns
    return out
