"""From stamps, counters and a profiler trace to numbers: the yardstick.

Kept with the benchmark so that every PR computes the same number in the
same way: percentiles, pooled inter-token gaps, the busy union of a device
trace, per-module device time, and the FLOPs a window needed: the sum, over
the tokens it processed, of what the configuration's family reckons for a
token (shape functions in `benchmark/families/`, not the compiler's cost
analysis, which counts what XLA emitted). `benchmark/tests/test_reduce.py`
checks each on the CPU.
"""

import glob
import os

import numpy as np


# ------------------------------------------------------------ statistics
def percentile(values, q):
    """The q-th percentile (0..100), linear between order statistics."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return None
    pos = (v.size - 1) * q / 100.0
    lo = int(np.floor(pos))
    hi = min(lo + 1, v.size - 1)
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


def pooled_gaps(stamp_lists):
    """Gaps between consecutive tokens of each request, all pooled."""
    out = []
    for s in stamp_lists:
        out.extend(np.diff(np.asarray(s, np.float64)).tolist())
    return out


CLIENT_PERCENTILES = (50, 90, 95, 99)


def serve_metrics(records, t0, seconds):
    """End-to-end numbers of a serving window from the client's records
    (`client.py`): every request sent in [t0, t0 + seconds) counts, however
    late it ended; a token counts for the rate if it arrived in the window.
    An open loop's request is timed from when it was due."""
    sent = [r for r in records if t0 <= r["sent"] < t0 + seconds]
    ok = [r for r in sent if not r["error"]]
    start = lambda r: r["due"] if r.get("due") is not None else r["sent"]  # noqa: E731
    ttft = [(r["stamps"][0] - start(r)) * 1e3 for r in ok if r["stamps"]]
    gaps = [g * 1e3 for g in pooled_gaps([r["stamps"] for r in ok])]
    in_window = sum(1 for r in records for s in r["stamps"]
                    if t0 <= s < t0 + seconds)
    late = [(r["sent"] - r["due"]) * 1e3 for r in sent
            if r.get("due") is not None]
    out = {"attempted": len(sent), "failed": len(sent) - len(ok),
           "serve_tokens_per_s": in_window / seconds,
           "n_ttft": len(ttft), "n_gaps": len(gaps),
           "generator_late_p95_ms": percentile(late, 95) if late else None}
    # the tail with the rest: a per-layer statistic beside the percentiles,
    # steady from seed to seed, moved 18 % by one stall of 3 s (PERF.md)
    out["ttft_mean_ms"] = float(np.mean(ttft)) if ttft else None
    for q in CLIENT_PERCENTILES:
        out[f"ttft_p{q}_ms"] = percentile(ttft, q)
        out[f"itl_p{q}_ms"] = percentile(gaps, q)
    return out


# ----------------------------------------------------------------- FLOPs
def window_tokens(records, t0, seconds):
    """What a serving window processed, as (position, logits) of each token
    that went through the model: a request's prompt (all but its last token
    go through prefill, without logits) counts if its first token arrived
    in the window, and each output token if it did."""
    inside = lambda s: t0 <= s < t0 + seconds                # noqa: E731
    out = []
    for r in records:
        P = r["prompt_len"]
        if r["stamps"] and inside(r["stamps"][0]):
            out.extend((i, False) for i in range(P - 1))
        out.extend((P - 1 + k, True) for k, s in enumerate(r["stamps"])
                   if inside(s))
    return out


def serve_window_flops(token_flops, records, t0, seconds):
    """FLOPs needed for what the window processed: the family's
    `token_flops(position, logits)` summed over `window_tokens`. (Every
    term is a whole number far under 2**53, so the order of the sum does
    not matter.)"""
    return float(sum(token_flops(p, logits)
                     for p, logits in window_tokens(records, t0, seconds)))


# ----------------------------------------------------------------- trace
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def read_device_lines(path, device_prefix="/device:TPU:"):
    """{plane name: {line name: [(event name, start_ns, duration_ns)]}} of
    the device planes of an `.xplane.pb`, read with nothing but JAX."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(device_prefix):
            continue
        lines = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events)
        out[plane.name] = lines
    return out


def short_name(name):
    """An op's name without its HLO text: `%fusion.12 = f32[...] ...` ->
    `fusion.12`."""
    return name.split(" = ")[0].lstrip("%")[:120]


def busy_union_ns(events):
    """Length of the union of the events' intervals."""
    spans = sorted((e[1], e[1] + e[2]) for e in events)
    total, cur_s, cur_e = 0.0, None, None
    for s, e in spans:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(events, top=10):
    """The longest stretches in which no event ran, each named by the
    event that ended it (what the device was waiting to be given)."""
    spans = sorted((e[1], e[1] + e[2], e[0]) for e in events)
    gaps, end = [], None
    for s, e, name in spans:
        if end is not None and s > end:
            gaps.append(("before " + short_name(name), (s - end) * 1e-9))
        end = e if end is None else max(end, e)
    gaps.sort(key=lambda g: -g[1])
    return [[n, t] for n, t in gaps[:top]]


def op_kind(name):
    """`fusion.3186` -> `fusion`, `copy-start.7` -> `copy-start`: a program
    of 48 unrolled layers has thousands of ops, a dozen kinds."""
    stem = short_name(name)
    head, _, tail = stem.rpartition(".")
    return head if head and tail.isdigit() else stem


def top_ops(events, top=10):
    """Device seconds by kind of op, the largest first."""
    by = {}
    for e in events:
        name = op_kind(e[0])
        by[name] = by.get(name, 0.0) + e[2] * 1e-9
    return [[n, t] for n, t in sorted(by.items(), key=lambda kv: -kv[1])[:top]]


def reduce_trace(planes, span_s=0.0):
    """What the readers and the result line need from the device planes:
    per chip the busy union over the ops line, the traced window, and the
    module events; averaged over the chips that ran anything. The window
    is the traced span as the host timed it (`span_s`), so that a device
    idle at either end of the trace counts as idle; where the device's own
    events reach further than that (the profiler starts before it returns),
    it is their extent."""
    chips = []
    for name, lines in sorted(planes.items()):
        ops = lines.get(OPS_LINE, [])
        if not ops:
            continue
        mods = lines.get(MODULES_LINE, [])
        every = ops + mods
        lo = min(e[1] for e in every)
        hi = max(e[1] + e[2] for e in every)
        chips.append({"plane": name, "busy_s": busy_union_ns(ops) * 1e-9,
                      "window_s": max((hi - lo) * 1e-9, span_s), "ops": ops,
                      "modules": mods})
    if not chips:
        return None
    return {"chips": chips,
            "busy_s": float(np.mean([c["busy_s"] for c in chips])),
            "window_s": float(np.mean([c["window_s"] for c in chips])),
            "device_ops": top_ops(chips[0]["ops"]),
            "idle_gaps": idle_gaps(chips[0]["ops"])}


def module_ms(trace, contains):
    """Mean device time of one run of the module whose name holds
    `contains`, over the runs the trace holds whole; None if it has none."""
    runs = [e[2] for c in trace["chips"] for e in c["modules"]
            if contains in e[0]]
    if not runs:
        return None
    return float(np.mean(runs)) * 1e-6


def matching_op_seconds(trace, pattern):
    """Device seconds, summed over the chips, of the `XLA Ops` events in
    whose name the compiled regular expression is found. On the TPU an
    event's name is its HLO instruction as text, without metadata:
    `%<name> = <shape> <op>(<operands with shapes>), <attributes>`."""
    return 1e-9 * sum(e[2] for c in trace["chips"] for e in c["ops"]
                      if pattern.search(e[0]))
