"""The readers of per-layer metrics.

A per-layer metric is a file `benchmark/layer_metrics/<name>.json`:
`{"reader": <name>, "args": {...}}`. The reader is one of the built-ins
below (`READERS`), or else the module `benchmark/layer_readers/<name>.py`,
whose function `read(ctx, **args)` is called. A reader gets the run's
context and returns a number, or None where it finds nothing to read, in
which case the metric is left out of the line: never 0 for a share of a
peak or of a roofline.

`ctx` holds: `before` and `after` (the program's counters, gauges and
histograms at the window's ends), `trace` (the reduced device trace of a
`--trace 1` run, else None), `window_s` and `needed_flops` (the window's
length and the FLOPs its work needed; of the traced steps alone in a traced
training run), `traced_work` (what the traced span processed: `tokens`, the
(position, logits) of each token, for a serving cell; `train_tokens` and
`sequence` for a training cell), `client` (a serving cell's statistics of
the client's stamps), `model_name`, `peaks` (the chip's row of
`benchmark/peaks/`), `chips`, `cfg` (the configuration's sizes as run),
`family` (its family's module) and `dirs` (the benchmark's directories).
"""

import re

import common as C
import reduce as R


def _differenced(ctx, kind, name, field=None):
    name = name.format(model=ctx.get("model_name", ""))
    a = ctx["after"][kind].get(name)
    if a is None:
        return None
    if field is None:
        return a - ctx["before"][kind].get(name, 0.0)
    b = ctx["before"][kind].get(name, {"sum": 0.0, "count": 0})
    return a[field] - b[field]


def _hist(ctx, name, field):
    return _differenced(ctx, "histograms", name, field)


def hist_mean(ctx, histogram, scale=1.0):
    """Mean of what a histogram recorded inside the window: the difference
    of its exact sums over the difference of its counts."""
    n = _hist(ctx, histogram, "count")
    if not n:
        return None
    return scale * _hist(ctx, histogram, "sum") / n


def ratio_of_sums(ctx, numerator, denominator, scale=1.0):
    num, den = _hist(ctx, numerator, "sum"), _hist(ctx, denominator, "sum")
    if num is None or not den:
        return None
    return scale * num / den


def counter_delta(ctx, counter, over=None, per_second=False, scale=1.0):
    """What a counter of the program gained inside the window; over what
    the counter `over` gained, or over the window's length with
    `per_second`. None where a counter is absent, or `over` stood still."""
    gained = _differenced(ctx, "counters", counter)
    if gained is None:
        return None
    if over is not None:
        base = _differenced(ctx, "counters", over)
        if not base:
            return None
        gained /= base
    if per_second:
        if not ctx.get("window_s"):
            return None
        gained /= ctx["window_s"]
    return scale * gained


def trace_module_ms(ctx, module):
    if ctx.get("trace") is None:
        return None
    return R.module_ms(ctx["trace"], module)


def trace_idle(ctx):
    t = ctx.get("trace")
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def client_stat(ctx, stat):
    """A statistic of the client's stamps over the window (`reduce.py`
    `serve_metrics`), for one that stands beside an end-to-end tail."""
    return (ctx.get("client") or {}).get(stat)


def flops_share(ctx, peak="bf16_flops_per_s"):
    """The whole step's share of the chip's peak: FLOPs the model needs for
    the work the window did, over the window times the peak."""
    if not ctx.get("needed_flops") or not ctx.get("window_s") \
            or not ctx["peaks"].get(peak):
        return None
    return 100.0 * ctx["needed_flops"] / (
        ctx["window_s"] * ctx["peaks"][peak] * ctx.get("chips", 1))


def kernel_roofline(ctx, kernel, match, peak="bf16_flops_per_s",
                    bandwidth="hbm_bytes_per_s"):
    """One mechanism's share of its roofline over the traced span: the
    least time the chip could take for the work, max(flops / peak, bytes /
    bandwidth), over the device time of the `XLA Ops` events that belong to
    the mechanism. The work is the model's, not the implementation's: what
    the family's `kernel_work(cfg, kernel, **traced_work)` reckons, from
    shapes, for what the span processed, so the share reads the same work
    whatever computes it. An event belongs where the regular expression
    `match` is found in its name, which on the TPU is the HLO instruction
    as text: a Pallas call's `name` is the instruction's name, and shapes
    tell other ops apart; a `jax.named_scope` is not in it (PERF.md,
    section 3). None where the run has no trace, the family reckons no such
    kernel or no event matches; never 0."""
    trace, family = ctx.get("trace"), ctx.get("family")
    work_of = getattr(family, "kernel_work", None)
    if trace is None or work_of is None or not ctx.get("traced_work"):
        return None
    seconds = R.matching_op_seconds(trace, re.compile(match))
    work = work_of(ctx["cfg"], kernel, **ctx["traced_work"])
    if not seconds or not work:
        return None
    least = max(work["flops"] / ctx["peaks"][peak],
                work["bytes"] / ctx["peaks"][bandwidth])
    return 100.0 * least / seconds if least > 0 else None


READERS = {f.__name__: f for f in
           (hist_mean, ratio_of_sums, counter_delta, trace_module_ms,
            trace_idle, client_stat, flops_share, kernel_roofline)}


def read_metric(name, ctx):
    dirs = ctx.get("dirs", (C.HERE,))
    spec = C.load_json(C.found(dirs, "layer_metrics", name + ".json",
                               "layer metric"))
    reader = READERS.get(spec["reader"])
    if reader is None:
        try:
            reader = C.plug_in(dirs, "layer_readers", spec["reader"],
                               f"layer metric {name!r}: reader").read
        except C.Refused as e:
            raise C.Refused(f"{e}; the built-in readers are "
                            f"{sorted(READERS)}") from e
    return reader(ctx, **spec.get("args", {}))
