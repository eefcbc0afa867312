"""The six readers of per-layer metrics.

A per-layer metric is a file `benchmark/layer_metrics/<name>.json`:
`{"reader": <one of READERS>, "args": {...}}`. A reader gets the run's
context (`ctx`: counters before and after the window, the reduced trace,
the window's work and length, the configuration, the peaks) and returns a
number, or None where it finds nothing to read, in which case the metric is
left out of the line: never 0 for a share of a peak.
"""

import json
import os

import reduce as R

HERE = os.path.dirname(os.path.abspath(__file__))


def _hist(ctx, name, field):
    name = name.format(model=ctx.get("model_name", ""))
    a = ctx["after"]["histograms"].get(name)
    if a is None:
        return None
    b = ctx["before"]["histograms"].get(name, {"sum": 0.0, "count": 0})
    return a[field] - b[field]


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


READERS = {f.__name__: f for f in
           (hist_mean, ratio_of_sums, trace_module_ms, trace_idle,
            client_stat, flops_share)}


def read_metric(name, ctx):
    with open(os.path.join(HERE, "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    reader = READERS.get(spec["reader"])
    if reader is None:
        raise ValueError(f"layer metric {name!r}: unknown reader "
                         f"{spec['reader']!r} (have {sorted(READERS)})")
    return reader(ctx, **spec.get("args", {}))
