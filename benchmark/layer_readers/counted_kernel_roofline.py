"""One mechanism's share of its roofline over the traced span, as
`kernel_roofline` reads it, with the span's work reckoned from what the
decode scheduler COUNTED and not from the client's records (which count a
prompt token that came from the prefix cache as computed).

The counters are read at the window's ends and not at the span's, so the
span is given its share of the window's counts by its length (the load is a
closed loop at a steady state). The family's `kernel_work(cfg, kernel,
counted=...)` turns the counts into the least FLOPs and bytes; the time is
that of the `XLA Ops` events in whose HLO text `match` is found. None where
the run has no trace, the program no such counters, the family no such
kernel, or no event matches; never 0.
"""

import re

import common as C
import reduce as R


def read(ctx, kernel, match, peak="bf16_flops_per_s",
         bandwidth="hbm_bytes_per_s"):
    trace, family = ctx.get("trace"), ctx.get("family")
    work_of = getattr(family, "kernel_work", None)
    if trace is None or work_of is None or not ctx.get("window_s"):
        return None
    counted = C.plug_in(ctx.get("dirs", (C.HERE,)), "layer_readers",
                        "counted_flops_share", "reader").counted(
        ctx, share=trace["span_s"] / ctx["window_s"])
    seconds = R.matching_op_seconds(trace, re.compile(match))
    work = work_of(ctx["cfg"], kernel, counted=counted) if counted else None
    if not seconds or not work:
        return None
    least = max(work["flops"] / ctx["peaks"][peak],
                work["bytes"] / ctx["peaks"][bandwidth])
    return 100.0 * least / seconds if least > 0 else None
