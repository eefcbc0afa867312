"""The whole serving step's share of the chip's peak, over the tokens the
program COMPUTED: for a cell whose prompts come mostly from the prefix
cache, where `flops_share` (which counts every prompt token of the client's
records as computed) would read several times the peak.

The work is what the decode scheduler counted inside the window
(`counted`: its counters differenced over the window), put through the
family's `counted_flops(cfg, counted)`, over window x peak. None where the
program has no such counters, or the family no such function; never 0.
"""

PREFIX = "serve/{model}/decode/"
# counted[key] = the sum of these counters' gains over the window
COUNTED = {"prefill_tokens": ("prefill_tokens",),
           "step_tokens": ("tokens", "rows_dropped"),
           "attended_tokens": ("attended_tokens",),
           "context_tokens": ("context_tokens",),
           "step_context_tokens": ("step_context_tokens",),
           "expert_pairs": ("expert_pairs",),
           "expert_loads": ("expert_loads",),
           "expert_tokens": ("expert_tokens",)}


def counted(ctx, share=1.0):
    """What the scheduler counted inside the window, times `share` (a
    traced span's share of the window); None where a counter is absent."""
    before, after = ctx["before"]["counters"], ctx["after"]["counters"]
    prefix = PREFIX.format(model=ctx.get("model_name", ""))
    out = {}
    for key, names in COUNTED.items():
        if any(prefix + n not in after for n in names):
            return None
        out[key] = share * sum(after[prefix + n] - before.get(prefix + n, 0.0)
                               for n in names)
    return out


def read(ctx, peak="bf16_flops_per_s"):
    flops_of = getattr(ctx.get("family"), "counted_flops", None)
    work = counted(ctx)
    if flops_of is None or work is None or not ctx.get("window_s") \
            or not ctx["peaks"].get(peak):
        return None
    flops = flops_of(ctx["cfg"], work)
    if not flops:
        return None
    return 100.0 * flops / (ctx["window_s"] * ctx["peaks"][peak]
                            * ctx.get("chips", 1))
