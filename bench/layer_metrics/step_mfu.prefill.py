"""Model FLOPs of the prompt tokens served (padding rows of a chunk do
not count) over the device time of the prefill executions times the
chip's bf16 peak."""


def read(ctx):
    steps = [s for s in ctx["reduced"].steps if s.kind == "prefill"]
    dur = sum(s.span.dur for s in steps)
    if not steps or dur <= 0:
        return None
    flops = sum(ctx["ticks"][s.tick].pf_flops for s in steps)
    return 100.0 * flops / (dur * ctx["peak"]["bf16_flops_per_s"])
