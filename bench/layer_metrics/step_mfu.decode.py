"""Model FLOPs of the decode tokens served (useful rows only, attention
over each token's real context; ``bench.work.token_flops``) over the
device time of the decode executions times the chip's bf16 peak."""


def read(ctx):
    steps = [s for s in ctx["reduced"].steps if s.kind == "decode"]
    dur = sum(s.span.dur for s in steps)
    if not steps or dur <= 0:
        return None
    flops = sum(ctx["ticks"][s.tick].dc_flops for s in steps)
    return 100.0 * flops / (dur * ctx["peak"]["bf16_flops_per_s"])
