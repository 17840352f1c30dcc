"""Share of its roofline the joint kernel reaches inside decode steps:
the least time of its calls on this chip, each at the shape the trace
shows (``bench.work``), over their summed device time."""

from bench.work import calls_roofline_s


def read(ctx):
    calls = [c for s in ctx["reduced"].steps if s.kind == "decode"
             for c in s.calls]
    spent = sum(c[3] for c in calls)
    if not calls or spent <= 0:
        return None
    return 100.0 * calls_roofline_s(calls, ctx["model"], ctx["peak"]) / spent
