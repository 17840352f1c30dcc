"""Share of the time inside the harness's tick spans in which no device
operation runs: host work between and around the engine's calls."""


def read(ctx):
    red = ctx["reduced"]
    if red.tick_s <= 0:
        return None
    return 100.0 * (red.tick_s - red.tick_busy_s) / red.tick_s
