"""Share of the traced window in which no operation ran on the device:
100 · (1 − busy / window), from the device trace."""


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return ctx.trace.idle_pct()
