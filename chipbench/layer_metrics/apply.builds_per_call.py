"""Programs built per call in the window (compiled, or loaded from JAX's
persistent cache), from JAX's own build events: what the host path of one
call costs beyond the launch.  Silent when no call completed."""


def read(ctx):
    calls = ctx.counts.get("calls", 0)
    if not calls:
        return None
    return ctx.counts["builds"] / calls
