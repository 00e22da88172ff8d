"""Device microseconds per featurized row spent in latent attention: the
leaf ops under the program scope ``repro.mla.attention`` (the q, kv_a,
kv_b and o projections, RoPE, the causal softmax, forward and backward)
in the traced window (``program_trace.py``), over the rows featurized
there.  Silent when no op carries the scope (a program without the
attention) or no row completed."""
import program_trace

SCOPE = "repro.mla.attention"


def read(ctx):
    prog = program_trace.of(ctx)
    rows = ctx.counts.get("rows", 0)
    if prog is None or not rows:
        return None
    s = prog.under_scope(SCOPE)
    if s <= 0:
        return None
    return 1e6 * s / rows
