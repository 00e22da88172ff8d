"""Device microseconds per featurized row spent forming the masked factor
products ``Σ_t x_t[mask_in]ᵀ δ_t[mask_out]`` of the attributed linears: the
leaf ops under the program scope ``repro.grass.factors`` in the traced
window (``program_trace.py``), over the rows featurized there.  Silent
when no op carries the scope (a gradient source without factors) or no
row completed."""
import program_trace

SCOPE = "repro.grass.factors"


def read(ctx):
    prog = program_trace.of(ctx)
    rows = ctx.counts.get("rows", 0)
    if prog is None or not rows:
        return None
    s = prog.under_scope(SCOPE)
    if s <= 0:
        return None
    return 1e6 * s / rows
