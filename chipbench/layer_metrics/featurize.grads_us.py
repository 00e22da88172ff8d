"""Device microseconds per featurized row spent computing per-example
gradients: the leaf ops under the program scope ``repro.grass.grads`` in
the traced window (``program_trace.py``), over the rows featurized there.
Silent when no op carries the scope (an older program) or no row
completed."""
import program_trace

SCOPE = "repro.grass.grads"


def read(ctx):
    prog = program_trace.of(ctx)
    rows = ctx.counts.get("rows", 0)
    if prog is None or not rows:
        return None
    s = prog.under_scope(SCOPE)
    if s <= 0:
        return None
    return 1e6 * s / rows
