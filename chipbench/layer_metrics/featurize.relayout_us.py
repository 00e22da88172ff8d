"""Device microseconds per featurized row spent on the sketch step's
non-kernel ops: the leaf ops under the program scope ``repro.grass.sketch``
that are not Pallas kernels (the gradient transposes, the row stack's pad
and copy), over the rows featurized in the traced window
(``program_trace.py``).  Silent when no op carries the scope (an older
program) or no row completed."""
import program_trace

SCOPE = "repro.grass.sketch"


def read(ctx):
    prog = program_trace.of(ctx)
    rows = ctx.counts.get("rows", 0)
    if prog is None or not rows:
        return None
    total = prog.under_scope(SCOPE)
    if total <= 0:
        return None
    return 1e6 * (total - prog.under_scope(SCOPE, kernels=True)) / rows
