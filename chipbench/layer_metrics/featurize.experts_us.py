"""Device microseconds per featurized row spent in the held experts of the
MoE layers: the leaf ops under the program scope ``repro.moe.experts``
(their grouped matmuls forward and backward, the gather into expert order
and back) in the traced window (``program_trace.py``), over the rows
featurized there.  Silent when no op carries the scope (a program without
the MoE layer) or no row completed."""
import program_trace

SCOPE = "repro.moe.experts"


def read(ctx):
    prog = program_trace.of(ctx)
    rows = ctx.counts.get("rows", 0)
    if prog is None or not rows:
        return None
    s = prog.under_scope(SCOPE)
    if s <= 0:
        return None
    return 1e6 * s / rows
