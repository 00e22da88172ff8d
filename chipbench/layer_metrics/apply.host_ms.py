"""Host milliseconds a call spends inside the public entry point, from the
program span ``repro.ops.sketch_apply`` in the traced window
(``program_trace.py``): the ``custom_vjp`` shell, lowering, padding and the
launch.  Silent when the program has no such span (an older program) or
no call completed."""
import program_trace

SPAN = "repro.ops.sketch_apply"


def read(ctx):
    prog = program_trace.of(ctx)
    calls = ctx.counts.get("calls", 0)
    if prog is None or not calls or not prog.span_s.get(SPAN):
        return None
    return 1e3 * prog.span_s[SPAN] / calls
