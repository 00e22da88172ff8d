"""Host milliseconds a call spends in its kernel launches, from the program
spans ``repro.kernel.<kernel>`` around each ``pallas_call`` in the traced
window (``program_trace.py``): the kernel body's trace, its lowering, the
program load and the enqueue.  The kernel layer's other spans (tables,
stream, row stack) are not launches.  Silent when the program has no
launch span or no call completed."""
import program_trace

PREFIX = "repro.kernel."
NOT_LAUNCHES = {"repro.kernel.tables", "repro.kernel.stream",
                "repro.kernel.row_stack"}


def read(ctx):
    prog = program_trace.of(ctx)
    calls = ctx.counts.get("calls", 0)
    if prog is None or not calls:
        return None
    launch_s = sum(v for name, v in prog.span_s.items()
                   if name.startswith(PREFIX) and name not in NOT_LAUNCHES)
    if launch_s <= 0:
        return None
    return 1e3 * launch_s / calls
