"""The sketch apply's share of its roofline: 100 · ideal · calls / device
busy time in the traced window.  The ideal time of one call is the larger
of its bytes over HBM bandwidth and its operations over the bf16 peak
(``work/sketch_apply.py``, ``peaks.json``), counted from the problem alone,
so no correct implementation can read over 100%."""


def read(ctx):
    t = ctx.trace
    calls = ctx.counts.get("calls", 0)
    if t is None or t.busy_s <= 0 or not calls:
        return None
    traffic = ctx.workload["traffic"]
    ideal = ctx.work("sketch_apply").ideal_s(
        ctx.config["sketch"], traffic["n"], ctx.peaks(),
        ctx.config["operand"]["dtype"])
    return 100.0 * ideal * calls / t.busy_s
