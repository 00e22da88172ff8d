"""The model's share of the chip's bf16 peak while the device is busy, in
%: the operations one sequence's factorized features need
(``work/moonlight_attrib.py``, counted from the configuration alone),
times the rows featurized in the traced window, over the device's busy
seconds there times the bf16 peak (``peaks.json``).  Silent without a
trace, a busy second or a completed row, or for a configuration that is
not a language model's factorized gradient."""


def read(ctx):
    t = ctx.trace
    rows = ctx.counts.get("rows", 0)
    if t is None or t.busy_s <= 0 or not rows \
            or "factor_keep" not in ctx.config:
        return None
    per_row = ctx.work("moonlight_attrib").flops(ctx.config)["total"]
    return 100.0 * per_row * rows / (t.busy_s
                                     * ctx.peaks()["bf16_flops_per_s"])
