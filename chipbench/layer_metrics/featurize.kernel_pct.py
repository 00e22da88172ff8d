"""Pallas kernel (custom call) device time over device busy time, in %,
from the device trace; silent when no kernel ran."""


def read(ctx):
    t = ctx.trace
    if t is None or t.kernel_s <= 0 or t.busy_s <= 0:
        return None
    return 100.0 * t.kernel_s / t.busy_s
