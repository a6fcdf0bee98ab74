"""Share of the traced window in which no operation ran on the device:
100 x (1 - union of device-operation intervals / window), averaged over the
chips used."""

from bench import trace


def read(ctx):
    window = trace.window_s(ctx.trace)
    if not ctx.trace.ops or window <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s(ctx.trace) / window)
