"""device_idle.<cell kind>: the share of the traced window in which no
operation ran on the device, in percent: 1 - (union of the device op
intervals) / window, averaged over the chips used."""


def read(ctx):
    if ctx.trace is None:
        return None
    from bench.harness import trace
    window = trace.window_seconds(ctx.trace)
    if window <= 0 or not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - trace.busy_seconds(ctx.trace) / window)
