"""moments_roofline.<cell kind>: the sample-moment pass's share of its
roofline, in percent.

The least time the pass needs is what any correct implementation has to
do (``harness.work.moment_pass_work``): per dispatch, read once the samples
of the strata that some query of it overlaps partially, with the query
bounds and the moment output, and per (query, sample of a stratum it
overlaps partially) make 2d compares and 3 multiply-adds, each bound
against the chip's published peaks. The time taken is the device time of
the moment kernel's ops in the window. A trace in which the kernel cannot
be told apart from the others gives no reading."""


def read(ctx):
    if ctx.trace is None:
        return None
    from bench.harness import trace, work
    w0, w1 = ctx.trace.window()
    ops = work.moment_ops(ctx.trace)
    kernel_ns = sum(min(o.end_ns, w1) - max(o.start_ns, w0) for o in ops
                    if o.end_ns > w0 and o.start_ns < w1)
    if kernel_ns <= 0:
        return None
    need_s = work.least_seconds(ctx.run, ctx.peaks)
    if need_s is None:
        return None
    return 100.0 * need_s / (kernel_ns / 1e9 / len(ctx.trace.devices()))
