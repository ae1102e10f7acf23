"""pull_transfers.<cell kind>: device-to-host transfers per coalescer
dispatch over the window: the change in the coalescer's own
``pull_transfers`` over the change in its ``dispatches``. One transfer per
dispatch means the whole result came to the host at once. A program
without that counter gives no reading."""


def read(ctx):
    b, a = ctx.run.co_before, ctx.run.co_after
    if "pull_transfers" not in a:
        return None
    n = a["dispatches"] - b["dispatches"]
    return None if n == 0 else (a["pull_transfers"] - b["pull_transfers"]) / n
