"""queue_wait_ms.<cell kind>: mean time a request waited in the coalescer's
queue, from submit to the start of the device dispatch it rode, in
milliseconds, over the window: the change in the coalescer's own
``queue_wait_ns`` over the change in ``queue_waits`` (a dedup rider counts;
a tier-0 answer, which no dispatch serves, does not). A program without
those counters gives no reading."""


def read(ctx):
    b, a = ctx.run.co_before, ctx.run.co_after
    if "queue_waits" not in a:
        return None
    n = a["queue_waits"] - b["queue_waits"]
    return None if n == 0 else (a["queue_wait_ns"] - b["queue_wait_ns"]) / n / 1e6
