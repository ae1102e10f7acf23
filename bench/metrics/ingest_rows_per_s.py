"""ingest_rows_per_s: rows of the stream batches acknowledged in the
window (``ingest`` returned and its state ready) over the window's
seconds."""


def read(ctx):
    run = ctx.run
    if run.stream is None:
        return None
    acked = sum(1 for t in run.stream.ack_times if run.t0 <= t <= run.t_end)
    return acked * run.stream.batch_rows / run.seconds
