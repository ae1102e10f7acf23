"""ingest_device_ms_per_batch: device time of the ingest step per batch, in
milliseconds: the mean length of the executions of the program that
``StreamingIngestor.ingest`` runs (module ``jit__ingest_step*`` on the
trace's ``XLA Modules`` line) that ran inside the window. The serving
programs of the reads that run beside it are other modules and do not
count."""

INGEST_MODULE = "_ingest_step"


def read(ctx):
    if ctx.trace is None:
        return None
    w0, w1 = ctx.trace.window()
    steps = [m for m in ctx.trace.modules
             if INGEST_MODULE in m.name and w0 <= m.start_ns
             and m.end_ns <= w1]
    if not steps:
        return None
    return sum(m.end_ns - m.start_ns for m in steps) / len(steps) / 1e6
