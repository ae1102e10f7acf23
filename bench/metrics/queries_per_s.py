"""queries_per_s: queries answered in the window (each with every kind the
configuration asks for) over the window's seconds."""


def read(ctx):
    run = ctx.run
    rows = sum(r.qidx.size for r in run.requests
               if r.result is not None and r.t_done <= run.t_end)
    return rows / run.seconds
