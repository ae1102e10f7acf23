"""setup_s: seconds from process start to the window's start (start-up,
data, build, compile or cache load, warm-up)."""


def read(ctx):
    return ctx.setup_s
