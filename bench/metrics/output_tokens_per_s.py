"""Output tokens streamed to the clients in the window, per second."""


def read(ctx):
    n = sum(1 for s in ctx.streams for t in s.times if ctx.in_window(t))
    return n / ctx.seconds if n else None
