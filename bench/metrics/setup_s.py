"""Process start to the opening of the window (s): weight build, compile
or cache load, the server's warmup and the load's ramp."""


def read(ctx):
    return ctx.setup_s
