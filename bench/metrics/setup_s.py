"""Process start to the window's start: JAX start-up, the session's
build, compilation or cache load, and the warm-up session."""


def read(ctx):
    return ctx.setup_s
