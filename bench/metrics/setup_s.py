"""Process start to the window's start: device check, weights, compiles or
cache loads, calibration, and the warm-up of every shape (host clock)."""


def read(ctx):
    return ctx.setup_s
