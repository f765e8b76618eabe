"""Process start to window start, s: weights made on the device, executor
built, shapes warmed (compiles or cache reads), and the clients' ramp."""


def read(ctx):
    return ctx.setup_s
