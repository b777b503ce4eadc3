"""setup_s: process start to the opening of the measured window (host
clock): JAX and chip start-up, weights, deploy and packing, compiles or
cache loads, warm-up and the ramp."""


def read(ctx):
    return ctx.setup_s
