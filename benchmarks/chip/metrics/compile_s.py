"""compile_s: seconds of backend compilation during set-up, from JAX's
monitoring events (a cache hit compiles nothing)."""


def read(ctx):
    return ctx.compile_s
