"""Seconds from process start to the first timed call: JAX start-up, the
inputs, loading or compiling the programs, and the warm-up."""


def read(ctx):
    return ctx.run.setup_s
