"""Seconds from process start to the first timed call: JAX start-up,
construction, compiling (or loading from the compile cache) and the
warm-up call of the cell's own shapes."""


def read(ctx):
    return ctx["setup"]["setup_s"]
