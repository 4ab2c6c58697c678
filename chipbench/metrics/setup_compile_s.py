"""Seconds of JAX's /jax/core/compile/* events (tracing, lowering,
compiling) during set-up."""


def read(ctx):
    return ctx["setup"]["compile_s"]
