"""Wall seconds during set-up in which JAX compiled: the union of its
/jax/core/compile/* spans (tracing, lowering, compiling), each nested span
counted once."""


def read(ctx):
    return ctx["setup"]["compile_s"]
