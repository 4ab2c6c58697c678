"""Host seconds of ModelSpec.build until the connectivity is on the
device, less the seconds in which JAX compiled inside it (the union of
its compile spans, so never more than the build's wall time)."""


def read(ctx):
    return ctx["setup"]["build_s"]
