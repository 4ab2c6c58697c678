"""Host seconds of ModelSpec.build until the connectivity is on the
device, less the compile seconds inside it."""


def read(ctx):
    return ctx["setup"]["build_s"]
