"""Network-steps completed per second of the measured window: every step
of every call that finished, over the time from the window's start to the
end of its last call.  A gScale candidate counts as one network."""


def read(ctx):
    w = ctx["window"]
    return w["net_steps"] / w["seconds"]
