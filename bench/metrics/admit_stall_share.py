"""Share of the window spent inside ``ContinuousEngine.try_admit`` (the
benchmark's ``bench.admit`` spans on the host clock); blocking admission
stalls every live slot for that time."""


def read(ctx):
    w = ctx.window
    return 100.0 * w.admit_s / w.seconds
