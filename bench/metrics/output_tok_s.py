"""Tokens emitted inside the window (decode steps and the first tokens of
admissions), whether or not their request finished, over the window."""


def read(ctx):
    w = ctx.window
    return w.tokens / w.seconds
