"""Median, over the traced window's decode steps, of the host's own time in
``ContinuousEngine.step``: the program's ``serve.step`` span less its
``serve.step.wait`` child (the blocking token read). What is left is the
page mapping and page-table upload, the dispatch of the step program and
the retirement bookkeeping."""
import progspans


def read(ctx):
    return progspans.host_ms(ctx, progspans.STEP)
