"""Median, over the traced window's admissions, of the host's own time in
``ContinuousEngine.try_admit``: the program's ``serve.admit`` span less its
``serve.admit.wait`` child (the blocking first-token read). What is left is
the slot and page claim, the B=1 row, the dispatch of the prefill and the
pool scatter, and the bookkeeping."""
import progspans


def read(ctx):
    return progspans.host_ms(ctx, progspans.ADMIT)
