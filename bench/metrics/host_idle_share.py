"""Share of the traced window in which device 0 runs no op while the host
is inside the program's own step or admission work: the device's idle time
intersected with the union of the window's ``serve.step`` and
``serve.admit`` spans less their ``.wait`` children (the blocking reads).
It is the part of ``idle_share`` that the program's host path explains."""
import progspans


def read(ctx):
    a = progspans.aligned(ctx)
    if a is None or not ctx.trace.ops:
        return None
    idle = progspans.overlap_ns(progspans.host_pieces(a),
                                progspans.idle_pieces(ctx.trace))
    return 100.0 * idle * 1e-9 / ctx.trace.window_s
