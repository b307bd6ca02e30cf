"""Share of the pool's slots that held a live request, over the window's
decode steps (``ServeStats.live_slot_steps`` over ``steps`` x slots)."""


def read(ctx):
    w = ctx.window
    if not w.steps:
        return None
    return 100.0 * w.live_slot_steps / (w.steps * ctx.n_slots)
