"""Scheduler: mean share of the slots that decoded a token, per window step
(counted from the requests the program served)."""


def read(ctx):
    steps = ctx.window.steps
    if not steps:
        return None
    slots = ctx.window.n_slots
    return 100.0 * sum(len(s.decode_cached) for s in steps) / (
        slots * len(steps))
