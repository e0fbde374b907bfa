"""Model step: device self time per window step under the program's
``orca/probe`` scope — ORCA's pooling, boundary-gated score-then-update
kernel and smoothing: what calibration costs the step (ms).  Nothing
without the trace's scopes."""
from bench import scopes as S


def read(ctx):
    red = S.from_ctx(ctx)
    if red is None or not ctx.window.steps:
        return None
    return 1e3 * red.under("probe") / len(ctx.window.steps)
