"""Kernels: device self time per window step under the model's
``decode_attention`` phase of ``orca/step`` — the paged decode kernel with
the operand preparation around it (ms).  Nothing without the trace's
scopes."""
from bench import scopes as S


def read(ctx):
    red = S.from_ctx(ctx)
    if red is None or not ctx.window.steps:
        return None
    return 1e3 * red.under("decode_attention") / len(ctx.window.steps)
