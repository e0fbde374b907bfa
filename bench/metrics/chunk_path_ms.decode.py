"""Model step: device self time per window step under the program's
``orca/chunk_prefill`` scope — the packed prefill chunk's ``lax.cond`` and
both its branches, so also what a step without prefill pays for it (ms).
Nothing without the trace's scopes."""
from bench import scopes as S


def read(ctx):
    red = S.from_ctx(ctx)
    if red is None or not ctx.window.steps:
        return None
    return 1e3 * red.under("chunk_prefill") / len(ctx.window.steps)
