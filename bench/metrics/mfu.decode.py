"""Model step: useful FLOPs the window served over its host seconds, as a
share of the chip's bf16 peak.  Decode tokens count their active weights,
the LM head and attention over their valid context; prefilled prompt
tokens count their active weights and causal attention over their prefix
(the configuration's family, ``ctx.family``)."""


def read(ctx):
    w = ctx.window
    if not w.steps:
        return None
    flops = 0.0
    for s in w.steps:
        for p in s.decode_cached:
            flops += ctx.family.token_flops(ctx.cfg, p + 1, head=True)
        for start, n in s.chunk_segs:
            flops += ctx.family.chunk_flops(ctx.cfg, start, n)
    return 100.0 * flops / w.seconds / ctx.chip.flops_bf16
