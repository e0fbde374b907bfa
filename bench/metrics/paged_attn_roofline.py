"""Kernel ``paged_flash_decode``: least time the chip needs for the decode
attention the traced window served (valid cached positions only, counted by
the configuration's family, ``ctx.family``) over the kernel's device time
in the trace.  Nothing when the kernel did not run."""
from bench import roofline as RF

KERNEL = "paged_flash_decode"


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = ctx.trace.kernel_seconds(KERNEL)
    if seconds <= 0:
        return None
    flops = nbytes = 0.0
    for s in ctx.window.steps:
        f, b = ctx.family.decode_attention_work(ctx.cfg, s.decode_cached)
        flops, nbytes = flops + f, nbytes + b
    if flops == 0 and nbytes == 0:
        return None
    return RF.roofline_share(flops, nbytes, seconds, ctx.chip)[0]
