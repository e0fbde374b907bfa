"""Engine step: the window's host seconds over its steps, in ms."""


def read(ctx):
    if not ctx.window.steps:
        return None
    return ctx.window.step_ms()
