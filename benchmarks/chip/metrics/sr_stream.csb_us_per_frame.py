"""Device time of the CSB kernel calls per frame step, warm-up steps
inside each call included (profiler trace)."""


def read(ctx):
    if ctx.trace is None:
        return None
    steps = ctx.traced("frame_steps")
    if not steps:
        return None
    ns = ctx.trace.op_ns(ctx.cell.config_mod.CSB_KERNEL.search)
    return ns / 1e3 / steps if ns else None
