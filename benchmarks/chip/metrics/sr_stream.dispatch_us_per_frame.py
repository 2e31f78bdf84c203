"""The frame server's per-frame dispatch loop (``frames[t]`` and the
step's enqueue, ``serve/frames/dispatch`` spans of both layers) per
frame step of the traced window, in us."""
import spans


def read(ctx):
    steps = ctx.traced("frame_steps")
    if not spans.calls(ctx.trace) or not steps:
        return None
    return spans.total_ns(ctx.trace, spans.DISPATCH) / 1e3 / steps
