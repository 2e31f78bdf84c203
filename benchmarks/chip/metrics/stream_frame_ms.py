"""Window wall time over frame steps completed; a frame step is one
10 ms frame of every stream through every layer (host clock)."""


def read(ctx):
    steps = ctx.out.get("frame_steps")
    return 1e3 * ctx.window_s / steps if steps else None
