"""Stream-frames completed through every layer over the window's wall
time (host clock)."""


def read(ctx):
    steps = ctx.out.get("frame_steps")
    if not steps:
        return None
    return ctx.out["streams"] * steps / ctx.window_s
