"""The whole frame step's share of the chip's peak: 2 survivors streams
operations per frame step, times frame steps per second of the window,
over the bf16 peak."""


def read(ctx):
    steps = ctx.out.get("frame_steps")
    if not steps or not ctx.peaks:
        return None
    work = ctx.cell.config_mod.csb_work(ctx.cell.config, ctx.out["streams"])
    ops = steps * sum(f for f, _ in work)
    return 100.0 * ops / ctx.window_s / ctx.peaks["bf16_flops_per_s"]
