"""The CSB kernel's share of its roofline: the least time of the products
the window needed (each the larger of operations over peak FLOP/s and
bytes over peak bandwidth, from the configuration's survivor counts)
over the kernel's device time in the trace."""


def read(ctx):
    if ctx.trace is None or not ctx.peaks:
        return None
    steps = ctx.traced("frame_steps")
    if not steps:
        return None
    ns = ctx.trace.op_ns(ctx.cell.config_mod.CSB_KERNEL.search)
    if not ns:
        return None
    fl, bw = ctx.peaks["bf16_flops_per_s"], ctx.peaks["hbm_bytes_per_s"]
    work = ctx.cell.config_mod.csb_work(ctx.cell.config, ctx.out["streams"])
    least = steps * sum(max(f / fl, b / bw) for f, b in work)
    return 100.0 * least / (ns / 1e9)
