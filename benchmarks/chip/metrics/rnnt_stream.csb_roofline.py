"""The CSB kernel's share of its roofline in the transducer cell: the
least time of the products the traced window ran (encoder layers at
their frame rates and the prediction network at every label step; each
product the larger of operations over peak FLOP/s and bytes over peak
bandwidth, from the configuration's survivor counts) over the kernel's
device time in the trace."""


def read(ctx):
    if ctx.trace is None or not ctx.peaks:
        return None
    steps = ctx.traced("frame_steps")
    if not steps:
        return None
    mod, cfg = ctx.cell.config_mod, ctx.cell.config
    ns = ctx.trace.op_ns(mod.CSB_KERNEL.search)
    if not ns:
        return None
    fl, bw = ctx.peaks["bf16_flops_per_s"], ctx.peaks["hbm_bytes_per_s"]
    work = mod.run_work(cfg, ctx.out["streams"], steps,
                        ctx.traced("enc_steps"), ctx.traced("label_steps"))
    least = sum(max(f / fl, b / bw) for f, b in work)
    return 100.0 * least / (ns / 1e9)
