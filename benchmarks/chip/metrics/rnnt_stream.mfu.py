"""The whole transducer step's share of the chip's peak: the operations
of every product the window ran (CSB products from the survivor counts,
the joint's dense products) over the window's wall time and the bf16
peak."""


def read(ctx):
    out = ctx.out
    if not out.get("frame_steps") or not ctx.peaks:
        return None
    mod, cfg = ctx.cell.config_mod, ctx.cell.config
    ops = sum(f for f, _ in mod.run_work(
        cfg, out["streams"], out["frame_steps"], out["enc_steps"],
        out["label_steps"]))
    ops += mod.dense_ops(cfg, out["streams"], out["enc_steps"],
                         out["label_steps"])
    return 100.0 * ops / ctx.window_s / ctx.peaks["bf16_flops_per_s"]
