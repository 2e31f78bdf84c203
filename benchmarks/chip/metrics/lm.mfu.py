"""The whole LM step's share of the chip's peak: forward operations of
every prompt and generated token of the window (from the configuration's
shapes), over the window's wall time and the bf16 peak."""


def read(ctx):
    out = ctx.out
    if not out.get("generated_tokens") or not ctx.peaks:
        return None
    ops = ctx.cell.config_mod.flops(
        ctx.cell.config,
        out["prompt_tokens"] + out["generated_tokens"] - out["completed"],
        out["generated_tokens"])
    return 100.0 * ops / ctx.window_s / ctx.peaks["bf16_flops_per_s"]
