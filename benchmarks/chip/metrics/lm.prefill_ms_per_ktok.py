"""Device time of the prefill programs per thousand prompt tokens
(profiler trace; the programs are found by their run counts, see
drivers/lm.py)."""


def read(ctx):
    if ctx.trace is None:
        return None
    by_len = ctx.traced("prefills_by_len")
    _, prefill = ctx.cell.driver.find_programs(
        ctx.trace.module_runs(), ctx.traced("decode_steps"), by_len)
    tokens = sum(int(n) * c for n, c in by_len.items())
    if not prefill or not tokens:
        return None
    return sum(ns for _, ns in prefill) / 1e6 / (tokens / 1e3)
