"""Device time of one run of the decode-step program (profiler trace;
the program is found by its run count, see drivers/lm.py)."""


def read(ctx):
    if ctx.trace is None:
        return None
    decode, _ = ctx.cell.driver.find_programs(
        ctx.trace.module_runs(), ctx.traced("decode_steps"),
        ctx.traced("prefills_by_len"))
    if not decode:
        return None
    runs, ns = decode[0]
    return ns / runs / 1e6
