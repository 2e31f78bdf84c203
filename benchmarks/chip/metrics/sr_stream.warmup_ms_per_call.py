"""The frame server's warm-up phase per call, in ms: the fresh step's
trace, lowering and cache lookup and its warm-up device steps
(``serve/frames/warmup`` spans over ``serve/frames/call`` spans, traced
window)."""
import spans


def read(ctx):
    n = spans.calls(ctx.trace)
    if not n:
        return None
    return spans.total_ns(ctx.trace, spans.WARMUP) / n / 1e6
