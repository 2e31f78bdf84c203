"""Share of the traced window in the frame server's warm-up phase
(``serve/frames/warmup`` spans): building and warming a fresh step a
call."""
import spans


def read(ctx):
    if not spans.calls(ctx.trace):
        return None
    return 100.0 * spans.total_ns(ctx.trace, spans.WARMUP) \
        / ctx.trace.window_ns
