"""The transducer's greedy decode (``serve/rnnt/decode`` spans: the
decode program's dispatch and run, to its labels) per 30 ms input frame
step of the traced window, in us."""
import spans

CALL, DECODE = "serve/rnnt/call", "serve/rnnt/decode"


def read(ctx):
    steps = ctx.traced("frame_steps")
    if not spans.spans(ctx.trace, CALL) or not steps:
        return None
    return spans.total_ns(ctx.trace, DECODE) / 1e3 / steps
