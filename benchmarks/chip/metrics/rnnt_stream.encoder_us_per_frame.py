"""The transducer's encoder layers (``serve/rnnt/encoder`` spans: each
layer a frame-server call, to its outputs) per 30 ms input frame step of
the traced window, in us."""
import spans

CALL, ENCODER = "serve/rnnt/call", "serve/rnnt/encoder"


def read(ctx):
    steps = ctx.traced("frame_steps")
    if not spans.spans(ctx.trace, CALL) or not steps:
        return None
    return spans.total_ns(ctx.trace, ENCODER) / 1e3 / steps
