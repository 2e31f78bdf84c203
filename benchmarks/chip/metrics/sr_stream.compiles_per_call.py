"""Compile requests per entry call in the window, counted by a JAX
monitoring listener in the harness. Each is a fresh trace and lowering
of the frame server's step; with the persistent cache warm it is served
from the cache rather than compiled (the log line splits the two)."""


def read(ctx):
    if not ctx.calls:
        return None
    return ctx.compiles["compile_requests"] / ctx.calls
