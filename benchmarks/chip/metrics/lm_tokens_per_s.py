"""Generated tokens of completed requests over the window's wall time
(host clock)."""


def read(ctx):
    tokens = ctx.out.get("generated_tokens")
    return tokens / ctx.window_s if tokens else None
