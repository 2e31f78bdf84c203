"""Slot occupancy of the LM engine over its decode steps, weighted by
decode steps across calls (the scheduler's own count)."""


def read(ctx):
    steps = ctx.out.get("decode_steps")
    return 100.0 * ctx.out["occupied_steps"] / steps if steps else None
