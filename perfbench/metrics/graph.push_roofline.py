"""The push kernel's share of its roofline: 8 bytes per edge and 8 per
vertex over the bandwidth (2 FLOPs per edge bound it less), against the
kernel's device time, over the traced iterations."""


def read(ctx):
    tw = ctx.window.traced
    sec = ctx.trace.group_seconds.get("push", 0.0) if ctx.trace else 0.0
    if sec <= 0 or "push_need_s" not in tw:
        return None
    return 100.0 * tw["push_need_s"] * tw["iterations"] / sec
