"""The whole iteration's share of the chip's peak: the least time an
iteration needs (the push, the ranks' encode and one scrub slice, by the
larger of FLOPs over the peak and bytes over the bandwidth) times the
traced iterations, over the traced chunks' wall time."""


def read(ctx):
    tw = ctx.window.traced
    if not ctx.trace or "iter_need_s" not in tw:
        return None
    return 100.0 * tw["iter_need_s"] * tw["iterations"] / ctx.trace.window_s
