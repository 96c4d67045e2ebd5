"""Device time of the blocked push kernel per PageRank iteration of the
traced chunks."""


def read(ctx):
    tw = ctx.window.traced
    if not ctx.trace or not ctx.trace.group_counts.get("push"):
        return None
    return 1e3 * ctx.trace.group_seconds["push"] / tw["iterations"]
