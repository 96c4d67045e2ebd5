"""Device time of the ECC kernels (the ranks' parity encode, the scrub
slices' SEC-DED scrub and parity check) per PageRank iteration of the
traced chunks."""


def read(ctx):
    tw = ctx.window.traced
    if not ctx.trace or not ctx.trace.group_counts.get("ecc"):
        return None
    return 1e3 * ctx.trace.group_seconds["ecc"] / tw["iterations"]
