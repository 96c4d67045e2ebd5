"""Device time of the ECC kernels (SEC-DED and parity, encode, scrub and
check) per engine iteration of the traced wave."""


def read(ctx):
    tw = ctx.window.traced
    if not ctx.trace or not ctx.trace.group_counts.get("ecc") \
            or not tw.get("iterations"):
        return None
    return 1e3 * ctx.trace.group_seconds["ecc"] / tw["iterations"]
