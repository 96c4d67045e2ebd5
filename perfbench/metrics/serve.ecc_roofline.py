"""The ECC kernels' share of their roofline in the traced wave: the bytes
their calls need (a check or scrub reads payload and sidecar once, an
encode reads the payload and writes the sidecar once) over the
bandwidth, against their device time."""


def read(ctx):
    tw = ctx.window.traced
    if not ctx.trace or not tw.get("ecc_need_s"):
        return None
    sec = ctx.trace.group_seconds.get("ecc", 0.0)
    return 100.0 * tw["ecc_need_s"] / sec if sec > 0 else None
