"""The memory domain's programs' share of the device's busy time in the
traced wave: every scrub and encode program (``jit_<kind>_scrub``,
``_scrub_slice``, ``_encode``, ``_encode_rows``), packing included."""


def read(ctx):
    import programs
    p = programs.of(ctx)
    if not p or not ctx.trace.busy_s:
        return None
    sec = p.module_total(*(n for n in p.module_seconds
                           if programs.DOMAIN_PROGRAM.match(n)))
    if sec is None:
        return None
    return 100.0 * sec / p.n_devices / ctx.trace.busy_s
