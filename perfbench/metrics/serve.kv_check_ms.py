"""Device time of the KV pools' check (``jit_cache_scrub``, packing
included) per engine iteration of the traced wave."""


def read(ctx):
    import programs
    return programs.module_ms(ctx, "jit_cache_scrub")
