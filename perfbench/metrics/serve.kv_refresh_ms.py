"""Device time of the KV pools' re-encode (``jit_cache_encode``, packing
included) per engine iteration of the traced wave."""


def read(ctx):
    import programs
    return programs.module_ms(ctx, "jit_cache_encode")
