"""Device time of the decode program (``jit_serve_decode``) per engine
iteration of the traced wave."""


def read(ctx):
    import programs
    return programs.module_ms(ctx, "jit_serve_decode")
