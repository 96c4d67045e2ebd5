"""Device time of the prefill program (``jit_serve_prefill``) per engine
iteration of the traced wave."""


def read(ctx):
    import programs
    return programs.module_ms(ctx, "jit_serve_prefill")
