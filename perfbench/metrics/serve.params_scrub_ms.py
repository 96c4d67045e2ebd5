"""Device time of the params patrol scrub (``jit_params_scrub``) per
engine iteration of the traced wave, over all iterations (it runs on the
policy's cadence)."""


def read(ctx):
    import programs
    return programs.module_ms(ctx, "jit_params_scrub")
