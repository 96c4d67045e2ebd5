"""The decode program's share of its HBM roofline in the traced wave: the
bytes its steps must move (the weights once per step, and each cached
token read once, the ``ctx_tokens`` of every ``serve.decode`` span times
the cache's bytes per token; the work module's ``decode_hbm_bytes``)
over the bandwidth, against ``jit_serve_decode``'s device time. None
where the program stamps no ``ctx_tokens`` or the configuration's work
module does not count the decode's bytes."""


def read(ctx):
    import bench
    import programs
    p = programs.of(ctx)
    if not p:
        return None
    ctx_tokens = p.span_stats.get("serve.decode", {}).get("ctx_tokens")
    steps = p.span_counts.get("serve.decode")
    sec = p.module_total("jit_serve_decode")
    work = bench.load_module("work", ctx.config["kind"])
    if not ctx_tokens or not steps or not sec \
            or not hasattr(work, "decode_hbm_bytes"):
        return None
    need = work.decode_hbm_bytes(ctx.config, steps, ctx_tokens) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * need / (sec / p.n_devices)
