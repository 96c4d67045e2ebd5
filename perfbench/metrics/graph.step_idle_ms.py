"""Device idle time while the host is inside ``graph.step`` (the power
iteration's push, dispatched op by op) and no inner span, per PageRank
iteration of the traced chunks."""


def read(ctx):
    import programs
    p = programs.of(ctx)
    if p is None:
        return None
    return programs.per_iteration_ms(ctx, p.span_idle_seconds.get(
        "graph.step"))
