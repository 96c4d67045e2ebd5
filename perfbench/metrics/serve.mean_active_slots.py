"""Mean number of occupied decode slots per decode step over the window:
the tokens decoded (all served tokens but each request's first, which
prefill makes) over the engine's decode steps. Counted by the program."""


def read(ctx):
    c = ctx.window.counters
    return c["mean_active_slots"] if c.get("decode_steps") else None
