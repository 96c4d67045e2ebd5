"""The whole engine's share of the chip's peak over the traced wave: the
least time the wave's work needs (weights once per decode step and per
prefill, the KV of live tokens once, the sidecars the policy's cadence
reads and writes, each phase by the larger of its FLOPs over the peak and
its bytes over the bandwidth) over the wave's wall time in the trace."""


def read(ctx):
    tw = ctx.window.traced
    if not ctx.trace or "need_s" not in tw:
        return None
    return 100.0 * tw["need_s"] / ctx.trace.window_s
