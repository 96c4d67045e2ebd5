"""Per-program reduction of a profiler trace, beside ``reduce_trace.py``:
the device time of each compiled program, and the host time and device
idle time of each program span.

- ``module_seconds``, ``module_counts``: device time and executions of
  each program, from the ``XLA Modules`` line of each device plane,
  clipped to the window and summed over the devices, with the program id
  (``jit_serve_decode(123)`` -> ``jit_serve_decode``) stripped.
- ``span_seconds``, ``span_counts``, ``span_stats``: host time, count and
  the sum of each integer stat of each program span (``serve.*``,
  ``graph.*``) inside the window.
- ``span_idle_seconds``: the first device's idle time inside the window,
  put down to the innermost program span covering it (or to the
  harness's own span where none does); ``span_idle_each`` holds the idle
  time inside each instance of a span, its children's included, in order.

The window is ``reduce_trace``'s: the harness's spans, first start to last
end. The metrics read a run's trace where ``run.py``'s tracer left it,
through ``of(ctx)``; a trace from a program without these programs or
spans gives empty tables, and their readers then give no value.

Run as a script to print a trace's tables:
    python3 perfbench/programs.py <trace.xplane.pb> [span ...]
"""
from __future__ import annotations

import bisect
import re
import sys
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import bench
import reduce_trace as rt

MODULES_LINE = "XLA Modules"
PROGRAM_SPANS = ("serve.", "graph.")
TRACE_DIR = bench.ROOT / ".bench_trace"    # where run.py's tracer writes
# the memory domain's programs: <root kind>_<pass>
DOMAIN_PROGRAM = re.compile(
    r"^jit_(params|opt|cache|graph|domain)_(scrub|scrub_slice|encode|"
    r"encode_rows)$")
_PROGRAM_ID = re.compile(r"\(\d+\)$")


@dataclass
class ProgramSummary:
    n_devices: int
    module_seconds: Dict[str, float]
    module_counts: Dict[str, int]
    span_seconds: Dict[str, float]
    span_counts: Dict[str, int]
    span_stats: Dict[str, Dict[str, float]]
    span_idle_seconds: Dict[str, float]
    span_idle_each: Dict[str, List[float]]

    def module_total(self, *names: str) -> Optional[float]:
        """Device seconds of the named programs, or None where none ran."""
        found = [self.module_seconds[n] for n in names
                 if n in self.module_seconds]
        return sum(found) if found else None


def _host_line(pd, spans: Sequence[str]):
    """Events (start, end, name, stats) of the host thread that wrote the
    harness's spans."""
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(float(e.start_ns), float(e.end_ns), e.name, e)
                   for e in line.events]
            if any(n in spans for _, _, n, _ in evs):
                return evs
    return []


def _device_planes(pd, n_devices: Optional[int]) -> List:
    planes = [p for p in pd.planes
              if p.name.startswith("/device:") and any(True for _ in p.lines)]
    return planes[:n_devices] if n_devices is not None else planes


class _Idle:
    """Idle time of sorted disjoint gaps inside any interval, by prefix
    sums."""

    def __init__(self, gaps: List[Tuple[float, float]]):
        self.starts = [a for a, _ in gaps]
        self.ends = [b for _, b in gaps]
        self.before = [0.0]
        for a, b in gaps:
            self.before.append(self.before[-1] + (b - a))

    def upto(self, t: float) -> float:
        i = bisect.bisect_right(self.starts, t) - 1
        if i < 0:
            return 0.0
        return self.before[i] + min(t, self.ends[i]) - self.starts[i]

    def seconds(self, a: float, b: float) -> float:
        return (self.upto(b) - self.upto(a)) * 1e-9


def _innermost_idle(spans, idle: _Idle) -> Dict[str, float]:
    """Idle seconds by the innermost span over each stretch of time.
    Spans of one thread nest, so a sweep over their ends with a stack
    does it; ends go before starts at the same instant, and of two
    starts at one instant the outer goes first."""
    marks = sorted([(a, 1, -b, i) for i, (a, b, _) in enumerate(spans)]
                   + [(b, 0, 0, i) for i, (_, b, _) in enumerate(spans)])
    out: Dict[str, float] = defaultdict(float)
    stack: List[int] = []
    t = None
    for at, is_start, _, i in marks:
        if stack and at > t:
            out[spans[stack[-1]][2]] += idle.seconds(t, at)
        t = at
        if is_start:
            stack.append(i)
        elif i in stack:
            stack.remove(i)
    return dict(out)


def summarize(path: str, spans: Sequence[str] = rt.HOST_SPANS,
              n_devices: Optional[int] = None) -> ProgramSummary:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host = _host_line(pd, spans)
    harness = [(a, b, n) for a, b, n, _ in host if n in spans]
    if not harness:
        raise ValueError(f"none of the host spans {tuple(spans)} in {path}")
    lo = min(a for a, _, _ in harness)
    hi = max(b for _, b, _ in harness)
    planes = _device_planes(pd, n_devices)
    mod_s: Dict[str, float] = defaultdict(float)
    mod_n: Dict[str, int] = defaultdict(int)
    for plane in planes:
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            for e in line.events:
                a, b = float(e.start_ns), float(e.end_ns)
                if b <= lo or a >= hi:
                    continue
                name = _PROGRAM_ID.sub("", e.name)
                mod_s[name] += (min(b, hi) - max(a, lo)) * 1e-9
                mod_n[name] += 1
    # the first device's idle stretches inside the window
    gaps: List[Tuple[float, float]] = []
    ops = rt._device_lines(pd)[:1]
    if ops:
        busy = rt._clip(rt._union([(float(e.start_ns), float(e.end_ns))
                                   for e in ops[0].events]), lo, hi)
        t = lo
        for a, b in busy + [(hi, hi)]:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
    prog = [(a, b, n, e) for a, b, n, e in host
            if n.startswith(PROGRAM_SPANS) and a >= lo and b <= hi]
    span_s: Dict[str, float] = defaultdict(float)
    span_n: Dict[str, int] = defaultdict(int)
    stats: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(
        float))
    each: Dict[str, List[float]] = defaultdict(list)
    idle = _Idle(gaps)
    for a, b, n, e in sorted(prog, key=lambda x: x[0]):
        span_s[n] += (b - a) * 1e-9
        span_n[n] += 1
        each[n].append(idle.seconds(a, b))
        for k, v in e.stats:
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                stats[n][k] += v
    return ProgramSummary(
        n_devices=len(planes), module_seconds=dict(mod_s),
        module_counts=dict(mod_n), span_seconds=dict(span_s),
        span_counts=dict(span_n),
        span_stats={k: dict(v) for k, v in stats.items()},
        span_idle_seconds=_innermost_idle(
            [(a, b, n) for a, b, n, _ in prog] + harness, idle),
        span_idle_each=dict(each))


def of(ctx) -> Optional[ProgramSummary]:
    """The tables of the run's trace, or None without one; read once and
    kept on the context that the run's readers share."""
    if ctx.trace is None:
        return None
    if not hasattr(ctx, "program_summary"):
        try:
            path = rt.find_xplane(str(TRACE_DIR))
        except FileNotFoundError:
            path = None
        ctx.program_summary = (summarize(path, n_devices=ctx.trace.n_devices)
                               if path else None)
    return ctx.program_summary


def per_iteration_ms(ctx, seconds: Optional[float]) -> Optional[float]:
    """Milliseconds per iteration of the traced units."""
    iters = ctx.window.traced.get("iterations")
    if seconds is None or not iters:
        return None
    return 1e3 * seconds / iters


def module_ms(ctx, *names: str) -> Optional[float]:
    """Device milliseconds of the named programs per traced iteration."""
    p = of(ctx)
    return per_iteration_ms(ctx, p.module_total(*names)) if p else None


if __name__ == "__main__":
    s = summarize(sys.argv[1], tuple(sys.argv[2:]) or rt.HOST_SPANS)
    print(f"modules over {s.n_devices} device(s): "
          f"{sum(s.module_seconds.values()):.6f} s")
    for name, sec in sorted(s.module_seconds.items(), key=lambda kv: -kv[1]):
        print(f"  module {sec:12.6f} s  x{s.module_counts[name]:<6d} {name}")
    for name, sec in sorted(s.span_seconds.items(), key=lambda kv: -kv[1]):
        print(f"  span   {sec:12.6f} s  x{s.span_counts[name]:<6d} {name} "
              f"idle {s.span_idle_seconds.get(name, 0.0):.6f} s "
              f"stats {s.span_stats.get(name, {})}")
    for name, sec in s.span_idle_seconds.items():
        if name not in s.span_seconds:
            print(f"  idle   {sec:12.6f} s  outside program spans, in {name}")
    for name in ("serve.iteration", "graph.iteration"):
        if name in s.span_idle_each:
            print(f"  idle per {name} (ms): " + " ".join(
                f"{1e3 * x:.3f}" for x in s.span_idle_each[name]))
