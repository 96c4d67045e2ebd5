"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy time over the traced window, device time per
operation, and the idle gaps, each put down to what the host was doing.

The window is the union of the harness's own host spans (written with
``jax.profiler.TraceAnnotation``: ``wave``, ``warmup``, ``pagerank_chunk``)
from the first span's start to the last one's end. A device's busy time
is the union of the intervals of its operations (the ``XLA Ops`` line of
each ``/device:TPU:<n>`` plane) clipped to the window, averaged over the
chips used. An idle gap inside the window is named after the innermost
host event on the spans' thread that covers the gap's middle, or, where
none does, after the last one that ended before it (``after <name>``).

Run as a script to print a trace's summary:
    python3 perfbench/reduce_trace.py <trace.xplane.pb> [span ...]
"""
from __future__ import annotations

import glob
import os
import sys
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

HOST_SPANS = ("wave", "warmup", "pagerank_chunk")
OPS_LINE = "XLA Ops"


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                                   # mean over devices
    n_devices: int
    op_seconds: Dict[str, float]                    # name -> device s
    op_counts: Dict[str, int]
    idle_by_host: Dict[str, float] = field(default_factory=dict)
    spans: Dict[str, int] = field(default_factory=dict)   # name -> count
    group_seconds: Dict[str, float] = field(default_factory=dict)
    group_counts: Dict[str, int] = field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, k: int = 10) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:k]
        gaps = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:k]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def _op_name(ev) -> str:
    """An operation's name, with its program where the trace gives it, so
    that ``fusion.3`` of two programs stay apart."""
    module = None
    for k, v in ev.stats:
        if k in ("hlo_module", "program_name"):
            module = str(v)
            break
    return f"{module}/{ev.name}" if module else ev.name


def _device_lines(pd) -> List:
    """The operations line of each device plane: ``XLA Ops`` where the
    plane has one, else its busiest line other than modules and steps."""
    lines = []
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        cand = list(plane.lines)
        ops = [ln for ln in cand if ln.name == OPS_LINE]
        if not ops:
            ops = sorted((ln for ln in cand if ln.name not in
                          ("XLA Modules", "Steps")),
                         key=lambda ln: -sum(1 for _ in ln.events))
        if ops:
            lines.append(ops[0])
    return lines


def _host_events(pd, spans: Sequence[str]):
    """(spans found, every event of the host thread that wrote them)."""
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(float(e.start_ns), float(e.end_ns), e.name)
                   for e in line.events]
            found = [(a, b, n) for a, b, n in evs if n in spans]
            if found:
                return found, evs
    return [], []


def _attribute(inner, gaps) -> Dict[str, float]:
    """Seconds of each idle gap by the innermost host event that covers
    its middle, or else by the last one that ended before it. Host events
    of one thread nest, so one sweep with a stack does it."""
    idle: Dict[str, float] = defaultdict(float)
    stack: List[Tuple[float, float, str]] = []
    last = "before the window"
    i = 0
    for ga, gb in gaps:
        t = (ga + gb) / 2
        while i < len(inner) and inner[i][0] <= t:
            a, b, n = inner[i]
            while stack and stack[-1][1] < a:
                last = stack.pop()[2]
            stack.append(inner[i])
            i += 1
        while stack and stack[-1][1] < t:
            last = stack.pop()[2]
        name = stack[-1][2] if stack else f"after {last}"
        idle[name] += (gb - ga) * 1e-9
    return idle


def _groups_of(ev, name: str, groups: Dict[str, Sequence[str]]
               ) -> List[str]:
    """Kernel groups whose names appear in the event's name or stats."""
    text = name + " " + " ".join(str(v) for _, v in ev.stats)
    return [g for g, names in groups.items() if any(n in text for n in names)]


def summarize(path: str, spans: Sequence[str] = HOST_SPANS,
              n_devices: Optional[int] = None,
              groups: Optional[Dict[str, Sequence[str]]] = None
              ) -> TraceSummary:
    """``groups`` maps a kernel group to the names that find its events
    (in an event's name or any of its stats); their device time and count
    land in ``group_seconds`` and ``group_counts``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    found, host = _host_events(pd, spans)
    if not found:
        raise ValueError(f"none of the host spans {tuple(spans)} in {path}")
    lo = min(a for a, _, _ in found)
    hi = max(b for _, b, _ in found)
    lines = _device_lines(pd)
    if n_devices is not None:
        lines = lines[:n_devices]
    if not lines:
        raise ValueError(f"no device plane with an {OPS_LINE!r} line")
    op_s: Dict[str, float] = defaultdict(float)
    op_n: Dict[str, int] = defaultdict(int)
    grp_s: Dict[str, float] = defaultdict(float)
    grp_n: Dict[str, int] = defaultdict(int)
    seen: Dict[str, List[str]] = {}
    busy_total = 0.0
    first_busy = None
    for line in lines:
        ivs = []
        for e in line.events:
            a, b = float(e.start_ns), float(e.end_ns)
            if b <= lo or a >= hi:
                continue
            ivs.append((a, b))
            name = _op_name(e)
            dur = (min(b, hi) - max(a, lo)) * 1e-9
            op_s[name] += dur
            op_n[name] += 1
            if groups:
                if name not in seen:
                    seen[name] = _groups_of(e, name, groups)
                for g in seen[name]:
                    grp_s[g] += dur
                    grp_n[g] += 1
        merged = _clip(_union(ivs), lo, hi)
        busy_total += sum(b - a for a, b in merged)
        if first_busy is None:
            first_busy = merged
    # idle gaps of the first device, put down to the host's activity
    inner = sorted(host)
    gaps, t = [], lo
    for a, b in first_busy + [(hi, hi)]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    idle = _attribute(inner, gaps)
    counts: Dict[str, int] = defaultdict(int)
    for _, _, n in found:
        counts[n] += 1
    return TraceSummary(
        window_s=(hi - lo) * 1e-9, busy_s=busy_total / len(lines) * 1e-9,
        n_devices=len(lines), op_seconds=dict(op_s), op_counts=dict(op_n),
        idle_by_host=dict(idle), spans=dict(counts),
        group_seconds=dict(grp_s), group_counts=dict(grp_n))


if __name__ == "__main__":
    import json
    kernels = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "kernels.json")
    with open(kernels) as f:
        groups = {k: v for k, v in json.load(f).items() if k != "why"}
    s = summarize(sys.argv[1], tuple(sys.argv[2:]) or HOST_SPANS,
                  groups=groups)
    print(f"kernel groups: seconds {s.group_seconds}, "
          f"counts {s.group_counts}")
    print(f"window {s.window_s:.6f} s, busy {s.busy_s:.6f} s "
          f"(idle {s.idle_share:.2%}) over {s.n_devices} device(s); "
          f"spans {s.spans}")
    for name, sec in s.breakdown(25)["device_ops"]:
        print(f"  op  {sec:12.6f} s  x{s.op_counts[name]:<6d} {name}")
    for name, sec in s.breakdown(15)["idle_gaps"]:
        print(f"  idle {sec:12.6f} s  {name}")
