#!/usr/bin/env python3
"""Benchmark of the HRM system on a TPU: one cell per call.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``BENCHMARK.json`` (at the checkout's root). Its
configuration, traffic mix and policy are files found by name under
``perfbench/``; the traffic names the runner that drives the program. A
run sets up the program (weights or data made from the seed on the
device, every program of the window compiled or loaded from the
persistent cache), measures for ``--seconds`` over whole units of work,
reads the peak device memory, frees the program's state and checks what
the window produced against the plain reference.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the profiler records the window's first unit and the
result carries the per-layer metrics and a breakdown. The last line of
standard output is one JSON object; the numbers compared, each with its
limit, close standard error and the result line. Without a TPU, or with
fewer chips than the cell asks for, the run exits 3 and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench  # noqa: E402

TRACE_DIR = bench.ROOT / ".bench_trace"


class Tracer:
    """Starts and stops the profiler around the units a runner picks."""

    def __init__(self, log_dir: Path):
        self.log_dir = log_dir
        self.path = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()
        from reduce_trace import find_xplane
        self.path = find_xplane(str(self.log_dir))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def per_layer(spec, workload, ctx) -> dict:
    out = {}
    for m in bench.metrics_for(spec, workload, "per_layer"):
        value = bench.load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(args, *, require_tpu: bool = True, root: Path = bench.ROOT,
        hooks=None) -> dict:
    """One run of a cell; returns the result object. ``require_tpu=False``
    skips the look for a chip (tests on the CPU); ``hooks`` may break the
    runner's cell after set-up (the fault tests)."""
    spec = bench.benchmark_spec(root)
    chips = int(bench.find_workload(spec, args.workload)["chips"])
    bench.add_program_path()
    bench.configure_jax()
    device = (bench.check_device(chips) if require_tpu
              else bench.device_info(chips))
    compiles = bench.CompileCounter()
    cell = bench.load_cell(args.workload, args.seed,
                           device["kind"] if require_tpu else None, root)
    cell.setup()
    if hooks is not None:
        hooks(cell)
    n_setup = compiles.count
    tracer = Tracer(TRACE_DIR) if args.trace else None
    setup_s = time.perf_counter() - T_START
    bench.log(f"[run] set-up {setup_s:.6f} s, {n_setup} programs compiled "
              f"or loaded ({compiles.seconds:.3f} s)")
    res = cell.window(args.seconds, tracer)
    in_window = compiles.count - n_setup
    bench.log(f"[run] compiles in window: {in_window}")
    peak = bench.memory_peak_bytes(chips)
    device = dict(device, memory_peak_bytes=peak)
    metrics = {}
    breakdown = None
    if args.trace:
        from reduce_trace import summarize
        summary = summarize(tracer.path, n_devices=chips,
                            groups=bench.kernel_groups())
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        ctx = bench.Context(workload=args.workload, config=cell.cfg,
                            traffic=cell.traffic, policy=cell.policy,
                            peaks=cell.peaks, window=res, trace=summary)
        metrics = per_layer(spec, args.workload, ctx)
        breakdown = summary.breakdown()
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        gib = (peak or 0) / 2 ** 30
        values = dict(res.end_to_end, setup_s=setup_s, peak_hbm_gib=gib)
        for m in bench.metrics_for(spec, args.workload, "end_to_end"):
            if m["name"] in values and values[m["name"]] is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    cell.free()
    checks = cell.check()
    checks.append(bench.Check("compiles_in_window", in_window, 0))
    checks.append(bench.Check("failed_requests", res.failed, 0))
    correct = all(c.ok for c in checks)
    result = {"correct": correct, "attempted": res.attempted,
              "failed": res.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    for c in checks:
        bench.log(f"check {c.name} {c.value!r} limit {c.limit!r} "
                  f"{'ok' if c.ok else 'FAILED'}")
    return result


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = run(args)
    except bench.NoChip as e:
        bench.log(f"[run] {e}")
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
