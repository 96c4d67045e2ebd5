#!/usr/bin/env python3
"""Readings from which a cell's correctness limits are set: for each seed,
one process sets the cell up, runs a short window at the cell's own load,
frees the program and reads every number the check compares, beside the
same number for the control: the plain reference one precision step below
the configuration's (float8 weights for a bfloat16 model, bfloat16 ranks
for float32 PageRank) in the program's place.

    python3 perfbench/control.py --workload <cell> --seeds 1 2 3 \
        [--seconds 1] [--out readings.jsonl]

The benchmark's own runs never run this. Each seed prints one JSON line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench  # noqa: E402


def readings(workload: str, seed: int, seconds: float, *,
             require_tpu: bool = True, root: Path = bench.ROOT) -> dict:
    spec = bench.benchmark_spec(root)
    chips = int(bench.find_workload(spec, workload)["chips"])
    bench.add_program_path()
    bench.configure_jax()
    device = (bench.check_device(chips) if require_tpu
              else bench.device_info(chips))
    cell = bench.load_cell(workload, seed, root=root)
    t0 = time.perf_counter()
    cell.setup()
    cell.window(seconds)
    cell.free()
    out = cell.check_readings(control=True)
    return {"workload": workload, "seed": seed, "device": device["kind"],
            "seconds": time.perf_counter() - t0, **out}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    for seed in args.seeds:
        line = json.dumps(readings(args.workload, seed, args.seconds))
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
