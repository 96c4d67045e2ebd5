"""End-to-end driver: train the ~100M-param example LM for a few hundred
steps under an HRM policy with live fault injection, scrubbing, clean-copy
recovery, checkpoint/restart, and a simulated node failure.

  PYTHONPATH=src python examples/train_hrm.py            # full (~100M)
  PYTHONPATH=src python examples/train_hrm.py --small    # CI-sized
"""
import argparse
import shutil

import jax

from repro.configs import get_config, get_tiny
from repro.configs.base import TrainConfig
from repro.core import Response, detect_recover
from repro.data.synthetic import batch_stream
from repro.launch.workdir import CKPT_ROOT
from repro.runtime.train_loop import LoopConfig, run_training


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--steps", type=int, default=None)
    args = ap.parse_args()

    if args.small:
        cfg = get_tiny("lm-100m")
        steps = args.steps or 30
        batch, seq = 8, 64
    else:
        cfg = get_config("lm-100m")
        steps = args.steps or 300
        batch, seq = 8, 256

    tcfg = TrainConfig(lr=3e-4, remat="none")
    policy = detect_recover()
    object.__setattr__(policy, "scrub_interval", 10)

    ckpt = str(CKPT_ROOT / "repro_train_hrm")
    shutil.rmtree(ckpt, ignore_errors=True)
    loop = LoopConfig(
        steps=steps,
        ckpt_interval=max(steps // 4, 10),
        ckpt_dir=ckpt,
        error_rate_per_step=0.2,            # a very error-prone "server"
        hard_error_fraction=0.3,
        node_failure_steps=(int(steps * 0.6),),
        policy=policy,
        response=Response.RELOAD_CLEAN_COPY,
    )
    stream = batch_stream(cfg, batch, seq)
    report = run_training(cfg, tcfg, loop, stream)

    first = sum(report.losses[:5]) / 5
    last = sum(report.losses[-5:]) / 5
    print(f"\nloss {first:.4f} -> {last:.4f} over {len(report.losses)} steps")
    print(f"injected errors:      {report.injected}")
    print(f"scrub detections:     {report.scrub_detected}")
    print(f"clean-copy recoveries:{report.recoveries}")
    print(f"restarts (node fail): {report.restarts}")
    print(f"straggler events:     {report.straggler_events}")
    ds = report.domain_stats
    print(f"memory domain:        {ds['protected_leaves']} leaves, "
          f"sidecar {ds['sidecar_bytes']}B "
          f"({ds['overhead']:.2%} of {ds['payload_bytes']}B), "
          f"{ds['live_hard_errors']} live hard errors")
    assert last < first, "training must make progress despite faults"
    assert report.restarts >= 1, "the node-failure drill must have fired"
    print("TRAIN_HRM OK")


if __name__ == "__main__":
    main()
