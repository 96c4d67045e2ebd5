"""Training launcher.

Single-host CPU runs execute for real (reduced configs); pod-scale runs
lower/compile through the same code path via ``--dryrun`` (see dryrun.py
for the full matrix). HRM policy, fault injection, checkpointing and
restart are all live in either mode.

  PYTHONPATH=src python -m repro.launch.train --arch lm-100m --steps 50 \
      --policy detect_recover --error-rate 0.05
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import get_config, get_tiny
from repro.configs.base import TrainConfig
from repro.core import DESIGN_POINTS
from repro.data.synthetic import batch_stream
from repro.launch.workdir import CKPT_ROOT, enable_compile_cache
from repro.runtime.train_loop import LoopConfig, run_training


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="lm-100m")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--policy", choices=sorted(DESIGN_POINTS), default=None)
    ap.add_argument("--scrub-interval", type=int, default=20)
    ap.add_argument("--error-rate", type=float, default=0.0)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[])
    ap.add_argument("--ckpt-dir", default=str(CKPT_ROOT / "repro_ckpt"))
    ap.add_argument("--ckpt-interval", type=int, default=25)
    ap.add_argument("--grad-compress", action="store_true")
    args = ap.parse_args()

    cfg = get_tiny(args.arch) if args.tiny else get_config(args.arch)
    tcfg = TrainConfig(lr=args.lr, microbatches=args.microbatches,
                       grad_compress=args.grad_compress, remat="none")
    policy = None
    if args.policy:
        policy = DESIGN_POINTS[args.policy]()
        object.__setattr__(policy, "scrub_interval", args.scrub_interval)
    loop = LoopConfig(steps=args.steps, ckpt_interval=args.ckpt_interval,
                      ckpt_dir=args.ckpt_dir,
                      error_rate_per_step=args.error_rate,
                      node_failure_steps=tuple(args.fail_at), policy=policy)
    stream = batch_stream(cfg, args.batch, args.seq)
    report = run_training(cfg, tcfg, loop, stream)
    print(f"steps={len(report.losses)} loss: {report.losses[0]:.4f} -> "
          f"{report.losses[-1]:.4f}")
    print(f"injected={report.injected} corrected={report.scrub_corrected} "
          f"detected={report.scrub_detected} recoveries={report.recoveries} "
          f"restarts={report.restarts} stragglers={report.straggler_events}")


if __name__ == "__main__":
    main()
