"""Production training launcher.

    python -m repro.launch.train --arch llama3.2-3b --steps 100 \
        [--smoke] [--workdir DIR] [--batch 8 --seq 256] [--accum 1]

On a host without an accelerator use --smoke (reduced config).  Training
runs on one device: the trainer builds no mesh, so the full config is not
sharded over the production mesh (``launch/mesh.py`` serves the dry-run
tooling only).  The training loop itself is a Triggerflow state-machine
workflow (checkpoint/resume per chunk, event-replay fault tolerance) — kill
and relaunch to resume.
"""
from __future__ import annotations

import argparse

from repro.configs import ARCHS, get_config
from repro.launch.compile_cache import use_compile_cache
from repro.training.trainer import run_training


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--chunk-steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--workdir", default=None)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    workdir = args.workdir or f"/tmp/tf-train-{cfg.arch}"
    print(f"arch={cfg.arch} params={cfg.param_count() / 1e6:.1f}M "
          f"workdir={workdir}")
    out = run_training(cfg, workdir, total_steps=args.steps,
                       chunk_steps=args.chunk_steps, batch=args.batch,
                       seq=args.seq, peak_lr=args.lr)
    print("status:", out["workflow_result"]["status"])
    for rec in out["history"]:
        print(f"  step {rec['step']:5d} loss {rec['loss_mean']:.4f} "
              f"({rec['wall_s']}s)")


if __name__ == "__main__":
    main()
