"""Training launcher.

Local (CPU/host) execution runs the reduced config end-to-end; on a real
cluster the same entry point jits the step with the production-mesh
shardings (which the dry-run proves coherent).

    PYTHONPATH=src python -m repro.launch.train --arch yi_6b --steps 20
"""

from __future__ import annotations

import argparse

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import AdamW
from repro.train.loop import FailurePlan, train


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--full-config", action="store_true",
                    help="use the full-scale config (cluster only)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--async-ckpt", action="store_true")
    ap.add_argument("--fail-at", type=int, nargs="*", default=[])
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if not args.full_config:
        cfg = cfg.smoke()
    plan = FailurePlan(fail_at_steps=tuple(args.fail_at)) \
        if args.fail_at else None
    opt = AdamW(warmup_steps=max(args.steps // 10, 1),
                total_steps=args.steps)
    rep = train(cfg, seq_len=args.seq_len, global_batch=args.batch,
                steps=args.steps, ckpt_dir=args.ckpt_dir,
                ckpt_every=args.ckpt_every, async_ckpt=args.async_ckpt,
                failure_plan=plan, opt=opt,
                on_step=lambda s, l: print(f"step {s} loss {l:.4f}"))
    print(f"losses: {rep.losses[0]:.4f} -> {rep.losses[-1]:.4f} "
          f"restarts={rep.restarts}")


if __name__ == "__main__":
    main()
