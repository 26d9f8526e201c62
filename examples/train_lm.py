"""End-to-end training driver: train a small LM with the full stack
(data pipeline -> model -> AdamW -> checkpoints -> fault tolerance).

    PYTHONPATH=src python examples/train_lm.py --steps 300        # ~20M params
    PYTHONPATH=src python examples/train_lm.py --arch yi_6b --smoke
    PYTHONPATH=src python examples/train_lm.py --backend threads \\
        --shards 4 --steps 40    # data-parallel via the Myrmics runtime

Any assigned architecture is selectable with --arch (reduced to its
smoke config unless --full-config, which is only sensible on a real
cluster).  ``--backend loop`` (default) is the plain JAX training loop;
``--backend threads`` schedules every optimizer step as a Myrmics task
DAG — per-shard gradient tasks + an update task — executed with real
multicore parallelism on the runtime's concurrent executor
(``Myrmics(backend="threads")``).
"""

import argparse
from dataclasses import replace

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.config import ModelConfig
from repro.optim import AdamW
from repro.train.loop import FailurePlan, train


def default_20m() -> ModelConfig:
    base = get_config("qwen2_0_5b")
    return replace(
        base, arch_id="demo_20m", n_layers=4, d_model=256, n_heads=4,
        n_kv_heads=2, d_ff=1024, vocab=8192, pad_to=64,
        tie_embeddings=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--full-config", action="store_true")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--inject-failure", action="store_true",
                    help="kill a 'worker' mid-run to demo restart")
    ap.add_argument("--backend", choices=("loop", "threads", "procs"),
                    default="loop",
                    help="loop: plain JAX loop; threads: schedule each "
                    "step as a Myrmics task DAG on the concurrent executor; "
                    "procs: same DAG on one OS process per shard (gradient "
                    "tasks ship params over the wire and write grads back)")
    ap.add_argument("--shards", type=int, default=4,
                    help="data-parallel gradient shards (threads backend)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.arch is None:
        cfg = default_20m()
    else:
        cfg = get_config(args.arch)
        if not args.full_config:
            cfg = cfg.smoke()
    n_params = cfg.param_count()
    print(f"arch={cfg.arch_id} ~{n_params/1e6:.1f}M params "
          f"steps={args.steps} seq={args.seq_len} batch={args.batch}")

    plan = FailurePlan(fail_at_steps=(args.steps // 2,)) \
        if args.inject_failure else None
    opt = AdamW(lr=1e-3, warmup_steps=max(args.steps // 20, 1),
                total_steps=args.steps)

    def on_step(step, loss):
        if step % 10 == 0:
            print(f"step {step:5d}  loss {loss:.4f}")

    if args.backend in ("threads", "procs"):
        if args.inject_failure:
            raise SystemExit("--inject-failure is loop-backend only")
        from repro.train.orchestrator import run_myrmics_training
        rep, run_rep = run_myrmics_training(
            cfg, seq_len=args.seq_len, global_batch=args.batch,
            steps=args.steps, n_shards=args.shards, opt=opt,
            on_step=on_step, backend=args.backend)
        print(f"done ({run_rep.backend} backend, {args.shards} shards, "
              f"{run_rep.tasks_done} tasks, "
              f"{run_rep.total_cycles:.1f}s wall): "
              f"first loss {rep.losses[0]:.4f} -> last {rep.losses[-1]:.4f}")
    else:
        rep = train(cfg, seq_len=args.seq_len, global_batch=args.batch,
                    steps=args.steps, ckpt_dir=args.ckpt_dir, ckpt_every=50,
                    async_ckpt=True, failure_plan=plan, opt=opt,
                    on_step=on_step)
        print(f"done: first loss {rep.losses[0]:.4f} -> last "
              f"{rep.losses[-1]:.4f}; restarts={rep.restarts} "
              f"stragglers={rep.stragglers}")
    assert rep.losses[-1] < rep.losses[0], "loss must decrease"


if __name__ == "__main__":
    main()
