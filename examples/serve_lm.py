"""Batched serving demo: submit a queue of requests, decode with the
continuous-batching engine, print per-request generations.

    PYTHONPATH=src python examples/serve_lm.py --arch qwen2_0_5b
"""

import argparse
import time

import jax

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import Request, ServingEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_0_5b")
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch).smoke()
    eng = ServingEngine(cfg, max_batch=args.max_batch, max_len=64,
                        prompt_len=8)
    reqs = [
        Request(rid=i, prompt=[1 + i, 2, 3, 4, 5, 6, 7, 8],
                max_new_tokens=args.max_new_tokens)
        for i in range(args.requests)
    ]
    for r in reqs:
        eng.submit(r)
    t0 = time.time()
    stats = eng.run()
    dt = time.time() - t0
    print(f"stats: {stats} in {dt:.1f}s")
    for r in reqs[:5]:
        print(f"req {r.rid}: {r.out_tokens}")
    toks = stats["decode_steps"] * args.max_batch
    dev = jax.devices()[0]
    print(f"~{toks / dt:.1f} batched tokens/s on {dev.platform} "
          f"({dev.device_kind}), smoke config")
    assert stats["completed"] == args.requests


if __name__ == "__main__":
    main()
