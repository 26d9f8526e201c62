"""Run the main path once on one TPU at the full width of qwen2-0.5B.

    python chip_smoke.py

Phase A trains through ``run_myrmics_training(backend="threads")``:
every optimizer step is a Myrmics task DAG (per-shard gradient tasks,
then an update task) run by ``Myrmics(backend="threads").run``.  Phase B
serves a few requests through ``ServingEngine``.  Weights are random,
made from a seed; the model has its published widths (24 layers,
d_model 896, 14/2 heads, d_ff 4864, vocab 151936).

Observations (device, compile and step times, losses, device memory)
go on earlier lines.  The last line is one JSON object naming the
device.  With no TPU, or when any check fails, the script exits
non-zero and prints no such line: it never falls back to the CPU.
"""

from __future__ import annotations

import gc
import json
import math
import sys
import time
from dataclasses import replace
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data import TokenDataset
from repro.launch.compile_cache import enable_compile_cache
from repro.models.config import ModelConfig
from repro.models.transformer import LM
from repro.serving import Request, ServingEngine
from repro.train.orchestrator import run_myrmics_training

ARCH = "qwen2_0_5b"
#: random init (std 0.02, tied embeddings) puts the step-0 loss at
#: ln(vocab) plus half the logit variance (about 0.2 nats at this width)
LOSS0_TOL = 0.5
#: step-0 loss in the config's bfloat16 vs float32 on the CPU with the
#: same parameters and batch (bf16 rounding of a mean over all tokens)
CPU_REF_TOL = 0.1
#: peak device bytes may not grow after the first two steps by more
#: than this share: every later step holds the same live set
PEAK_GROWTH_TOL = 0.01


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _memory(dev) -> tuple[int, int] | None:
    stats = dev.memory_stats()
    if stats is None:          # the CPU backend reports none
        return None
    return stats["bytes_in_use"], stats["peak_bytes_in_use"]


def cpu_reference_loss(cfg: ModelConfig, batch: dict, seed: int) -> float:
    """The step-0 loss in float32 on the CPU, with the parameters the
    training DAG starts from (made on the default device, then copied)
    and under the highest matmul precision."""
    cpu = jax.devices("cpu")[0]
    params = LM(cfg).init(jax.random.PRNGKey(seed))
    params = jax.device_put(params, cpu)
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    b = jax.device_put({k: jnp.asarray(v) for k, v in batch.items()}, cpu)
    lm32 = LM(replace(cfg, param_dtype="float32", compute_dtype="float32"))
    with jax.default_matmul_precision("highest"):
        return float(jax.jit(lm32.loss)(params, b))


def train_phase(cfg: ModelConfig, *, steps: int = 6, global_batch: int = 8,
                seq_len: int = 256, n_shards: int = 2, seed: int = 0,
                cpu_ref: bool = True) -> list[float]:
    """Train ``steps`` steps through the Myrmics runtime; returns the
    per-step losses after checking them."""
    dev = jax.devices()[0]
    wall: list[float] = []
    mem: list[tuple[int, int] | None] = []
    last = [time.perf_counter()]

    # on_step runs once the step's shard losses were read back to the
    # host: that read waits for the step's gradient program, which on
    # the device waits for the previous step's update.  So step k's wall
    # time spans update(k-1) + grads(k).  Step 0 also includes the
    # parameter init and both compiles: the step's barrier waits for the
    # update task, which compiles its program before dispatching it.
    def on_step(step: int, loss: float) -> None:
        now = time.perf_counter()
        wall.append(now - last[0])
        last[0] = now
        mem.append(_memory(dev))
        m = mem[-1]
        mem_s = ("bytes_in_use n/a" if m is None else
                 f"bytes_in_use {m[0]} peak_bytes_in_use {m[1]}")
        print(f"train step {step}: loss {loss!r} wall_s {wall[-1]!r} "
              f"{mem_s}", flush=True)

    report, run_rep = run_myrmics_training(
        cfg, seq_len=seq_len, global_batch=global_batch, steps=steps,
        n_shards=n_shards, seed=seed, on_step=on_step, backend="threads")
    losses = list(report.losses)
    print(f"train: {run_rep.backend} backend, {run_rep.tasks_done} tasks, "
          f"{n_shards} shards x {global_batch // n_shards} x {seq_len} "
          f"tokens, first-step (compile) wall_s {wall[0]!r}, "
          f"later steps wall_s {wall[1:]!r}", flush=True)

    check(len(losses) == steps, f"{len(losses)} of {steps} steps reported")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    ln_v = math.log(cfg.vocab)
    check(abs(losses[0] - ln_v) < LOSS0_TOL,
          f"step-0 loss {losses[0]} not within {LOSS0_TOL} of "
          f"ln(vocab) {ln_v}")
    if mem[0] is not None and steps > 2:
        peak_1, peak_n = mem[1][1], mem[-1][1]
        check(peak_n <= peak_1 * (1 + PEAK_GROWTH_TOL),
              f"peak_bytes_in_use grew from {peak_1} after step 1 to "
              f"{peak_n} after step {steps - 1}")
    if cpu_ref:
        batch = TokenDataset(cfg, seq_len, global_batch, seed).get_batch(0)
        t0 = time.perf_counter()
        ref = cpu_reference_loss(cfg, batch, seed)
        print(f"train: step-0 loss {losses[0]!r} on {dev.platform}, "
              f"float32 CPU reference {ref!r} "
              f"(wall_s {time.perf_counter() - t0!r})", flush=True)
        check(abs(losses[0] - ref) < CPU_REF_TOL,
              f"step-0 loss {losses[0]} vs CPU float32 {ref}: more than "
              f"{CPU_REF_TOL} apart")
    return losses


def serve_phase(cfg: ModelConfig, *, n_requests: int = 8, max_batch: int = 4,
                prompt_len: int = 32, max_len: int = 256,
                max_new_tokens: int = 16, seed: int = 0) -> list[list[int]]:
    """Serve ``n_requests`` random prompts; returns each request's
    generated tokens after checking them."""
    eng = ServingEngine(cfg, max_batch=max_batch, max_len=max_len,
                        prompt_len=prompt_len, seed=seed)
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, prompt_len).tolist(),
                    max_new_tokens=max_new_tokens)
            for i in range(n_requests)]
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    stats = eng.run()
    wall = time.perf_counter() - t0
    print(f"serve: {n_requests} requests, max_batch {max_batch}, prompt "
          f"{prompt_len}, max_len {max_len}, {max_new_tokens} new tokens "
          f"each; prefill_s {stats['prefill_s']!r} decode_s "
          f"{stats['decode_s']!r} ({stats['prefills']} prefills, "
          f"{stats['decode_steps']} decode steps, first round includes "
          f"compiles), run wall_s {wall!r}", flush=True)

    check(stats["completed"] == n_requests,
          f"{stats['completed']} of {n_requests} requests completed")
    for r in reqs:
        check(r.done and len(r.out_tokens) == max_new_tokens,
              f"request {r.rid}: done={r.done}, {len(r.out_tokens)} of "
              f"{max_new_tokens} tokens")
        check(all(0 <= t < cfg.vocab for t in r.out_tokens),
              f"request {r.rid}: token outside the vocab {r.out_tokens}")
    print(f"serve: request 0 tokens {reqs[0].out_tokens}", flush=True)
    return [r.out_tokens for r in reqs]


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    cfg = get_config(ARCH)
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}, "
          f"bytes_limit {dev.memory_stats()['bytes_limit']}; jax "
          f"{jax.__version__}; compile cache {cache}", flush=True)
    print(f"config {cfg.arch_id}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab} (padded {cfg.padded_vocab}), "
          f"~{cfg.param_count()} params", flush=True)
    train_phase(cfg)
    gc.collect()
    serve_phase(cfg)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
