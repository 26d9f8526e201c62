"""Serving traffic against ``repro.serving.ServingEngine``.

Set-up: the weights are made on the device from the seed (one jitted
call, in the served dtype) and handed to the engine; one request of two
tokens warms the prefill and the batch decode.  The window: clients
submit through ``ServingEngine.submit`` and the engine advances one
iteration at a time through ``ServingEngine.run(max_steps=1)``.  A token
is visible to its client at the end of the iteration that produced it,
and is timed then.  After the window a sample of the finished requests,
the longest among them, is checked against the plain reference.

Traffic parameters (``bench/traffic/<mix>.json``): ``engine`` (the
engine's ``max_batch``, ``prompt_len``, ``max_len``), ``arrivals``,
``prompt``, ``output`` and ``strata`` (see ``bench/loadgen.py``),
``check`` (``sample``: requests compared), ``trace`` (profiler on for
the whole window when ``--trace 1``).
"""

from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time

import numpy as np

from bench import flops, harness, loadgen, weights
from bench import trace as btrace

#: the engine's batched decode program, as the trace names it
DECODE_PROGRAM = "decode_step"


def _p95(xs) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), 95))


def _span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(f"bench.serve.{name}")


def served_params(ref, m: dict, mc, seed: int) -> dict:
    """The served weights: the reference's parameter tree, drawn from
    the seed in the configuration's dtype."""
    return weights.serve_params(ref.param_tree(m, mc.padded_vocab), seed,
                                weights.dtype_of(mc.param_dtype),
                                ref.stacked_groups(m))


def check_tree(cell, ref, mc, program_params) -> None:
    """The program's parameter tree has the reference's leaves and shapes."""
    import jax

    want = jax.tree.map(lambda a: tuple(a.shape), program_params)
    if want != weights.shapes_of(ref.param_tree(cell.config["model"],
                                                mc.padded_vocab)):
        raise harness.BenchError(
            f"the program's parameter tree differs from bench/references/"
            f"{cell.config['reference']}.py's")


def check_served(cell, seed: int, sample: list, prec: str = "f32",
                 pick: str = "served") -> float:
    """Widest gap, over every sampled position, by which the picked
    token's reference logit lies below the reference's best.  ``pick``
    "served" reads the tokens the program served; "control" reads the
    token that the reference at ``prec`` puts first (the control)."""
    import jax
    import jax.numpy as jnp

    ref = harness.reference_module(cell)
    m = cell.config["model"]
    mc = harness.model_config(cell.config)
    eng = cell.traffic["engine"]
    params = served_params(ref, m, mc, seed)
    # one fixed shape: the padded prompt plus the longest possible answer
    t = eng["prompt_len"] + cell.traffic["output"]["max"]
    seqs = np.zeros((len(sample), t), np.int32)
    for j, (prompt, out) in enumerate(sample):
        full = list(prompt) + list(out[:-1])
        seqs[j, :len(full)] = full
    n = max(len(out) for _, out in sample)
    pos = np.zeros((len(sample), n), np.int32)
    for j, (prompt, out) in enumerate(sample):
        pos[j, :len(out)] = np.arange(len(prompt) - 1, len(prompt) - 1 + len(out))
    take = jax.vmap(lambda x, p: x[p])
    with jax.default_matmul_precision("highest"):
        hid = take(ref.served_hidden(params, seqs, m, "f32"), jnp.asarray(pos))
        logits = ref.head_logits(params, hid, m, "f32")
        if pick == "control":
            alt = take(ref.served_hidden(params, seqs, m, prec), jnp.asarray(pos))
            tok_alt = jnp.argmax(ref.head_logits(params, alt, m, prec), -1)
    worst = 0.0
    for j, (prompt, out) in enumerate(sample):
        lj = logits[j, :len(out)]
        if pick == "control":
            tok = tok_alt[j, :len(out)]
        else:
            tok = jnp.asarray(out, jnp.int32)
        got = jnp.take_along_axis(lj, tok[:, None], -1)[:, 0]
        worst = max(worst, float(jnp.max(jnp.max(lj, -1) - got)))
    return worst


def run(cell, *, seed: int, seconds: float, trace: bool, t_process: float,
        peak: dict) -> dict:
    import jax

    from repro.models.transformer import LM
    from repro.serving import Request, ServingEngine

    tr, m = cell.traffic, cell.config["model"]
    mc = harness.model_config(cell.config)
    ref = harness.reference_module(cell)
    check_tree(cell, ref, mc, LM(mc).abstract_params())
    params = served_params(ref, m, mc, seed)
    e = tr["engine"]
    eng = ServingEngine(mc, params=params, max_batch=e["max_batch"],
                        max_len=e["max_len"], prompt_len=e["prompt_len"],
                        seed=seed)
    stream = loadgen.RequestStream(tr, m["vocab"], seed)

    # warm-up: the prefill and the batch decode, with no request left over
    warm = Request(rid=-1, prompt=stream.request(0)[0], max_new_tokens=2)
    eng.submit(warm)
    while not warm.done:
        eng.run(max_steps=1)
    jax.block_until_ready(eng.cache)
    s0 = eng.run(max_steps=0)
    setup_s = time.perf_counter() - t_process

    arrivals = tr["arrivals"]
    if arrivals["kind"] != "closed":
        raise harness.BenchError(f"{arrivals['kind']!r} arrivals: the serving "
                                 f"traffic is a closed loop of clients")
    recs: dict[int, dict] = {}
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)

    n_sent = 0

    def submit(now: float) -> None:
        nonlocal n_sent
        with _span("submit"):
            prompt, olen = stream.request(n_sent)
            req = Request(rid=n_sent, prompt=prompt, max_new_tokens=olen)
            eng.submit(req)
            recs[n_sent] = {"req": req, "sent": now, "times": []}
            n_sent += 1

    decode_ctx: list[list[int]] = []
    t0 = time.perf_counter()
    for _ in range(arrivals["clients"]):
        submit(t0)
    while True:
        steps_before = eng.run(max_steps=0)["decode_steps"]
        with _span("iter"):
            eng.run(max_steps=1)
        t = time.perf_counter()
        with _span("client"):
            ran_decode = eng.run(max_steps=0)["decode_steps"] > steps_before
            ctx = []
            for r in list(recs.values()):
                req, times = r["req"], r["times"]
                new = len(req.out_tokens) - len(times)
                if new <= 0:
                    continue
                times.extend([t] * new)
                ctx.append(e["prompt_len"] + len(req.out_tokens) - 1)
                if req.done and "closed" not in r:
                    r["closed"] = True
                    submit(t)
            if ran_decode:
                decode_ctx.append(ctx)
        if t - t0 >= seconds:
            break
    t_end = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()

    s1 = eng.run(max_steps=0)
    window_s = t_end - t0
    tokens = sum(len(r["times"]) for r in recs.values())
    ttft = [(r["times"][0] - r["sent"]) * 1e3 for r in recs.values() if r["times"]]
    itl = [(b - a) * 1e3 for r in recs.values()
           for a, b in zip(r["times"], r["times"][1:])]
    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    memory_peak = int(mem.get("peak_bytes_in_use", 0))

    done = [r["req"] for r in recs.values() if r["req"].done]
    print(f"serve: {n_sent} requests sent, {len(ttft)} first tokens, "
          f"{len(done)} finished, {tokens} tokens in {window_s!r} s; "
          f"first-token waits {sorted(round(x) for x in ttft)} ms",
          file=sys.stderr)
    if not done:
        raise harness.BenchError("no request finished in the window")
    rng = np.random.default_rng([seed, 7])
    longest = max(range(len(done)), key=lambda i: len(done[i].out_tokens))
    rest = [i for i in range(len(done)) if i != longest]
    n_more = min(len(rest), tr["check"]["sample"] - 1)
    picks = [longest] + list(rng.choice(rest, size=n_more, replace=False))
    sample = [(list(done[i].prompt), list(done[i].out_tokens)) for i in picks]

    eng_stats = {k: s1[k] - s0[k] for k in s1}
    w_item = np.dtype(weights.dtype_of(mc.param_dtype)).itemsize
    kv_item = np.dtype(weights.dtype_of(mc.compute_dtype)).itemsize
    step_flops = [sum(ref.decode_token_flops(m, c) for c in ctx)
                  for ctx in decode_ctx]
    least = [flops.least_time_s(f, ref.decode_step_bytes(m, ctx, w_item, kv_item),
                                peak) for f, ctx in zip(step_flops, decode_ctx)]
    bounds = [b for _, b in least]
    rec = {
        "window_s": window_s,
        "engine": eng_stats,
        "decode_flops": float(sum(step_flops)),
        "decode_least_s": [t for t, _ in least],
        "decode_bound": max(set(bounds), key=bounds.count) if bounds else None,
        "decode_program": DECODE_PROGRAM,
        "peak": peak,
        "trace": None,
    }
    del eng, params, warm, recs, done
    gc.collect()
    if trace:
        rec["trace"] = btrace.summarize(btrace.read(btrace.find_xplane(log_dir)))
        shutil.rmtree(log_dir, ignore_errors=True)
    gap = check_served(cell, seed, sample)
    return {
        "e2e": {"serve_tokens_per_s": tokens / window_s,
                "ttft_p95_ms": _p95(ttft) if ttft else None,
                "itl_p95_ms": _p95(itl) if itl else None,
                "setup_s": setup_s},
        "rec": rec,
        "attempted": n_sent,
        "failed": 0,
        "memory_peak_bytes": memory_peak,
        "checks": [{"name": "logit_gap", "value": gap,
                    "limit": cell.limits.get("logit_gap")}],
        "sample": sample,
    }
