"""A tiny granite-4.0-h-small for the CPU tests: every mechanism of the
published model at small widths.  It keeps both layer kinds, runs of
each, two B/C groups, a prompt that is not a whole number of SSD chunks,
four of eight experts held (a share that starts past expert 0), the
shared MLP and granite's four multipliers."""

from __future__ import annotations

import copy
import json

import jax
import jax.numpy as jnp

from bench import harness, weights
from bench.references import granite_hybrid as ref

#: the benchmark's cell of this configuration
CELL = "serve.granite-4.0-h-small.chat"

#: the tiny model's sizes, over the configuration's ``model``
TINY = {"n_layers": 4, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 16, "vocab": 256, "pad_to": 128,
        "layer_types": ["mamba", "mamba", "attention", "mamba"],
        "ssm": {"state_dim": 16, "version": 2, "conv_dim": 4, "expand": 2,
                "headdim": 16, "n_groups": 2, "chunk": 4, "conv_bias": True},
        "moe": {"n_experts": 8, "top_k": 3, "expert_d_ff": 32,
                "first_held": 2, "n_held": 4},
        "shared_d_ff": 48, "param_dtype": "float32",
        "compute_dtype": "float32"}


def config(**model):
    cfg = json.loads((harness.BENCH_DIR / "configs"
                      / "granite-4.0-h-small.json").read_text())
    cfg["model"].update(copy.deepcopy(TINY), **model)
    return cfg


def program(cfg):
    from repro.models.transformer import LM

    mc = harness.model_config(cfg)
    return LM(mc), mc


def served(m, padded_vocab, seed=5, dtype=jnp.float32):
    return weights.serve_params(ref.param_tree(m, padded_vocab), seed, dtype,
                                ref.stacked_groups(m))


def decode(lm, params, prompt, nxt, max_len=24):
    """Prefill ``prompt``, then decode the tokens ``nxt``; every logits."""
    cache, logits = jax.jit(lm.prefill, static_argnames="max_len")(
        params, {"tokens": jnp.asarray(prompt)}, max_len=max_len)
    out = [logits]
    step = jax.jit(lm.decode_step)
    for t in nxt:
        cache, logits = step(params, cache, jnp.asarray(t))
        out.append(logits)
    return cache, out


#: tolerance of logits against the reference's, as a share of the largest
#: reference logit: both sides are float32 at full matmul precision and
#: differ only in the order of sums (a chunked SSD scan and a KV cache
#: against a sequential recurrence), about 1e-7 of it.  The program's
#: scan fed bfloat16-rounded inputs misses it by some 17 times
LOGIT_TOL = 1e-5
