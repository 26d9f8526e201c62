"""The plain reference against the program's model at a tiny size, in
float32 on the CPU: prefill and decode through the cache against the
reference's full forward for ChatGLM3 (the benchmark's configuration)
and for Qwen2 (tied embedding, rotary on every head dimension), and for
Qwen2 the loss and one AdamW update."""

import copy
import hashlib
import json
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, weights
from bench.references import dense_decoder as ref
from bench.tests.tiny import TINY_MODEL

CONFIGS = ["qwen2-0.5b", "chatglm3-6b"]

#: Qwen2-0.5B (hf Qwen/Qwen2-0.5B config.json) in the form of a
#: configuration file; no cell serves or trains it yet
QWEN2 = {"arch_id": "qwen2_0_5b", "model": {
    "n_layers": 24, "d_model": 896, "n_heads": 14, "n_kv_heads": 2,
    "head_dim": 64, "d_ff": 4864, "vocab": 151936, "qkv_bias": True,
    "tie_embeddings": True, "rope_style": "full", "rope_theta": 1000000.0,
    "norm_eps": 1e-06, "param_dtype": "bfloat16", "compute_dtype": "bfloat16",
    "pad_to": 128}}


def _config(name, **over):
    if name == "qwen2-0.5b":
        cfg = copy.deepcopy(QWEN2)
    else:
        cfg = copy.deepcopy(json.loads(
            (harness.BENCH_DIR / "configs" / f"{name}.json").read_text()))
    cfg["model"].update(TINY_MODEL, **over)
    return cfg


def _served(m, padded_vocab, seed, dtype):
    return weights.serve_params(ref.param_tree(m, padded_vocab), seed, dtype,
                                ref.stacked_groups(m))


def _program(cfg):
    from repro.models.transformer import LM

    mc = harness.model_config(cfg)
    return LM(mc), mc


@pytest.mark.parametrize("name", CONFIGS)
def test_prefill_and_cached_decode_match_the_full_forward(name):
    cfg = _config(name, param_dtype="float32", compute_dtype="float32")
    m = cfg["model"]
    lm, mc = _program(cfg)
    params = _served(m, mc.padded_vocab, 5, jnp.float32)
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, m["vocab"], size=(1, 12)).astype(np.int32)
    nxt = rng.integers(1, m["vocab"], size=4).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        cache, logits = lm.prefill(params, {"tokens": jnp.asarray(prompt)}, max_len=24)
        got = [logits[0, :m["vocab"]]]
        for t in nxt:
            cache, logits = lm.decode_step(params, cache, jnp.asarray([t]))
            got.append(logits[0, :m["vocab"]])
        seq = np.concatenate([prompt[0], nxt])[None]
        hid = ref.served_hidden(params, seq, m, "f32", q_block=8)
        want = ref.head_logits(params, hid[0, 11:], m, "f32", chunk=100)
    np.testing.assert_allclose(np.stack(got), np.asarray(want), atol=2e-4, rtol=0)


def test_qwen2_loss_and_adamw_update_match_the_program():
    from repro.optim import AdamW

    cfg = _config("qwen2-0.5b", param_dtype="float32", compute_dtype="float32")
    m = cfg["model"]
    lm, mc = _program(cfg)
    p0 = lm.init(jax.random.PRNGKey(3))
    opt_hp = dict(lr=1e-2, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1,
                  warmup_steps=1, total_steps=100, clip_norm=1.0)
    toks = np.random.default_rng(3).integers(0, m["vocab"], (2, 32)).astype(np.int32)
    labels = np.roll(toks, -1, axis=1)
    labels[:, -1] = 0
    b = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}
    with jax.default_matmul_precision("highest"):
        loss_p, grads = jax.value_and_grad(lm.loss)(p0, b)
        opt = AdamW(**opt_hp)
        new_p, _, _ = opt.update(grads, opt.init(p0), p0)
        loss_r, g_r = jax.value_and_grad(ref.loss_fn)(
            p0, b["tokens"], b["labels"], m, "f32", chunk=16)
        zeros = jax.tree.map(jnp.zeros_like, p0)
        new_r, _, _ = ref.adamw_step(p0, zeros, zeros, g_r,
                                     jnp.asarray(1, jnp.int32), opt_hp)
    assert float(loss_p) == pytest.approx(float(loss_r), abs=1e-5)
    for a, c in zip(jax.tree.leaves(grads), jax.tree.leaves(g_r)):
        scale = float(jnp.max(jnp.abs(c))) + 1e-12
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-4 * scale)
    # Adam divides by |g|: where |g| is near eps a round-off of g moves the
    # update by a share of lr, so the update is held to 0.5% of lr
    for a, c in zip(jax.tree.leaves(new_p), jax.tree.leaves(new_r)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   atol=5e-3 * opt_hp["lr"])


def test_serve_params_are_a_function_of_the_seed():
    cfg = _config("chatglm3-6b")
    m = cfg["model"]
    a = _served(m, 256, 7, jnp.bfloat16)
    b = _served(m, 256, 7, jnp.bfloat16)
    c = _served(m, 256, 8, jnp.bfloat16)
    for x, y, z in zip(jax.tree.leaves(a), jax.tree.leaves(b), jax.tree.leaves(c)):
        assert x.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))
        assert not np.array_equal(np.asarray(x, np.float32),
                                  np.asarray(z, np.float32))
    lm, _ = _program(cfg)
    abstract = lm.abstract_params()
    assert jax.tree.map(lambda x: x.shape, abstract) == \
        jax.tree.map(lambda x: x.shape, a)


#: sha256 of the served weights at the tiny size, each leaf's path and
#: bytes in flat-path order, computed at commit 1a782be, where
#: bench/weights.py still wrote out the dense decoder's tree itself: the
#: tree in the reference draws every served weight as it did
DIGESTS = {
    ("chatglm3-6b", "bfloat16", 5):
        "9dc9e997eb6b18557221a7d87f262a8ee13c99ebfd657fedf61e3467f437693d",
    ("chatglm3-6b", "bfloat16", 2**31 + 11):
        "8cbd3e5bb32b963e7c63a8081368611b05c05fb6754cc6f7fda88dfbdbe4fffa",
    ("chatglm3-6b", "float32", 5):
        "3c641ce84c04cdc41c7916eaf0b183306b3f0dfa5d34b555517bc159dfa8e736",
    ("chatglm3-6b", "float32", 2**31 + 11):
        "c86392e7583e1707739fcf83dac80a4e8d9e8817d17c56d7bea28daae3e78768",
    ("qwen2-0.5b", "bfloat16", 5):
        "0bf8284535596cb6378c88f650670dcc203d0ef37ed8173d10b01849f71609eb",
    ("qwen2-0.5b", "bfloat16", 2**31 + 11):
        "ff91675efbf0e096b6093bc94c0c3c9ea200ca950e3c71cd8792af814d646915",
    ("qwen2-0.5b", "float32", 5):
        "8ad91ef8e46671b0d9e3d07e16fd1f9a8816215b36425d095bae6c0d5e5e98ca",
    ("qwen2-0.5b", "float32", 2**31 + 11):
        "ae653e25d9b0e447e261c511fa2a9ff0282c6c2add5977d5ba41d9b2524fca96",
}


@pytest.mark.parametrize("name, dtype, seed", list(DIGESTS))
def test_served_weights_match_the_parent_digests(name, dtype, seed):
    cfg = _config(name)
    m = cfg["model"]
    params = _served(m, harness.model_config(cfg).padded_vocab, seed,
                     weights.dtype_of(dtype))
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == DIGESTS[name, dtype, seed]


def test_the_fp8_control_rounds_each_operand():
    x = jnp.asarray([[0.1, -0.37, 2.0]], jnp.float32)
    q = ref._fp8(x)
    assert not np.allclose(np.asarray(q), np.asarray(x), atol=1e-4)
    np.testing.assert_allclose(np.asarray(q), np.asarray(x), rtol=0.07)


def test_config_sizes_follow_the_published_config():
    g = json.loads((harness.BENCH_DIR / "configs" / "chatglm3-6b.json").read_text())
    p, m = g["published"], g["model"]
    assert (m["n_layers"], m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"],
            m["d_ff"], m["vocab"], m["norm_eps"], m["qkv_bias"],
            m["tie_embeddings"]) == (
        p["num_layers"], p["hidden_size"], p["num_attention_heads"],
        p["multi_query_group_num"], p["kv_channels"], p["ffn_hidden_size"],
        p["padded_vocab_size"], p["layernorm_epsilon"], p["add_qkv_bias"],
        p["tie_word_embeddings"])
    for cfg in (QWEN2, g):
        mc = harness.model_config(cfg)
        assert mc.padded_vocab == cfg["model"]["vocab"]
        assert replace(mc).arch_id == cfg["arch_id"]
