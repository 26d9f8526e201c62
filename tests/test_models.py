"""Per-architecture smoke tests (reduced configs) + decode consistency."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models.transformer import LM

RNG = jax.random.PRNGKey(0)

# The smoke, training and microbatch tests keep one cheap representative
# arch in the fast tier-1 run; the expensive architectures (vision/MoE/
# mamba hybrids dominate suite wall time) run under `pytest -m slow`.
FAST_ARCHS = ("qwen2_0_5b",)
ARCH_PARAMS = [
    a if a in FAST_ARCHS else pytest.param(a, marks=pytest.mark.slow)
    for a in ARCH_IDS
]


#: the served configurations, by their file under bench/configs
BENCH_CONFIGS = ("chatglm3-6b", "granite-4.0-h-small")
PINNED_TREES = Path(__file__).parent / "data" / "model_trees.json"


def make_batch(cfg, b=2, s=16, with_labels=True):
    batch = {"tokens": jax.random.randint(RNG, (b, s), 0, cfg.vocab)}
    if with_labels:
        batch["labels"] = jax.random.randint(
            jax.random.fold_in(RNG, 1), (b, s), 0, cfg.vocab)
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            RNG, (b, cfg.enc_seq, cfg.d_model)) * 0.1
    if cfg.family == "vlm":
        batch["img_embeds"] = jax.random.normal(
            RNG, (b, cfg.img_tokens, cfg.d_model)) * 0.1
    return batch


@pytest.mark.parametrize("arch", ARCH_PARAMS)
def test_smoke_forward_and_train_step(arch):
    """Reduced same-family config: one forward/train step on CPU,
    asserting output shapes and no NaNs (deliverable (f))."""
    cfg = get_config(arch).smoke()
    lm = LM(cfg)
    params = lm.init(RNG)
    batch = make_batch(cfg)
    x, aux = lm.forward(params, batch, remat=False)
    assert x.shape == (2, 16, cfg.d_model)
    assert np.isfinite(np.asarray(x, np.float32)).all()
    loss, grads = jax.value_and_grad(
        lambda p: lm.loss(p, batch, loss_chunk=8))(params)
    assert np.isfinite(float(loss))
    for leaf in jax.tree.leaves(grads):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()


@pytest.mark.parametrize("arch", ARCH_PARAMS)
def test_train_step_reduces_loss(arch):
    from repro.optim import AdamW
    from repro.train.steps import make_train_step
    cfg = get_config(arch).smoke()
    lm = LM(cfg)
    params = lm.init(RNG)
    opt = AdamW(lr=3e-3, warmup_steps=1, total_steps=20)
    step = jax.jit(make_train_step(lm, opt))
    opt_state = opt.init(params)
    batch = make_batch(cfg)
    losses = []
    for _ in range(8):
        params, opt_state, metrics = step(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses


# every preset: each family's prefill and decode paths, about 3 s a case
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_forward(arch):
    """prefill(S) + decode(token S) == forward(S+1) last logits."""
    cfg = get_config(arch).smoke()
    lm = LM(cfg)
    params = lm.init(jax.random.fold_in(RNG, 2))
    b, s = 2, 12
    toks = jax.random.randint(RNG, (b, s + 1), 0, cfg.vocab)
    full = make_batch(cfg, b, s + 1, with_labels=False)
    full["tokens"] = toks
    pre = dict(full)
    pre["tokens"] = toks[:, :s]
    x, _ = lm.forward(params, full, remat=False)
    full_logits = np.asarray(
        (x[:, s] @ lm.lm_head(params)).astype(jnp.float32))
    cache, _ = lm.prefill(params, pre, max_len=s + 4)
    cache, dec_logits = lm.decode_step(params, cache, toks[:, s])
    rel = np.abs(full_logits - np.asarray(dec_logits)).max() / (
        np.abs(full_logits).max() + 1e-9)
    assert rel < 5e-2, rel


@pytest.mark.parametrize("arch", ARCH_PARAMS)
def test_microbatched_grads_match(arch):
    """Gradient accumulation (2 microbatches) ~= full-batch step."""
    from repro.optim import AdamW
    from repro.train.steps import make_train_step
    cfg = get_config(arch).smoke()
    lm = LM(cfg)
    params = lm.init(RNG)
    opt = AdamW(lr=1e-3, warmup_steps=1, total_steps=10)
    batch = make_batch(cfg, b=4, s=8)
    s1 = make_train_step(lm, opt, microbatches=1)
    s2 = make_train_step(lm, opt, microbatches=2)
    p1, _, m1 = jax.jit(s1)(params, opt.init(params), batch)
    p2, _, m2 = jax.jit(s2)(params, opt.init(params), batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 5e-2
    d = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        p1, p2)
    assert max(jax.tree.leaves(d)) < 5e-2


def test_param_count_sane():
    """Config param math matches the actual tree within 25% (smoke
    scale; position tables excluded — negligible at full scale)."""
    for arch in ARCH_IDS:
        cfg = get_config(arch).smoke()
        lm = LM(cfg)
        params = lm.init(RNG)
        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        actual = sum(
            leaf.size for path, leaf in flat
            if "pos_" not in "/".join(str(getattr(p, "key", p))
                                      for p in path))
        approx = cfg.param_count()
        assert abs(actual - approx) / actual < 0.25, (
            arch, actual, approx)


def test_full_configs_param_counts():
    """Full-scale configs land near their nameplate sizes."""
    expect = {
        "llama32_vision_90b": (80e9, 110e9),
        "grok1_314b": (280e9, 340e9),
        "yi_6b": (5e9, 7e9),
        "falcon_mamba_7b": (5.5e9, 9e9),
        "qwen2_0_5b": (0.4e9, 0.7e9),
        "zamba2_2_7b": (2e9, 3.5e9),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo < n < hi, (arch, n)


def _bench_config(name):
    from bench import harness

    return harness.model_config(json.loads(
        (harness.BENCH_DIR / "configs" / f"{name}.json").read_text()))


def tree_listing(cfg) -> dict:
    """Path, shape and dtype of each leaf of ``abstract_params()`` and of
    ``init_cache(2, 16)``."""
    lm = LM(cfg)

    def listing(tree):
        return {jax.tree_util.keystr(path): f"{leaf.dtype}{list(leaf.shape)}"
                for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}
    return {"params": listing(lm.abstract_params()),
            "cache": listing(jax.eval_shape(lambda: lm.init_cache(2, 16)))}


@pytest.mark.parametrize("arch", ARCH_IDS + [f"bench:{n}" for n in BENCH_CONFIGS])
def test_param_and_cache_trees_are_pinned(arch):
    """Every preset at smoke size, and both served configurations at
    full size, keep the parameter and cache trees recorded in
    ``data/model_trees.json``: what checkpoints, the benchmark's tree
    check and the serving engine's splice read by name."""
    cfg = (_bench_config(arch[len("bench:"):]) if arch.startswith("bench:")
           else get_config(arch).smoke())
    assert tree_listing(cfg) == json.loads(PINNED_TREES.read_text())[arch]
