"""granite-4.0-h-small: the program against the plain reference at a
tiny size, in float32 on the CPU: prefill and decode through the cache,
two streams spliced into one batch, the SSD scan against the sequential
recurrence, and expert shares against the whole expert layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.references import granite_hybrid as ref
from bench.tests.granite_tiny import LOGIT_TOL, TINY, config, decode, program, served


def test_prefill_and_cached_decode_match_the_full_forward():
    cfg = config()
    m = cfg["model"]
    lm, mc = program(cfg)
    params = served(m, mc.padded_vocab)
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, m["vocab"], size=(1, 13)).astype(np.int32)
    nxt = rng.integers(1, m["vocab"], size=5).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        _, got = decode(lm, params, prompt, [[t] for t in nxt])
        seq = np.concatenate([prompt[0], nxt])[None]
        want = ref.head_logits(params, ref.served_hidden(params, seq, m)[0, 12:],
                               m, "f32", chunk=100)
    got = np.stack([g[0, :m["vocab"]] for g in got])
    want = np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=LOGIT_TOL * np.abs(want).max())


def test_two_slots_spliced_by_name_decode_as_each_alone():
    from repro.serving import ServingEngine

    cfg = config()
    m = cfg["model"]
    lm, mc = program(cfg)
    params = served(m, mc.padded_vocab)
    rng = np.random.default_rng(1)
    prompts = rng.integers(1, m["vocab"], size=(2, 1, 12)).astype(np.int32)
    nxt = rng.integers(1, m["vocab"], size=(4, 2)).astype(np.int32)
    eng = ServingEngine(mc, params=params, max_batch=2, max_len=24,
                        prompt_len=12)
    with jax.default_matmul_precision("highest"):
        alone = [decode(lm, params, prompts[i], nxt[:, i:i + 1])[1]
                 for i in range(2)]
        eng.cache = {**lm.init_cache(2, 24), "len": jnp.asarray(12, jnp.int32)}
        for slot in (1, 0):
            cache1, _ = eng._prefill(params, {"tokens": jnp.asarray(prompts[slot])},
                                     max_len=24)
            eng._splice(cache1, slot)
        cache = eng.cache
        for step, toks in enumerate(nxt):
            cache, logits = eng._decode(params, cache, jnp.asarray(toks))
            for i in range(2):
                want = np.asarray(alone[i][step + 1][0])
                np.testing.assert_allclose(np.asarray(logits[i]), want, rtol=0,
                                           atol=1e-6 * np.abs(want).max())
    # the counters are the step's, not a stream's: never spliced
    assert int(cache["expert_rows"]) == 4 * 2 * 4 * 4


#: tolerance of the chunked scan against the recurrence, relative to the
#: largest output: float32 sums in another order, about 1e-7
SSD_TOL = 1e-5


@pytest.mark.parametrize("chunk", [4, 5, 16])
def test_ssd_scan_and_its_final_state_equal_the_recurrence(chunk):
    from repro.models.layers import ssd_scan, ssd_step

    rng = np.random.default_rng(2)
    b, s, h, p, g, n = 2, 13, 4, 8, 2, 6

    def draw(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)

    x, B, C = draw(b, s, h, p), draw(b, s, g, n), draw(b, s, g, n)
    dt = jax.nn.softplus(draw(b, s, h) - 1.0)
    A, D = -jnp.exp(draw(h, scale=0.5)), draw(h)
    s0 = draw(b, h, p, n, scale=0.3)
    y, fin = ssd_scan(x, dt, A, B, C, D, s0, chunk=chunk)
    y_ref, fin_ref = ref.ssm_recurrence(x, dt, A, B, C, D, s0)
    np.testing.assert_allclose(np.asarray(fin), np.asarray(fin_ref), rtol=0,
                               atol=SSD_TOL * float(jnp.abs(fin_ref).max()))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=0,
                               atol=SSD_TOL * float(jnp.abs(y_ref).max()))
    # the decode step continues the scan's final state exactly
    y1, fin1 = ssd_step(x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], D, fin)
    y1_ref, fin1_ref = ref.ssm_recurrence(x[:, :1], dt[:, :1], A, B[:, :1],
                                          C[:, :1], D, fin_ref)
    np.testing.assert_allclose(np.asarray(fin1), np.asarray(fin1_ref), rtol=0,
                               atol=SSD_TOL * float(jnp.abs(fin1_ref).max()))
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y1_ref[:, 0]), rtol=0,
                               atol=SSD_TOL * float(jnp.abs(y1_ref).max()))
    # the scan in bfloat16 misses the tolerance by far
    bf = [t.astype(jnp.bfloat16).astype(jnp.float32) for t in (x, dt, B, C)]
    y_bf, fin_bf = ref.ssm_recurrence(bf[0], bf[1], A, bf[2], bf[3], D, s0)
    assert float(jnp.abs(fin_bf - fin_ref).max()) > 10 * SSD_TOL * float(
        jnp.abs(fin_ref).max())


def test_expert_shares_add_up_to_the_whole_layer():
    """Four disjoint shares of eight experts, each computed by the
    program's expert layer as a chip holding it would, plus the shared
    MLP once, add up to the reference layer over all eight."""
    from repro.models.layers import held_expert_mlp, swiglu

    cfg = config(moe={**TINY["moe"], "first_held": 0, "n_held": 8})
    m = cfg["model"]
    params = served(m, 256, seed=9)
    w = {k: v[0] for k, v in params["mamba"].items()}
    h = jax.random.normal(jax.random.PRNGKey(3), (11, m["d_model"]))
    with jax.default_matmul_precision("highest"):
        parts = [held_expert_mlp(h, w["router"], w["wg"][i:i + 2],
                                 w["wu"][i:i + 2], w["wd"][i:i + 2],
                                 m["moe"]["top_k"], i)
                 for i in range(0, 8, 2)]
        got = sum(p for p, _ in parts) + swiglu(
            h, w["shared_wg"], w["shared_wu"], w["shared_wd"])
        want = ref.experts(h, w, m, "f32") + ref.shared_mlp(h, w, "f32")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-6 * float(jnp.abs(want).max()))
    # every token's k routed pairs land on exactly one share
    assert sum(int(r) for _, r in parts) == 11 * m["moe"]["top_k"]
    # a share that holds none of a token's experts gives it nothing
    zero = held_expert_mlp(h[:1], w["router"], w["wg"][:2], w["wu"][:2],
                           w["wd"][:2], 1, 8)
    assert not np.asarray(zero[0]).any() and int(zero[1]) == 0
