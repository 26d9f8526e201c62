"""The serving engine donates its cache to the decode program: a step
deletes the cache it was given and writes the new one into its buffers,
the tokens are those of the undonated program, the model's counters
that ``run()`` returns stay readable after later steps, and a step
writes one K/V row a layer.  On the CPU, for
a tiny dense model and a tiny granite (Mamba-2, attention and held
experts)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.transformer import LM
from repro.serving import Request, ServingEngine

PROMPT_LEN, MAX_LEN = 6, 32


def _dense():
    cfg = get_config("qwen2_0_5b").smoke()
    return cfg, LM(cfg).init(jax.random.PRNGKey(1))


def _granite():
    from bench.tests.granite_tiny import config, program, served

    cfg = config()
    lm, mc = program(cfg)
    return mc, served(cfg["model"], mc.padded_vocab)


MODELS = {"dense": _dense, "granite": _granite}


@pytest.fixture(scope="module", params=sorted(MODELS))
def model(request):
    return MODELS[request.param]()


def _engine(model) -> ServingEngine:
    cfg, params = model
    return ServingEngine(cfg, params=params, max_batch=2, max_len=MAX_LEN,
                         prompt_len=PROMPT_LEN)


def _requests(n: int, new: int, first: int = 0) -> list[Request]:
    return [Request(rid=first + i, prompt=[3 + 5 * i, 7, 11 + i, 2],
                    max_new_tokens=new) for i in range(n)]


def _admitted(model) -> ServingEngine:
    """An engine with two requests prefilled and spliced into its slots."""
    eng = _engine(model)
    for r in _requests(2, new=30):
        eng.submit(r)
    eng._admit()
    return eng


def test_a_step_deletes_the_cache_it_was_given(model):
    eng = _admitted(model)
    eng._step_decode()
    old = eng.cache
    eng._step_decode()
    for k in eng._per_stream:
        assert old[k].is_deleted(), k
    assert old["len"].is_deleted()
    # the counters are passed apart from the cache, and never donated
    assert not any(old[k].is_deleted() for k in eng._counters)
    assert not any(a.is_deleted() for a in eng.cache.values())


def test_decode_program_aliases_the_cache(model):
    eng = _admitted(model)
    per_stream = sum(eng.cache[k].nbytes for k in eng._per_stream)
    assert eng.decode_alias_bytes() >= per_stream > 0


def test_greedy_tokens_equal_the_undonated_program(model):
    """20 greedy steps of two spliced slots through the engine's donated
    program, against ``jax.jit(lm.decode_step)`` from a copy of the same
    cache: the same tokens and the same logits."""
    eng = _admitted(model)
    vocab = eng.cfg.vocab
    plain = jax.jit(eng.lm.decode_step)
    tokens = jnp.asarray([r.out_tokens[-1] for r in eng.slots], jnp.int32)
    donated = {**eng.cache, "len": jnp.asarray(PROMPT_LEN, jnp.int32)}
    kept = jax.tree.map(jnp.copy, donated)
    tok_d, tok_k = tokens, tokens
    for _ in range(20):
        donated, logits_d = eng._decode(eng.params, donated, tok_d)
        kept, logits_k = plain(eng.params, kept, tok_k)
        np.testing.assert_array_equal(np.asarray(logits_d), np.asarray(logits_k))
        tok_d = jnp.argmax(logits_d[:, :vocab], -1).astype(jnp.int32)
        tok_k = jnp.argmax(logits_k[:, :vocab], -1).astype(jnp.int32)
        np.testing.assert_array_equal(np.asarray(tok_d), np.asarray(tok_k))
    for k in kept:
        np.testing.assert_array_equal(np.asarray(donated[k]), np.asarray(kept[k]))


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it (scan and
    cond bodies, remat, calls)."""
    for e in jaxpr.eqns:
        yield e
        for p in e.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def test_decode_writes_one_kv_row_per_attention_layer(model):
    """The decode step writes into the stacked K/V only the token's row
    of a layer, (1, B, 1, Hkv, hd); no whole (B, T, Hkv, hd) layer slice
    goes back."""
    cfg, params = model
    lm = LM(cfg)
    cache = lm.init_cache(2, MAX_LEN)
    jaxpr = jax.make_jaxpr(lm.decode_step)(
        params, cache, jnp.zeros(2, jnp.int32)).jaxpr
    kv = cache["k"].shape
    writes = [e.invars[1].aval.shape for e in _eqns(jaxpr)
              if e.primitive.name == "dynamic_update_slice"
              and e.invars[0].aval.shape == kv]
    assert writes == [(1, 2, 1) + kv[3:]] * 2, writes


#: the engine's counters that do not depend on the clock or the process
COUNTS = ("prefills", "decode_steps", "completed", "slots_busy", "slots_idle")


def _served(model, read_between: bool):
    eng = _engine(model)
    warm = _requests(1, new=2, first=-1)[0]
    eng.submit(warm)
    while not warm.done:
        eng.run(max_steps=1)
    s0 = eng.run(max_steps=0)
    for r in _requests(3, new=6):
        eng.submit(r)
    for _ in range(10):
        if read_between:
            eng.run(max_steps=0)
        eng.run(max_steps=1)
    return eng, s0, eng.run(max_steps=0)


def test_counters_read_before_later_steps_stay_readable(model):
    """The benchmark's pattern: counters taken before the window, read
    again on every iteration, subtracted after it.  Each difference
    equals that of an engine that reads its counters at the end only."""
    eng, s0, s1 = _served(model, read_between=True)
    d = {k: s1[k] - s0[k] for k in s1}
    _, t0, t1 = _served(model, read_between=False)
    for k in COUNTS + tuple(eng._counters):
        assert int(d[k]) == int(t1[k]) - int(t0[k]), k
    assert d["decode_steps"] > 0
    if eng.cfg.moe is not None:
        # every held expert computes a row for every slot, layer and step
        assert int(d["expert_rows"]) == (d["decode_steps"] * eng.max_batch
                                         * eng.cfg.moe.held * eng.cfg.n_layers)
        assert 0 < int(d["expert_routed"]) <= int(d["expert_rows"])
