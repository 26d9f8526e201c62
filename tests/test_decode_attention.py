"""The models' decode attention (``repro.models.layers.decode_attention``)
against the float32 oracle, and a structural guard on its grouped
contraction: at the serving cell's shapes no intermediate may hold the
KV cache expanded to every query head."""

import math

import jax
import jax.extend.core as jex
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ref import decode_attention_ref
from repro.models.layers import decode_attention

B, T, D = 3, 160, 64


def _rnd(shape, dtype, k):
    return jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(0), k),
                             shape, jnp.float32).astype(dtype)


def _bf16_step(x: np.ndarray) -> float:
    """One bfloat16 step (8 significant bits) at the scale of max |x|."""
    return 2.0 ** (math.floor(math.log2(float(np.abs(x).max()))) - 7)


def _call(length_kind: str, q, k, v):
    """Run ``decode_attention`` with ``length`` in the given form; return
    the output and the per-row lengths it stands for."""
    if length_kind == "int":
        return decode_attention(q, k, v, 97), [97] * B
    if length_kind == "traced":
        fn = jax.jit(decode_attention)
        return fn(q, k, v, jnp.int32(97)), [97] * B
    if length_kind == "per_batch":
        lens = [1, 58, T - 1]
        return decode_attention(q, k, v, jnp.asarray(lens, jnp.int32)), lens
    assert length_kind == "full"
    return decode_attention(q, k, v, k.shape[1]), [T] * B


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("length_kind", ["int", "traced", "per_batch", "full"])
@pytest.mark.parametrize("hq,hkv", [(8, 8), (14, 2), (32, 2)],
                         ids=["mha8", "gqa14_2", "gqa32_2"])
def test_decode_attention_matches_reference(hq, hkv, length_kind, dtype):
    q = _rnd((B, 1, hq, D), dtype, 0)
    k = _rnd((B, T, hkv, D), dtype, 1)
    v = _rnd((B, T, hkv, D), dtype, 2)
    out, lens = _call(length_kind, q, k, v)
    assert out.shape == q.shape and out.dtype == q.dtype
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    want = np.concatenate([
        np.asarray(decode_attention_ref(*(x[i:i + 1] for x in f32), n))
        for i, n in enumerate(lens)])
    got = np.asarray(out, np.float32)
    tol = _bf16_step(want)
    if dtype == jnp.float32:
        tol *= 2.0 ** -8     # float32 throughout: summation order only
    np.testing.assert_allclose(got, want, rtol=0, atol=tol)


def _avals(jaxpr):
    """Shapes of every value a jaxpr and its sub-jaxprs produce."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield var.aval.shape
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                if isinstance(sub, jex.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jex.Jaxpr):
                    yield from _avals(sub)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_decode_attention_never_expands_the_cache(dtype):
    # serve.chatglm3-6b.chat: batch 8, 2,432 cache positions, 32 query
    # heads over 2 KV groups of 128; traced only, nothing runs
    b, t, hq, hkv, d = 8, 2432, 32, 2, 128
    q = jax.ShapeDtypeStruct((b, 1, hq, d), dtype)
    kv = jax.ShapeDtypeStruct((b, t, hkv, d), dtype)
    closed = jax.make_jaxpr(decode_attention)(q, kv, kv, jnp.int32(t))
    expanded = b * t * hq * d
    sizes = [math.prod(s) for s in _avals(closed.jaxpr)]
    assert sizes and max(sizes) < expanded
