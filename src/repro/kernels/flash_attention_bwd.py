"""Flash-attention backward Pallas TPU kernel.

Computes (dq, dk, dv) without ever materializing the (S, T) probability
matrix in HBM: grid = (B*Hq, T/bk, S/bq) — the KV block is the *outer*
parallel axis so dk/dv accumulate in VMEM scratch across the inner
sequential q sweep; dq is accumulated into its output block via
read-modify-write on the first/each kv pass.

Layout note (vs the fwd kernel): backward is naturally kv-major — each
(kv block) program recomputes p for every q block against its own K/V
tile, which gives exact dk/dv locality; dq is revisited T/bk times, the
standard flash-2 backward trade.

Inputs are pre-expanded to Hq heads (GQA reduction to Hkv happens in
the ops.py wrapper via reshape-sum, matching the custom-vjp fallback).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                causal: bool, bq: int, bk: int, kv_len: int, scale: float):
    j = pl.program_id(1)          # kv block (outer)
    i = pl.program_id(2)          # q block (inner, sequential)
    ni = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0].astype(jnp.float32)          # (bq, d)
    k = k_ref[0].astype(jnp.float32)          # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)        # (bq, d)
    lse = lse_ref[0, 0]                       # (bq,) of a (1, 1, bq) block
    delta = delta_ref[0, 0]                   # (bq,)

    s = scale * jnp.dot(q, k.T, preferred_element_type=jnp.float32)
    q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kv_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kv_pos < kv_len
    if causal:
        mask = mask & (kv_pos <= q_pos)
    p = jnp.exp(s - lse[:, None])
    p = jnp.where(mask, p, 0.0)               # (bq, bk)

    dv_acc[...] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
    dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None]) * scale
    dk_acc[...] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)

    # dq accumulates across kv blocks: rmw into the output block
    contrib = jnp.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _dq_first():
        dq_ref[0] = contrib.astype(dq_ref.dtype)

    @pl.when(j > 0)
    def _dq_acc():
        dq_ref[0] = (dq_ref[0].astype(jnp.float32) + contrib
                     ).astype(dq_ref.dtype)

    @pl.when(i == ni - 1)
    def _fin():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd_bhsd(q, k, v, do, lse, delta, *, causal: bool,
                             bq: int = 128, bk: int = 128,
                             kv_len: int | None = None,
                             sm_scale: float | None = None,
                             interpret: bool = False):
    """q, do: (BH, S, D); k, v: (BH, T, D) (pre-expanded heads);
    lse, delta: (BH, S).  Returns (dq, dk, dv)."""
    bh, s_len, d = q.shape
    t = k.shape[1]
    assert s_len % bq == 0 and t % bk == 0
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    kv_len = t if kv_len is None else kv_len
    grid = (bh, t // bk, s_len // bq)

    def q_map(b, j, i):
        return (b, i, 0)

    def kv_map(b, j, i):
        return (b, j, 0)

    # lse/delta travel as (BH, 1, S): a (1, bq) block of a (BH, S) array
    # breaks the TPU's (8, 128) tiling rule on its second-to-last dim
    def stat_map(b, j, i):
        return (b, 0, i)

    kernel = functools.partial(_bwd_kernel, causal=causal, bq=bq, bk=bk,
                               kv_len=kv_len, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, 1, bq), stat_map),
            pl.BlockSpec((1, 1, bq), stat_map),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_len, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t, d), k.dtype),
            jax.ShapeDtypeStruct((bh, t, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse[:, None], delta[:, None])
