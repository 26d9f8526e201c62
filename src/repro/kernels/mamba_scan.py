"""Selective state-space scan Pallas TPU kernel (Mamba recurrence).

TPU-native adaptation of the CUDA selective-scan: the GPU kernel keeps
per-thread states in registers and scans warp-wide; on TPU we tile the
channel dimension so each program owns a (bd, N) state slab in VMEM and
streams sequence chunks HBM->VMEM.  Grid = (B, Din/bd, S/L) with the
chunk axis innermost-sequential; the state persists in VMEM scratch
across chunks, so HBM traffic is exactly one read of (x, dt, B, C) and
one write of y — the operational-intensity win the paper's CUDA kernel
gets from shared memory.

Within a chunk the recurrence is stepped with a fori_loop over L; each
step reads its timestep's rows straight from the refs (``pl.ds``: the
TPU lowering has no dynamic_slice of a loaded value), does a (bd, N) VPU
elementwise update + a (bd,) contraction, and stores its output row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, o_ref, h_ref,
                 *, chunk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[...].astype(jnp.float32)          # (bd, N)
    dpar = d_ref[...].astype(jnp.float32)       # (1, bd)

    def step(t, h):
        row = pl.ds(t, 1)
        x_t = x_ref[0, row, :].astype(jnp.float32)            # (1, bd)
        dt_t = jax.nn.softplus(dt_ref[0, row, :].astype(jnp.float32))
        b_t = b_ref[0, row, :].astype(jnp.float32)            # (1, N)
        c_t = c_ref[0, row, :].astype(jnp.float32)
        decay = jnp.exp(dt_t.T * a)                           # (bd, N)
        h = decay * h + (dt_t * x_t).T * b_t
        y_t = jnp.sum(h * c_t, axis=1)[None, :] + dpar * x_t
        o_ref[0, row, :] = y_t.astype(o_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])


def mamba_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
               C: jax.Array, D: jax.Array, *, bd: int = 512,
               chunk: int = 64, interpret: bool = False) -> jax.Array:
    """x, dt: (Bt, S, Din); A: (Din, N); B, C: (Bt, S, N); D: (Din,).
    Returns y: (Bt, S, Din).  dt is pre-bias, softplus applied inside.
    """
    bt, s, din = x.shape
    n = A.shape[1]
    bd = min(bd, din)
    chunk = min(chunk, s)
    assert din % bd == 0 and s % chunk == 0, (din, bd, s, chunk)
    grid = (bt, din // bd, s // chunk)

    def xd_map(b, i, k):
        return (b, k, i)

    def bc_map(b, i, k):
        return (b, k, 0)

    def a_map(b, i, k):
        return (i, 0)

    def d_map(b, i, k):
        return (0, i)

    kernel = functools.partial(_scan_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, bd), xd_map),
            pl.BlockSpec((1, chunk, bd), xd_map),
            pl.BlockSpec((1, chunk, n), bc_map),
            pl.BlockSpec((1, chunk, n), bc_map),
            pl.BlockSpec((bd, n), a_map),
            pl.BlockSpec((1, bd), d_map),
        ],
        out_specs=pl.BlockSpec((1, chunk, bd), xd_map),
        out_shape=jax.ShapeDtypeStruct((bt, s, din), x.dtype),
        scratch_shapes=[pltpu.VMEM((bd, n), jnp.float32)],
        interpret=interpret,
    )(x, dt, B, C, A, D.reshape(1, din))
