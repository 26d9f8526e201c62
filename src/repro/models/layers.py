"""Model building blocks (pure JAX, GSPMD-friendly).

Every op here is written to be safe at production scale *at compile
time*: attention and the selective scan are chunked (lax.scan over
blocks with online accumulators) so the dry-run's memory analysis never
materializes O(S^2) or O(S*N*D) temporaries.  The Pallas kernels in
``repro.kernels`` implement the same math for the TPU target; these jnp
paths are simultaneously the reference oracles and the XLA fallback.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _constrain_batch(x: jax.Array) -> jax.Array:
    from .sharding import constrain_batch_dim
    return constrain_batch_dim(x)


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w.astype(jnp.float32)).astype(dt)


# -- RoPE ------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float, style: str) -> np.ndarray:
    rot = head_dim if style == "full" else head_dim // 2
    return 1.0 / theta ** (np.arange(0, rot, 2, dtype=np.float32) / rot)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               style: str = "full") -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    rot = d if style == "full" else d // 2
    freqs = jnp.asarray(rope_freqs(d, theta, style))          # (rot/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs    # (..., S, rot/2)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    xr = x[..., :rot].astype(jnp.float32)
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    r1 = x1 * cos - x2 * sin
    r2 = x1 * sin + x2 * cos
    rotated = jnp.stack([r1, r2], axis=-1).reshape(xr.shape).astype(x.dtype)
    if rot == d:
        return rotated
    return jnp.concatenate([rotated, x[..., rot:]], axis=-1)


# -- attention ---------------------------------------------------------------------


def _expand_kv(k: jax.Array, n_q_heads: int) -> jax.Array:
    """(B, T, Hkv, D) -> (B, T, Hq, D) by repeating groups."""
    b, t, hkv, d = k.shape
    if hkv == n_q_heads:
        return k
    rep = n_q_heads // hkv
    return jnp.repeat(k, rep, axis=2)


NEG_BIG = -1e30


def _attn_mask(s: int, chunk: int, ci, t: int, causal: bool, q_offset: int):
    kv_pos = ci * chunk + jnp.arange(chunk)
    mask = (kv_pos[None, :] < t) & jnp.ones((s, 1), bool)
    if causal:
        q_pos = q_offset + jnp.arange(s)
        mask = mask & (kv_pos[None, :] <= q_pos[:, None])
    return mask  # (s, chunk)


def _flash_fwd_scan(qf, kc_t, vc_t, s, chunk, t, causal, q_offset):
    b, hq, _, d = (qf.shape[0], qf.shape[2], qf.shape[1], qf.shape[3])

    def step(carry, blk):
        m, l, acc = carry
        kb, vb, ci = blk
        logits = jnp.einsum("bshd,bthd->bhst", qf, kb,
                            preferred_element_type=jnp.float32)
        mask = _attn_mask(s, chunk, ci, t, causal, q_offset)
        logits = jnp.where(mask[None, None], logits, NEG_BIG)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        p = jnp.where(mask[None, None], p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhst,bthd->bhsd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    n_chunks = kc_t.shape[0]
    m0 = jnp.full((b, hq, s), NEG_BIG, jnp.float32)
    l0 = jnp.zeros((b, hq, s), jnp.float32)
    a0 = jnp.zeros((b, hq, s, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), (kc_t, vc_t, jnp.arange(n_chunks)))
    l_safe = jnp.maximum(l, 1e-37)
    out = acc / l_safe[..., None]
    lse = m + jnp.log(l_safe)
    return out, lse


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention_xla(q, k, v, causal: bool, q_offset: int, chunk: int,
                         scale: float):
    """Differentiable flash attention in pure XLA.

    Forward saves only (q, k, v, o, lse) — the KV-chunk scan's per-chunk
    probabilities are never stacked as autodiff residuals; the backward
    pass recomputes them chunk-by-chunk (the flash-attention backward),
    which is what keeps the memory roofline term sane at seq 4k-32k.
    q: (B, S, Hq, D); k, v already expanded to Hq heads.
    """
    out, _ = _flash_core(q, k, v, causal, q_offset, chunk, scale)
    return out


def _flash_core(q, k, v, causal, q_offset, chunk, scale):
    b, s, hq, d = q.shape
    t = k.shape[1]
    n_chunks = (t + chunk - 1) // chunk
    pad = n_chunks * chunk - t
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc_t = jnp.moveaxis(k.reshape(b, n_chunks, chunk, hq, d), 1, 0)
    vc_t = jnp.moveaxis(v.reshape(b, n_chunks, chunk, hq, d), 1, 0)
    qf = (q * scale).astype(q.dtype)
    acc, lse = _flash_fwd_scan(qf, kc_t, vc_t, s, chunk, t, causal, q_offset)
    return jnp.moveaxis(acc, 1, 2).astype(q.dtype), lse


def _flash_fwd(q, k, v, causal, q_offset, chunk, scale):
    out, lse = _flash_core(q, k, v, causal, q_offset, chunk, scale)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, q_offset, chunk, scale, res, dout):
    q, k, v, out, lse = res
    b, s, hq, d = q.shape
    t = k.shape[1]
    n_chunks = (t + chunk - 1) // chunk
    pad = n_chunks * chunk - t
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else k
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else v
    kc_t = jnp.moveaxis(kp.reshape(b, n_chunks, chunk, hq, d), 1, 0)
    vc_t = jnp.moveaxis(vp.reshape(b, n_chunks, chunk, hq, d), 1, 0)
    do = jnp.moveaxis(dout, 2, 1).astype(jnp.float32)      # (B, Hq, S, D)
    of = jnp.moveaxis(out, 2, 1).astype(jnp.float32)
    delta = jnp.sum(do * of, axis=-1)                      # (B, Hq, S)
    qf = q.astype(jnp.float32)

    def step(dq_acc, blk):
        kb, vb, ci = blk
        kf = kb.astype(jnp.float32)
        vf = vb.astype(jnp.float32)
        logits = scale * jnp.einsum("bshd,bthd->bhst", qf, kf)
        mask = _attn_mask(s, chunk, ci, t, causal, q_offset)
        p = jnp.exp(logits - lse[..., None])
        p = jnp.where(mask[None, None], p, 0.0)            # (B, Hq, S, ck)
        dv = jnp.einsum("bhst,bhsd->bthd", p, do)
        dp = jnp.einsum("bhsd,bthd->bhst", do, vf)
        ds = p * (dp - delta[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum("bhst,bthd->bshd", ds, kf)
        dk = jnp.einsum("bhst,bshd->bthd", ds, qf)
        return dq_acc, (dk.astype(k.dtype), dv.astype(v.dtype))

    dq0 = jnp.zeros((b, s, hq, d), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(
        step, dq0, (kc_t, vc_t, jnp.arange(n_chunks)))
    dk = jnp.moveaxis(dks, 0, 1).reshape(b, n_chunks * chunk, hq, d)[:, :t]
    dv = jnp.moveaxis(dvs, 0, 1).reshape(b, n_chunks * chunk, hq, d)[:, :t]
    if pad:
        dk = dk[:, :t]
        dv = dv[:, :t]
    return dq.astype(q.dtype), dk, dv


_flash_attention_xla.defvjp(_flash_fwd, _flash_bwd)


def blocked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      causal: bool, q_offset: int = 0,
                      chunk: int = 512, scale: float = 0.0) -> jax.Array:
    """Flash-style attention, scanned over KV chunks, with a flash
    custom-VJP so training never stacks per-chunk probabilities.

    q: (B, S, Hq, D);  k, v: (B, T, Hkv, D).  Peak temp is
    (B, Hq, S, chunk).  ``scale`` multiplies the scores; 0 is
    1/sqrt(D).
    """
    hq = q.shape[2]
    t = k.shape[1]
    k = _expand_kv(k, hq)
    v = _expand_kv(v, hq)
    chunk = min(chunk, t)
    return _flash_attention_xla(q, k, v, causal, q_offset, chunk,
                                scale or 1.0 / np.sqrt(q.shape[-1]))


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     length: jax.Array | int, scale: float = 0.0) -> jax.Array:
    """Single-position GQA attention against a KV cache.

    q: (B, 1, Hq, D); caches: (B, T, Hkv, D); ``length`` (a scalar or
    one per batch row) masks the valid prefix; ``scale`` multiplies the
    scores, 0 is 1/sqrt(D).  The contraction is
    grouped: the G = Hq / Hkv query heads of a KV group are contracted
    together against that group as the cache stores it, in the cache's
    dtype with float32 accumulation, so each KV group is read once and
    the cache is never expanded to Hq heads.  The seq-sharded shard_map
    variant below uses the same contraction.
    """
    b, _, hq, d = q.shape
    t, hkv = k_cache.shape[1], k_cache.shape[2]
    qs = q * scale if scale else q / np.sqrt(d)
    qg = qs.reshape(b, hkv, hq // hkv, d).astype(k_cache.dtype)
    logits = jnp.einsum("bkgd,btkd->bkgt", qg, k_cache,
                        preferred_element_type=jnp.float32)
    mask = jnp.arange(t)[None, None, None, :] < jnp.asarray(length).reshape(-1, 1, 1, 1)
    logits = jnp.where(mask, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, hq, d).astype(q.dtype)


# -- MLPs ---------------------------------------------------------------------------


def decode_attention_sharded(q, k_cache, v_cache, k_new, v_new, length,
                             *, dp_axes: tuple, model_axis: str = "model"):
    """Flash-decode with the KV cache sequence-sharded over the model
    axis (one shard_map: local cache update + partial softmax + psum
    combine).

    The baseline GSPMD lowering of decode with a seq-sharded cache
    reshards the whole cache every step ("involuntary full
    rematerialization"); here the new token's KV is written only on the
    owning shard and the softmax is stitched with three tiny psums —
    the EXPERIMENTS.md SPerf decode iteration.

    q: (B, 1, Hq, D); caches: (B, T, Hkv, D); k_new/v_new: (B, 1, Hkv, D).
    Returns (out (B, 1, Hq, D), k_cache, v_cache).
    """
    from jax.sharding import PartitionSpec as P
    from .sharding import get_ctx_mesh
    mesh = get_ctx_mesh()
    n_shards = mesh.shape[model_axis]
    t = k_cache.shape[1]
    t_local = t // n_shards
    scale = 1.0 / np.sqrt(q.shape[-1])

    def local(q, kc, vc, kn, vn, length):
        sid = jax.lax.axis_index(model_axis)
        b = q.shape[0]
        hq = q.shape[2]
        hkv = kc.shape[2]
        dd = q.shape[3]
        g = hq // hkv
        # write the new KV on the owning shard only; non-owners write
        # back the slice they already hold (single in-place DUS, no
        # whole-cache select copies)
        pos = length - sid * t_local
        owner = (pos >= 0) & (pos < t_local)
        pos_c = jnp.clip(pos, 0, t_local - 1)
        cur_k = jax.lax.dynamic_slice(kc, (0, pos_c, 0, 0),
                                      (b, 1, hkv, dd))
        cur_v = jax.lax.dynamic_slice(vc, (0, pos_c, 0, 0),
                                      (b, 1, hkv, dd))
        kn_eff = jnp.where(owner, kn.astype(kc.dtype), cur_k)
        vn_eff = jnp.where(owner, vn.astype(vc.dtype), cur_v)
        kc = jax.lax.dynamic_update_slice(kc, kn_eff, (0, pos_c, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, vn_eff, (0, pos_c, 0, 0))
        # grouped-query partial attention (no KV head repetition, bf16
        # operands with f32 accumulation: the cache is never up-cast)
        q1 = (q[:, 0].reshape(b, hkv, g, dd) * scale).astype(kc.dtype)
        logits = jnp.einsum("bkgd,btkd->bkgt", q1, kc,
                            preferred_element_type=jnp.float32)
        kv_pos = sid * t_local + jnp.arange(t_local)
        mask = kv_pos[None, None, None, :] <= length
        logits = jnp.where(mask, logits, -1e30)
        m_loc = logits.max(axis=-1)                       # (B,Hkv,G)
        p = jnp.exp(logits - m_loc[..., None])
        p = jnp.where(mask, p, 0.0)
        l_loc = p.sum(axis=-1)
        o_loc = jnp.einsum("bkgt,btkd->bkgd", p.astype(vc.dtype), vc,
                           preferred_element_type=jnp.float32)
        # softmax stitch across shards
        m_glob = jax.lax.pmax(m_loc, model_axis)
        corr = jnp.exp(m_loc - m_glob)
        l_glob = jax.lax.psum(l_loc * corr, model_axis)
        o_glob = jax.lax.psum(o_loc * corr[..., None], model_axis)
        out = o_glob / jnp.maximum(l_glob, 1e-30)[..., None]
        return out.reshape(b, 1, hq, dd).astype(q.dtype), kc, vc

    dp = P(dp_axes) if dp_axes else P(None)
    rep4 = P(dp_axes if dp_axes else None, None, None, None)
    kv_spec = P(dp_axes if dp_axes else None, model_axis, None, None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(rep4, kv_spec, kv_spec, rep4, rep4, P()),
        out_specs=(rep4, kv_spec, kv_spec),
        check_vma=False,
    )(q, k_cache, v_cache, k_new, v_new, length)


def swiglu(x: jax.Array, wg: jax.Array, wu: jax.Array, wd: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ wg) * (x @ wu)
    return h @ wd


def moe_mlp(x: jax.Array, router_w: jax.Array, wg: jax.Array, wu: jax.Array,
            wd: jax.Array, top_k: int, capacity_factor: float = 1.25,
            group_routing: bool = True):
    """Top-k token-choice MoE with expert-capacity gather/scatter.

    ``group_routing=True`` (default): capacity is applied *per sequence*
    (group-limited routing) so every dispatch tensor keeps the batch dim
    and shards over DP — without it, the per-expert top-C runs over the
    global token set, which GSPMD cannot shard (the EXPERIMENTS.md SPerf
    granite/grok iteration; 16x replicated expert compute in the
    baseline lowering).

    FLOP-honest dispatch: per-expert top-C token gather (no one-hot
    matmuls), expert SwiGLU on (B, E, C, D), weighted scatter-add back.
    x: (B, S, D); wg/wu: (E, D, F); wd: (E, F, D).
    Returns (out, aux_loss).
    """
    b, s, d = x.shape
    e = router_w.shape[1]
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                      # (B, S, E)
    top_p, top_i = jax.lax.top_k(probs, top_k)                   # (B, S, k)
    chosen = jnp.zeros_like(probs).at[
        jnp.arange(b)[:, None, None], jnp.arange(s)[None, :, None],
        top_i].set(top_p)                                        # (B, S, E)

    if not group_routing:
        xt = x.reshape(1, b * s, d)
        chosen = chosen.reshape(1, b * s, e)
        b_eff, n = 1, b * s
    else:
        xt = x
        b_eff, n = b, s

    cap = max(1, min(int(np.ceil(top_k * n / e * capacity_factor)), n))
    # per-(group, expert) strongest tokens within capacity
    gate_ec, idx_ec = jax.lax.top_k(
        jnp.swapaxes(chosen, -1, -2), cap)                       # (B, E, C)
    idx_ec = _constrain_batch(idx_ec)
    xg = jnp.take_along_axis(xt[:, None], idx_ec[..., None],
                             axis=2)                             # (B, E, C, D)
    xg = _constrain_batch(xg)
    # operand-dtype dispatch intermediates: the (B,E,C,F) hidden tensor
    # dominates MoE HBM traffic at grok scale (XLA's MXU accumulates
    # bf16 dots in f32 internally; CPU thunks reject explicit
    # bf16->f32 preferred types)
    h = (jax.nn.silu(jnp.einsum("becd,edf->becf", xg, wg))
         * jnp.einsum("becd,edf->becf", xg, wu)).astype(x.dtype)
    y = jnp.einsum("becf,efd->becd", h, wd)                      # (B, E, C, D)
    y = (y * gate_ec[..., None].astype(y.dtype)).astype(x.dtype)
    y = _constrain_batch(y)
    out = jnp.zeros((b_eff, n, d), y.dtype).at[
        jnp.arange(b_eff)[:, None, None], idx_ec].add(y)
    out = _constrain_batch(out)
    # load-balance aux loss (Switch-style)
    me = probs.reshape(-1, e).mean(axis=0)
    ce = (chosen > 0).astype(jnp.float32).reshape(-1, e).mean(axis=0)
    aux = e * jnp.sum(me * ce)
    return out.reshape(b, s, d), aux


def held_expert_mlp(x: jax.Array, router_w: jax.Array, wg: jax.Array,
                    wu: jax.Array, wd: jax.Array, top_k: int,
                    first: int) -> tuple[jax.Array, jax.Array]:
    """The part of a top-k MoE layer that the experts held here give.

    The router keeps its full width: x: (T, D); router_w: (D, E) over all
    E experts; each token takes its top ``top_k`` router logits and a
    softmax over those k (granite's TopKGating).  wg, wu: (Eh, D, F) and
    wd: (Eh, F, D) are the held experts ``first .. first + Eh - 1``, each
    a SwiGLU.  Every token is computed on every held expert and weighted
    by its gate there, zero where the token did not choose the expert: no
    capacity, no dropped token.  A token whose experts are all absent
    gets zero from this layer.

    Returns (out (T, D), routed): ``routed`` counts the (token, expert)
    pairs that landed on a held expert; the matmuls compute T * Eh rows.
    """
    eh = wg.shape[0]
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    top_v, top_i = jax.lax.top_k(logits, top_k)                  # (T, k)
    gates = jax.nn.softmax(top_v, axis=-1)
    hit = top_i[..., None] == first + jnp.arange(eh)              # (T, k, Eh)
    gate = jnp.sum(jnp.where(hit, gates[..., None], 0.0), axis=-2)  # (T, Eh)
    h = (jax.nn.silu(jnp.einsum("td,edf->etf", x, wg))
         * jnp.einsum("td,edf->etf", x, wu))
    # the gate folded in before the down projection, so that no
    # (Eh, T, D) tensor is made: one contraction over (expert, F)
    h = (h * jnp.swapaxes(gate, 0, 1)[..., None].astype(h.dtype)).astype(x.dtype)
    out = jnp.einsum("etf,efd->td", h, wd)
    return out, jnp.sum(hit, dtype=jnp.int32)


# -- causal depthwise conv (mamba) ------------------------------------------------------


def causal_conv1d(x: jax.Array, w: jax.Array, state: jax.Array | None = None):
    """Depthwise causal conv.  x: (B, S, C); w: (K, C).

    Returns (y, new_state): ``state`` carries the trailing K-1 inputs so
    decode can stream one token at a time.
    """
    k = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i][None, None, :] for i in range(k))
    new_state = xp[:, -(k - 1):] if k > 1 else xp[:, :0]
    return y, new_state


# -- selective scan (mamba) ----------------------------------------------------------------


def selective_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                   C: jax.Array, D: jax.Array,
                   h0: jax.Array | None = None, chunk: int = 256,
                   scan_dtype=jnp.float32):
    """Chunked selective state-space scan (Mamba recurrence).

    x, dt: (Bt, S, Din);  A: (Din, N);  B, C: (Bt, S, N);  D: (Din,)
    h_{t} = exp(dt_t * A) * h_{t-1} + dt_t * B_t * x_t;  y_t = C_t . h_t + D * x_t

    lax.scan over chunks carrying (Bt, Din, N) state; within a chunk an
    associative scan over at most ``chunk`` steps.  Peak temp is
    (Bt, chunk, Din, N) — never (Bt, S, Din, N).
    Returns (y, h_final).
    """
    bt, s, din = x.shape
    n = A.shape[1]
    chunk = min(chunk, s)
    n_chunks = (s + chunk - 1) // chunk
    pad = n_chunks * chunk - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    xc = x.reshape(bt, n_chunks, chunk, din)
    dtc = dt.reshape(bt, n_chunks, chunk, din)
    Bc = B.reshape(bt, n_chunks, chunk, n)
    Cc = C.reshape(bt, n_chunks, chunk, n)

    if h0 is None:
        h0 = jnp.zeros((bt, din, n), jnp.float32)

    def assoc(a, b):
        # elements: (decay, inhom); compose left-to-right
        da, xa = a
        db, xb = b
        return da * db, xa * db + xb

    def chunk_step(h, blk):
        xb, dtb, bb, cb = blk                     # (Bt, L, ...)
        dtb = jax.nn.softplus(dtb.astype(jnp.float32))
        decay = jnp.exp(dtb[..., None] * A[None, None].astype(jnp.float32)
                        ).astype(scan_dtype)
        inhom = ((dtb * xb.astype(jnp.float32))[..., None]
                 * bb[:, :, None, :].astype(jnp.float32)).astype(scan_dtype)
        dec_cum, h_in = jax.lax.associative_scan(assoc, (decay, inhom), axis=1)
        h_all = (dec_cum * h[:, None].astype(scan_dtype)
                 + h_in)                          # (Bt, L, Din, N) scan_dtype
        y = jnp.einsum("bldn,bln->bld", h_all, cb.astype(scan_dtype),
                       preferred_element_type=jnp.float32)
        y = y + xb.astype(jnp.float32) * D[None, None].astype(jnp.float32)
        return h_all[:, -1].astype(jnp.float32), y.astype(x.dtype)

    # remat per chunk: the backward recomputes the (Bt, L, Din, N)
    # intra-chunk states instead of stacking them as residuals
    h_fin, yc = jax.lax.scan(
        jax.checkpoint(chunk_step), h0,
        (jnp.moveaxis(xc, 1, 0), jnp.moveaxis(dtc, 1, 0),
         jnp.moveaxis(Bc, 1, 0), jnp.moveaxis(Cc, 1, 0)))
    y = jnp.moveaxis(yc, 0, 1).reshape(bt, n_chunks * chunk, din)[:, :s]
    return y, h_fin


def selective_scan_step(x: jax.Array, dt: jax.Array, A: jax.Array,
                        B: jax.Array, C: jax.Array, D: jax.Array,
                        h: jax.Array):
    """Single decode step.  x, dt: (Bt, Din); B, C: (Bt, N); h: (Bt, Din, N)."""
    dt = jax.nn.softplus(dt.astype(jnp.float32))
    decay = jnp.exp(dt[..., None] * A[None].astype(jnp.float32))
    h_new = decay * h + (dt * x.astype(jnp.float32))[..., None] * B[:, None, :]
    y = jnp.einsum("bdn,bn->bd", h_new, C.astype(jnp.float32))
    y = y + x.astype(jnp.float32) * D[None]
    return y.astype(x.dtype), h_new


# -- Mamba-2 (SSD) -------------------------------------------------------------------------

_HIGHEST = jax.lax.Precision.HIGHEST


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, D: jax.Array, h0: jax.Array | None = None,
             chunk: int = 256) -> tuple[jax.Array, jax.Array]:
    """Mamba-2's state-space scan over a whole sequence, chunked (SSD).

    x: (Bt, S, H, P); dt: (Bt, S, H), after its softplus; A, D: (H,);
    B, C: (Bt, S, G, N), head h reading group h // (H / G).  Per head

        h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T     (P, N)
        y_t = h_t C_t + D x_t

    Within a chunk of L positions y is the masked quadratic (attention-
    like) form; a ``lax.scan`` over the chunks carries the (Bt, H, P, N)
    state between them, so the peak temporary is (Bt, L, L, H), never
    (Bt, S, H, P, N).  float32 throughout, matmuls at full precision.
    Positions past S are padded with dt = 0, which leaves the state as
    it is.  Returns (y (Bt, S, H, P) float32, final state).
    """
    f32 = jnp.float32
    bt, s, nh, p = x.shape
    g, n = B.shape[2], B.shape[3]
    r = nh // g
    L = min(chunk, s)
    nc = -(-s // L)
    pad = nc * L - s

    def chunks(t):
        t = t.astype(f32)
        if pad:
            t = jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        return jnp.moveaxis(t.reshape(bt, nc, L, *t.shape[2:]), 1, 0)

    A = A.astype(f32)
    causal = jnp.tril(jnp.ones((L, L), bool))

    def step(h, blk):
        xb, dtb, bb, cb = blk
        acs = jnp.cumsum(dtb * A, axis=1)                         # (Bt, L, H)
        seg = acs[:, :, None, :] - acs[:, None, :, :]             # (Bt, i, j, H)
        decay = jnp.exp(jnp.where(causal[None, :, :, None], seg, -jnp.inf))
        xdt = (xb * dtb[..., None]).reshape(bt, L, g, r, p)
        # two operands at a time, so that no (i, j, p) product is made
        cbt = jnp.einsum("bign,bjgn->bijg", cb, bb, precision=_HIGHEST)
        mix = cbt[..., None] * decay.reshape(bt, L, L, g, r)     # (Bt, i, j, G, R)
        y = jnp.einsum("bijgr,bjgrp->bigrp", mix, xdt, precision=_HIGHEST)
        hg = h.reshape(bt, g, r, p, n)
        y = y + jnp.einsum("bign,bgrpn->bigrp", cb, hg, precision=_HIGHEST) \
            * jnp.exp(acs).reshape(bt, L, g, r)[..., None]
        to_end = jnp.exp(acs[:, -1:, :] - acs).reshape(bt, L, g, r)
        h = (jnp.exp(acs[:, -1])[..., None, None] * h
             + jnp.einsum("bjgn,bjgrp->bgrpn", bb, to_end[..., None] * xdt,
                          precision=_HIGHEST).reshape(bt, nh, p, n))
        y = y.reshape(bt, L, nh, p) + D.astype(f32)[:, None] * xb
        return h, y

    if h0 is None:
        h0 = jnp.zeros((bt, nh, p, n), f32)
    # remat per chunk: the backward recomputes the (L, L) decays
    h, ys = jax.lax.scan(jax.checkpoint(step), h0.astype(f32),
                         (chunks(x), chunks(dt), chunks(B), chunks(C)))
    y = jnp.moveaxis(ys, 0, 1).reshape(bt, nc * L, nh, p)[:, :s]
    return y, h


def ssd_step(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, D: jax.Array,
             h: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One position of :func:`ssd_scan`: x: (Bt, H, P); dt: (Bt, H);
    B, C: (Bt, G, N); h: (Bt, H, P, N) float32.  Returns (y float32, h)."""
    f32 = jnp.float32
    nh, g = x.shape[1], B.shape[1]
    Bh = jnp.repeat(B.astype(f32), nh // g, axis=1)               # (Bt, H, N)
    Ch = jnp.repeat(C.astype(f32), nh // g, axis=1)
    xf = x.astype(f32)
    dtf = dt.astype(f32)
    decay = jnp.exp(dtf * A.astype(f32))[..., None, None]
    h = decay * h + (dtf[..., None] * xf)[..., None] * Bh[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", h, Ch, precision=_HIGHEST)
    return y + D.astype(f32)[:, None] * xf, h


def mamba2_mixer(p: dict, h: jax.Array, *, headdim: int, n_groups: int,
                 state_dim: int, chunk: int, eps: float,
                 conv0: jax.Array | None = None,
                 ssm0: jax.Array | None = None):
    """Mamba-2 mixer (granite-4.0-h's mamba layer).

    h: (Bt, S, D), already normed.  ``in_proj`` gives z, then x||B||C,
    then dt (one per head); x||B||C goes through the causal depthwise
    conv (``conv_w``, ``conv_b``) and silu; dt = softplus(dt + dt_bias);
    A = -exp(A_log); the scan's y goes through the gated norm,
    RMSNorm(y * silu(z)), and ``out_proj``.  ``conv0`` (Bt, K-1, C) and
    ``ssm0`` (Bt, H, P, N) carry state in; one position with a state in
    (a decode step) is one step of the recurrence, anything else the
    chunked scan.  Returns (out, conv state, ssm state).
    """
    f32 = jnp.float32
    bt, s, _ = h.shape
    din = p["out_proj"].shape[0]
    nh, gn = din // headdim, n_groups * state_dim
    z, xbc, dt = jnp.split(h @ p["in_proj"], [din, 2 * din + 2 * gn], axis=-1)
    xbc, conv = causal_conv1d(xbc, p["conv_w"], conv0)
    if "conv_b" in p:
        xbc = xbc + p["conv_b"]
    x, B, C = jnp.split(jax.nn.silu(xbc), [din, din + gn], axis=-1)
    x = x.reshape(bt, s, nh, headdim)
    B = B.reshape(bt, s, n_groups, state_dim)
    C = C.reshape(bt, s, n_groups, state_dim)
    dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32))
    A = -jnp.exp(p["A_log"].astype(f32))
    if s == 1 and ssm0 is not None:
        y, ssm = ssd_step(x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], p["D"], ssm0)
    else:
        y, ssm = ssd_scan(x, dt, A, B, C, p["D"], ssm0, chunk=chunk)
    y = rms_norm(y.reshape(bt, s, din) * jax.nn.silu(z.astype(f32)),
                 p["norm"], eps).astype(h.dtype)
    return y @ p["out_proj"], conv, ssm
