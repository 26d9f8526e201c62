"""Plain float32 reference of a pre-norm dense decoder LM.

Written from the published equations, in straightforward ``jax.numpy``;
it imports nothing of the program.  It covers two families of dense
decoders (the benchmark serves ChatGLM3; the tests also hold the
program's Qwen2 path, its loss and its AdamW update to it):

* Qwen2 (arXiv:2407.10671): RMSNorm, grouped-query attention with bias
  on q/k/v, rotary position embedding on every head dimension, SwiGLU
  MLP, tied input/output embedding.
* ChatGLM3 (THUDM/chatglm3-6b config.json, arXiv:2406.12793): the same
  block with rotary embedding on the first half of each head only
  ("2d RoPE", ``rope_style = "half"``) and an untied output layer.

Equations per layer, with x the residual stream:

    h = RMSNorm(x) * g1
    q, k, v = h Wq + bq, h Wk + bk, h Wv + bv     (heads of size hd)
    q, k = rope(q), rope(k)          pairs (2i, 2i+1) of the rotated dims
    a = softmax(q k^T / sqrt(hd) + causal mask) v   (query head j reads
                                                     kv head j // (Hq/Hkv))
    x = x + a Wo
    h = RMSNorm(x) * g2
    x = x + (silu(h Wg) * (h Wu)) Wd

then logits = RMSNorm(x) * g_out @ head over the real vocabulary.
Rotary pairs are adjacent dims (2i, 2i+1): the layout in which ChatGLM
publishes its weights; Qwen2's published checkpoint pairs (i, i + hd/2),
which is the same function once wq/wk columns are permuted, so with
weights drawn at random the two are one model.

``prec`` selects how each weight matmul is computed: ``"f32"`` (float32
operands, ``highest`` precision) or ``"fp8"`` (both operands rounded to
float8 e4m3 with a per-tensor scale, float32 accumulation), the control
that has to come out as not correct.

Beside the equations it gives what the harness needs of the
architecture: the parameter tree and how each leaf is drawn
(``param_tree``, ``stacked_groups``), and the operations and least
bytes of decoding (``decode_token_flops``, ``decode_step_bytes``).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import Leaf

F32 = jnp.float32
E4M3_MAX = 448.0


# -- parameter tree -------------------------------------------------------------


def _hd(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def param_tree(m: dict, padded_vocab: int) -> dict:
    """The program's tree (``emb``, ``out_norm``, ``lm_head``,
    ``blocks/{ln1,wq,wk,wv,wo,bq,bk,bv,ln2,wg,wu,wd}``): matrices
    N(0, 0.02), output projections N(0, 0.02/sqrt(2L)), biases
    N(0, 0.02) and norm gains 1 + N(0, 0.05), so that every term of the
    equations is exercised."""
    d, L = m["d_model"], m["n_layers"]
    hd = _hd(m)
    hq, hkv, ff = m["n_heads"] * hd, m["n_kv_heads"] * hd, m["d_ff"]
    gain = partial(Leaf, scale=0.05, shift=1.0)
    tree = {"emb": Leaf((padded_vocab, d)), "out_norm": gain((d,))}
    if not m["tie_embeddings"]:
        tree["lm_head"] = Leaf((d, padded_vocab))
    blocks = {"ln1": gain((L, d)), "wq": Leaf((L, d, hq)),
              "wk": Leaf((L, d, hkv)), "wv": Leaf((L, d, hkv)),
              "wo": Leaf((L, hq, d), 0.02 / math.sqrt(2 * L))}
    if m["qkv_bias"]:
        blocks.update(bq=Leaf((L, hq)), bk=Leaf((L, hkv)), bv=Leaf((L, hkv)))
    blocks.update(ln2=gain((L, d)), wg=Leaf((L, d, ff)), wu=Leaf((L, d, ff)),
                  wd=Leaf((L, ff, d)))
    tree["blocks"] = blocks
    return tree


def stacked_groups(m: dict) -> dict:
    """Groups whose leaves carry a leading per-layer axis, and its count."""
    return {"blocks": m["n_layers"]}


# -- operations and bytes of decoding (see bench/flops.py) -----------------------


def matmul_params(m: dict) -> int:
    """Weights that each token multiplies through: every layer's q, k, v,
    o and MLP matrices, and the output head over the real vocabulary
    (the embedding lookup multiplies nothing)."""
    d, hd = m["d_model"], _hd(m)
    per_layer = (d * m["n_heads"] * hd + 2 * d * m["n_kv_heads"] * hd
                 + m["n_heads"] * hd * d + 3 * d * m["d_ff"])
    return m["n_layers"] * per_layer + d * m["vocab"]


def decode_token_flops(m: dict, context: int) -> int:
    """One decoded token that attends to ``context`` cached positions
    (its own included)."""
    return (2 * matmul_params(m)
            + m["n_layers"] * m["n_heads"] * 4 * _hd(m) * context)


def kv_bytes_per_token(m: dict, kv_itemsize: int) -> int:
    return m["n_layers"] * 2 * m["n_kv_heads"] * _hd(m) * kv_itemsize


def decode_step_bytes(m: dict, contexts: list[int], w_itemsize: int,
                      kv_itemsize: int) -> int:
    """Least HBM traffic of one decode step over the active slots: every
    matmul weight once, the embedding rows of the step's tokens, and each
    slot's cached keys and values up to its context."""
    n = len(contexts)
    weights = matmul_params(m) * w_itemsize
    emb_rows = n * m["d_model"] * w_itemsize
    return weights + emb_rows + kv_bytes_per_token(m, kv_itemsize) * sum(contexts)


# -- equations ------------------------------------------------------------------


def _fp8(x):
    """x rounded to float8 e4m3 under a per-tensor scale.  To autodiff
    the rounding is the identity, as in fp8 training (a plain cast would
    also round the cotangent to float8 on the way back)."""
    amax = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30))
    s = amax / E4M3_MAX
    q = (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return x + jax.lax.stop_gradient(q - x)


def mm(a, b, prec: str):
    a, b = a.astype(F32), b.astype(F32)
    if prec == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, g, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, positions, theta, style):
    """x: (B, S, H, hd); rotates the first hd (full) or hd/2 (half)
    dims in adjacent pairs."""
    hd = x.shape[-1]
    rot = hd if style == "full" else hd // 2
    inv = 1.0 / theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = positions[:, None].astype(F32) * jnp.asarray(inv, F32)[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    xr = x[..., :rot]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return jnp.concatenate([out.reshape(xr.shape), x[..., rot:]], -1)


def _attn_block(q, k, v, q0):
    """Causal attention of the query rows [q0, q0 + qb) against keys
    [0, q0 + qb).  q: (B, qb, Hq, hd); k, v: (B, T, Hq, hd)."""
    qb, hd = q.shape[1], q.shape[-1]
    t = q0 + qb
    k, v = k[:, :t], v[:, :t]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
    mask = (q0 + jnp.arange(qb))[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                      precision=jax.lax.Precision.HIGHEST)


def attention(q, k, v, q_block: int, remat: bool):
    """Causal GQA attention, computed in blocks of query rows."""
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = q.shape[1]
    qb = min(q_block, s)
    blk = jax.checkpoint(_attn_block, static_argnums=(3,)) if remat \
        else _attn_block
    outs = [blk(q[:, i:i + qb], k, v, i) for i in range(0, s, qb)]
    return jnp.concatenate(outs, axis=1)


def layer(x, w: dict, positions, m: dict, prec: str, q_block: int = 1024,
          remat: bool = False):
    b, s, d = x.shape
    hd = m.get("head_dim") or d // m["n_heads"]
    eps = m["norm_eps"]
    h = rms_norm(x, w["ln1"], eps)
    q, k, v = mm(h, w["wq"], prec), mm(h, w["wk"], prec), mm(h, w["wv"], prec)
    if "bq" in w:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = q.reshape(b, s, m["n_heads"], hd)
    k = k.reshape(b, s, m["n_kv_heads"], hd)
    v = v.reshape(b, s, m["n_kv_heads"], hd)
    q = rope(q, positions, m["rope_theta"], m["rope_style"])
    k = rope(k, positions, m["rope_theta"], m["rope_style"])
    a = attention(q, k, v, q_block, remat).reshape(b, s, -1)
    x = x + mm(a, w["wo"], prec)
    h = rms_norm(x, w["ln2"], eps)
    u = jax.nn.silu(mm(h, w["wg"], prec)) * mm(h, w["wu"], prec)
    return x + mm(u, w["wd"], prec)


def head_matrix(params, m: dict):
    """(d, V) output matrix over the real vocabulary."""
    v = m["vocab"]
    if m["tie_embeddings"]:
        return params["emb"][:v].T
    return params["lm_head"][:, :v]


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(F32), tree)


# -- training -------------------------------------------------------------------


def loss_fn(params, tokens, labels, m: dict, prec: str, chunk: int = 512):
    """Mean next-token cross-entropy over every position of the batch."""
    b, s = tokens.shape
    positions = jnp.arange(s)
    x = params["emb"][tokens].astype(F32)

    def body(x, w):
        return layer(x, w, positions, m, prec, remat=True), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["blocks"])
    x = rms_norm(x, params["out_norm"], m["norm_eps"])
    head = head_matrix(params, m)

    @jax.checkpoint
    def chunk_ce(xc, tc):
        logits = mm(xc, head, prec)
        lse = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, tc[..., None], -1)[..., 0]
        return jnp.sum(lse - gold)

    c = min(chunk, s)
    tot = sum(chunk_ce(x[:, i:i + c], labels[:, i:i + c])
              for i in range(0, s, c))
    return tot / (b * s)


def adamw_step(params, mom, vel, grads, step, opt: dict):
    """Clip by global norm, then AdamW (decoupled decay on every leaf)
    with linear warm-up and cosine decay of the learning rate."""
    leaves = jax.tree.leaves(grads)
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    grads = jax.tree.map(
        lambda g: g * jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gn, 1e-9)),
        grads)
    t = step.astype(F32)
    warm, total, base = opt["warmup_steps"], opt["total_steps"], opt["lr"]
    prog = jnp.clip((t - warm) / max(total - warm, 1), 0.0, 1.0)
    lr = jnp.where(t < warm, base * t / max(warm, 1),
                   0.5 * base * (1.0 + jnp.cos(jnp.pi * prog)))
    b1, b2 = opt["b1"], opt["b2"]
    mom = jax.tree.map(lambda mo, g: b1 * mo + (1 - b1) * g, mom, grads)
    vel = jax.tree.map(lambda ve, g: b2 * ve + (1 - b2) * g * g, vel, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, mo, ve: p - lr * ((mo / c1) / (jnp.sqrt(ve / c2) + opt["eps"])
                                    + opt["weight_decay"] * p),
        params, mom, vel)
    return params, mom, vel


# -- serving --------------------------------------------------------------------


def served_hidden(params, seqs, m: dict, prec: str = "f32",
                  q_block: int = 512):
    """Final normed hidden states (B, T, d) of ``seqs`` (B, T), computed
    one layer at a time from the served weights."""
    s = seqs.shape[1]
    positions = jnp.arange(s)
    lay = jax.jit(partial(layer, m=m, prec=prec, q_block=q_block))
    x = params["emb"][jnp.asarray(seqs)].astype(F32)
    for i in range(m["n_layers"]):
        w = {k: v[i] for k, v in params["blocks"].items()}
        x = lay(x, _f32(w), positions)
    return jax.jit(lambda x, g: rms_norm(x, g, m["norm_eps"]))(
        x, params["out_norm"].astype(F32))


def head_logits(params, x, m: dict, prec: str = "f32", chunk: int = 8192):
    """Logits over the real vocabulary of hidden states x (..., d), the
    head taken to float32 a block of vocabulary rows at a time."""
    head = head_matrix(params, m)
    scale = None
    if prec == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(head.astype(F32))), 1e-30) / E4M3_MAX
        x = _fp8(x)

    @jax.jit
    def block(x, h):
        h = h.astype(F32)
        if scale is not None:
            h = (h / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
        return jnp.matmul(x, h, precision=jax.lax.Precision.HIGHEST)

    v = head.shape[1]
    return jnp.concatenate([block(x, head[:, i:i + chunk])
                            for i in range(0, v, chunk)], axis=-1)
