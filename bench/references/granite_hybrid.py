"""Plain float32 reference of granite-4.0-h (GraniteMoeHybrid): Mamba-2
and NoPE attention layers, each followed by a mixture of experts and a
shared MLP.

Written term by term after ``transformers``' ``modeling_granitemoehybrid``
(``GraniteMoeHybridForCausalLM``), in straightforward ``jax.numpy``; it
imports nothing of the program.  With x the residual stream, r the
residual multiplier and every norm an RMSNorm with its gain:

    x = embedding_multiplier * emb[tokens]
    per layer (``layer_types``):
      x = x + r * mixer(norm1(x))
      h = norm2(x)
      x = x + r * (experts(h) + shared(h))
    logits = norm_out(x) @ emb^T / logits_scaling     (tied embedding)

Mamba-2 mixer (GraniteMoeHybridMambaLayer.torch_forward), H heads of P
channels, G groups of state N, din = H P:

    z | xBC | dt = h W_in                  (din, din + 2 G N, H)
    xBC = silu(causal depthwise conv_K(xBC) + conv_b)
    x | B | C = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        (per head, P x N)
    y_t = S_t C_t + D x_t            (head h reads group h // (H / G))
    mixer = RMSNorm(y * silu(z)) W_out              (the gated norm)

The recurrence is a sequential ``lax.scan`` over positions, one step a
position, so that it is independent of the program's chunked scan.

Attention mixer: grouped-query attention without positions (NoPE), the
scores scaled by ``attention_multiplier`` (not 1/sqrt(hd)), causal.

Experts (GraniteMoeHybridMoE): the router's E logits, the top k, a
softmax over those k; each expert a SwiGLU, ``silu(h W_g) * (h W_u)
W_d``, the published fused ``input_linear`` split into its gate half
``W_g`` and its up half ``W_u``.  This chip holds experts ``first_held``
to ``first_held + n_held - 1`` of E: every held expert is computed on
every token and weighted by the token's gate there, zero where the token
did not choose it, and the absent experts' part is left out (the share a
deployment's chip computes).  ``shared`` is a SwiGLU of width
``shared_d_ff``, counted once.

Departures from the published code: none in the equations.  The
program's precision is the configuration's; here every weight matmul is
float32 at ``highest`` precision (``"f32"``), or both operands rounded to
float8 e4m3 (``"fp8"``), the control that has to come out not correct.

Beside the equations: the parameter tree and how each leaf is drawn
(``param_tree``, ``stacked_groups``), and the operations and least bytes
of a decode step (``decode_token_flops``, ``decode_step_bytes``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench.references.dense_decoder import _f32, head_logits as _head, mm, rms_norm
from bench.weights import Leaf

F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST

#: bytes of an element of the SSM state, which is float32
SSM_ITEMSIZE = 4


# -- sizes ----------------------------------------------------------------------


def _hd(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def _mamba(m: dict) -> tuple[int, int, int, int, int, int]:
    """(din, heads, head dim, groups, state, conv channels)."""
    s = m["ssm"]
    din = s["expand"] * m["d_model"]
    return (din, din // s["headdim"], s["headdim"], s["n_groups"],
            s["state_dim"], din + 2 * s["n_groups"] * s["state_dim"])


def _held(m: dict) -> int:
    moe = m["moe"]
    return moe.get("n_held") or moe["n_experts"]


def _count(m: dict, kind: str) -> int:
    return list(m["layer_types"]).count(kind)


# -- parameter tree -------------------------------------------------------------


def param_tree(m: dict, padded_vocab: int) -> dict:
    """The program's tree: ``emb``, ``out_norm``, and one stacked group per
    layer kind, ``mamba`` and ``attention``, each with its mixer's leaves
    and ``ln2``, ``router``, the held experts ``wg``/``wu`` (Eh, d, F) and
    ``wd``, and ``shared_wg``/``shared_wu``/``shared_wd``.  Matrices
    N(0, 0.02) but ``emb``, N(0, 0.002) (below); gains 1 + N(0, 0.05);
    ``conv_w`` N(0, 0.3), ``conv_b`` N(0, 0.1); ``A_log`` N(0.5, 0.5) (A
    from about -0.6 to -4.5); ``dt_bias`` N(-3, 0.5) (dt about 0.01 to
    0.2); ``D`` N(1, 0.1).

    The tied embedding is drawn ten times smaller than the matrices: the
    head is the embedding and the input is 12 times it, so at N(0, 0.02)
    the final state keeps so much of the input token that its own logit
    leads every other by many standard deviations, and the model
    echoes its input whatever the precision (every served token, and
    every token of the fp8 control, is the reference's first).  At
    N(0, 0.002) the layers decide the token."""
    d, moe = m["d_model"], m["moe"]
    din, nh, _, _, _, conv_ch = _mamba(m)
    hd, eh, f, fs = _hd(m), _held(m), moe["expert_d_ff"], m["shared_d_ff"]
    gain = partial(Leaf, scale=0.05, shift=1.0)

    def mlp(n):
        return {"ln2": gain((n, d)), "router": Leaf((n, d, moe["n_experts"])),
                "wg": Leaf((n, eh, d, f)), "wu": Leaf((n, eh, d, f)),
                "wd": Leaf((n, eh, f, d)), "shared_wg": Leaf((n, d, fs)),
                "shared_wu": Leaf((n, d, fs)), "shared_wd": Leaf((n, fs, d))}

    tree = {"emb": Leaf((padded_vocab, d), 0.002), "out_norm": gain((d,))}
    n = _count(m, "mamba")
    if n:
        k = m["ssm"]["conv_dim"]
        tree["mamba"] = {
            "ln1": gain((n, d)), "in_proj": Leaf((n, d, din + conv_ch + nh)),
            "conv_w": Leaf((n, k, conv_ch), 0.3),
            **({"conv_b": Leaf((n, conv_ch), 0.1)} if m["ssm"]["conv_bias"] else {}),
            "dt_bias": Leaf((n, nh), 0.5, -3.0), "A_log": Leaf((n, nh), 0.5, 0.5),
            "D": Leaf((n, nh), 0.1, 1.0), "norm": gain((n, din)),
            "out_proj": Leaf((n, din, d)), **mlp(n)}
    n = _count(m, "attention")
    if n:
        hq, hkv = m["n_heads"] * hd, m["n_kv_heads"] * hd
        tree["attention"] = {
            "ln1": gain((n, d)), "wq": Leaf((n, d, hq)), "wk": Leaf((n, d, hkv)),
            "wv": Leaf((n, d, hkv)), "wo": Leaf((n, hq, d)), **mlp(n)}
    return tree


def stacked_groups(m: dict) -> dict:
    """Groups whose leaves carry a leading per-layer axis, and its count."""
    return {k: _count(m, k) for k in ("mamba", "attention") if _count(m, k)}


# -- operations and bytes of decoding (see bench/flops.py) -----------------------


def expected_experts(m: dict, n: int) -> float:
    """Held experts that a decode step of ``n`` active slots touches, in
    expectation under uniform top-k routing: each held expert is missed by
    one token with probability 1 - k/E, so touched by some token of the
    step with 1 - (1 - k/E)^n.  The counts see only the slots' contexts,
    not the step's routing, so this is the rule they use."""
    moe = m["moe"]
    return _held(m) * (1.0 - (1.0 - moe["top_k"] / moe["n_experts"]) ** n)


def _matmul_weights(m: dict, kind: str) -> int:
    """The matrices of one layer that a token multiplies through, but its
    experts: the mixer's, the router and the shared MLP."""
    d = m["d_model"]
    if kind == "mamba":
        din, nh, _, _, _, conv_ch = _mamba(m)
        mixer = d * (din + conv_ch + nh) + din * d
    else:
        hd = _hd(m)
        mixer = 2 * d * m["n_heads"] * hd + 2 * d * m["n_kv_heads"] * hd
    return mixer + d * m["moe"]["n_experts"] + 3 * d * m["shared_d_ff"]


def _layer_weights(m: dict, kind: str) -> int:
    """Every weight of one layer but its experts: its matrices, and the
    conv, the per-head vectors and the norms' gains."""
    d = m["d_model"]
    small = 2 * d
    if kind == "mamba":
        din, nh, _, _, _, conv_ch = _mamba(m)
        small += (conv_ch * (m["ssm"]["conv_dim"] + m["ssm"]["conv_bias"])
                  + 3 * nh + din)
    return _matmul_weights(m, kind) + small


def _expert_weights(m: dict) -> int:
    return 3 * m["d_model"] * m["moe"]["expert_d_ff"]


def decode_token_flops(m: dict, context: int) -> float:
    """One decoded token that attends to ``context`` cached positions:
    its matmuls (mixers, router, shared MLP, the head over the real
    vocabulary, and the held experts it is routed to, k * Eh / E in
    expectation), each mamba layer's state update (dA * S + dt x B^T, 3
    operations an element) and read-out (S C, 2), and attention over the
    context.  The conv and other element-wise work is left out."""
    moe = m["moe"]
    _, nh, p, _, n, _ = _mamba(m)
    routed = moe["top_k"] * _held(m) / moe["n_experts"] * _expert_weights(m)
    mat = m["d_model"] * m["vocab"] + sum(
        _matmul_weights(m, k) + routed for k in m["layer_types"])
    return (2 * mat + _count(m, "mamba") * 5 * nh * p * n
            + _count(m, "attention") * m["n_heads"] * 4 * _hd(m) * context)


def decode_step_bytes(m: dict, contexts: list[int], w_itemsize: int,
                      kv_itemsize: int) -> float:
    """Least HBM traffic of one decode step over the active slots: every
    weight but the experts once (the tied embedding as the head, over the
    real vocabulary), the embedding rows of the step's tokens, the held
    experts the step touches (``expected_experts``), each slot's float32
    SSM state and its conv state read and written, and each slot's keys
    and values of the attention layers up to its context."""
    n = len(contexts)
    d = m["d_model"]
    din, nh, p, _, ns, conv_ch = _mamba(m)
    weights = (d * m["vocab"] + d
               + sum(_layer_weights(m, k) for k in m["layer_types"]))
    experts = (len(m["layer_types"]) * expected_experts(m, n)
               * _expert_weights(m))
    n_m = _count(m, "mamba")
    state = n * n_m * 2 * (nh * p * ns * SSM_ITEMSIZE
                           + (m["ssm"]["conv_dim"] - 1) * conv_ch * kv_itemsize)
    kv = _count(m, "attention") * 2 * m["n_kv_heads"] * _hd(m) * kv_itemsize
    return ((weights + experts + n * d) * w_itemsize + state
            + kv * sum(contexts))


# -- equations ------------------------------------------------------------------


def gated_norm(y, z, g, eps):
    """GraniteMoeHybridRMSNormGated: RMSNorm of y * silu(z), then the gain."""
    return rms_norm(y * jax.nn.silu(z), g, eps)


def ssm_recurrence(x, dt, A, B, C, D, S0=None):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T; y_t = S_t C_t + D x_t,
    one position a step.  x: (b, s, H, P); dt: (b, s, H); B, C:
    (b, s, G, N).  Returns (y (b, s, H, P), final state (b, H, P, N))."""
    b, _, nh, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = nh // g

    def step(S, t):
        xt, dtt, bt, ct = t
        bh = jnp.repeat(bt, rep, axis=1)
        ch = jnp.repeat(ct, rep, axis=1)
        S = (jnp.exp(dtt * A)[..., None, None] * S
             + (dtt[..., None] * xt)[..., None] * bh[:, :, None, :])
        y = jnp.einsum("bhpn,bhn->bhp", S, ch, precision=_HIGHEST)
        return S, y + D[:, None] * xt

    S0 = jnp.zeros((b, nh, p, n), F32) if S0 is None else S0
    seq = [jnp.moveaxis(t, 1, 0) for t in (x, dt, B, C)]
    S, y = jax.lax.scan(step, S0, seq)
    return jnp.moveaxis(y, 0, 1), S


def mamba_mixer(h, w: dict, m: dict, prec: str):
    b, s, _ = h.shape
    din, nh, p, g, n, conv_ch = _mamba(m)
    k = m["ssm"]["conv_dim"]
    zxbcdt = mm(h, w["in_proj"], prec)
    z, xbc, dt = zxbcdt[..., :din], zxbcdt[..., din:din + conv_ch], \
        zxbcdt[..., din + conv_ch:]
    # nn.Conv1d(groups=channels, padding=K-1), its first s outputs
    xp = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = sum(xp[:, i:i + s] * w["conv_w"][i] for i in range(k)) + w.get("conv_b", 0.0)
    xbc = jax.nn.silu(xbc)
    x = xbc[..., :din].reshape(b, s, nh, p)
    B = xbc[..., din:din + g * n].reshape(b, s, g, n)
    C = xbc[..., din + g * n:].reshape(b, s, g, n)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    y, _ = ssm_recurrence(x, dt, -jnp.exp(w["A_log"]), B, C, w["D"])
    y = gated_norm(y.reshape(b, s, din), z, w["norm"], m["norm_eps"])
    return mm(y, w["out_proj"], prec)


def _attn_block(q, k, v, q0, scale):
    """Causal attention of query rows [q0, q0 + qb) against keys
    [0, q0 + qb).  q: (b, qb, Hq, hd); k, v: (b, T, Hq, hd)."""
    qb = q.shape[1]
    t = q0 + qb
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k[:, :t], precision=_HIGHEST) * scale
    mask = (q0 + jnp.arange(qb))[:, None] >= jnp.arange(t)[None, :]
    pr = jax.nn.softmax(jnp.where(mask[None, None], sc, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", pr, v[:, :t], precision=_HIGHEST)


def attention_mixer(h, w: dict, m: dict, prec: str, q_block: int = 512):
    """GraniteMoeHybridAttention without positions: q, k, v, GQA (query
    head j reads KV head j // (Hq / Hkv)), scores * attention_multiplier,
    causal softmax, o_proj; computed in blocks of query rows."""
    b, s, _ = h.shape
    hd = _hd(m)
    q = mm(h, w["wq"], prec).reshape(b, s, m["n_heads"], hd)
    k = mm(h, w["wk"], prec).reshape(b, s, m["n_kv_heads"], hd)
    v = mm(h, w["wv"], prec).reshape(b, s, m["n_kv_heads"], hd)
    rep = m["n_heads"] // m["n_kv_heads"]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    qb = min(q_block, s)
    o = jnp.concatenate(
        [_attn_block(q[:, i:i + qb], k, v, i, m["attention_multiplier"])
         for i in range(0, s, qb)], axis=1)
    return mm(o.reshape(b, s, -1), w["wo"], prec)


def experts(h, w: dict, m: dict, prec: str):
    """The held experts' part of GraniteMoeHybridMoE: every held expert on
    every token, times the token's gate there (zero where not chosen)."""
    moe = m["moe"]
    logits = mm(h, w["router"], prec)
    top_v, top_i = jax.lax.top_k(logits, moe["top_k"])
    gates = jax.nn.softmax(top_v, axis=-1)

    def one(out, e):
        wg, wu, wd, idx = e
        gate = jnp.sum(jnp.where(top_i == idx, gates, 0.0), axis=-1)
        u = jax.nn.silu(mm(h, wg, prec)) * mm(h, wu, prec)
        return out + gate[..., None] * mm(u, wd, prec), None

    first = moe.get("first_held", 0)
    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (w["wg"], w["wu"], w["wd"],
                           first + jnp.arange(w["wg"].shape[0])))
    return out


def shared_mlp(h, w: dict, prec: str):
    u = jax.nn.silu(mm(h, w["shared_wg"], prec)) * mm(h, w["shared_wu"], prec)
    return mm(u, w["shared_wd"], prec)


def layer(x, w: dict, kind: str, m: dict, prec: str):
    """GraniteMoeHybridDecoderLayer."""
    r, eps = m["residual_multiplier"], m["norm_eps"]
    h = rms_norm(x, w["ln1"], eps)
    mix = mamba_mixer if kind == "mamba" else attention_mixer
    x = x + r * mix(h, w, m, prec)
    h = rms_norm(x, w["ln2"], eps)
    return x + r * (experts(h, w, m, prec) + shared_mlp(h, w, prec))


# -- serving --------------------------------------------------------------------


def served_hidden(params, seqs, m: dict, prec: str = "f32"):
    """Final normed hidden states (B, T, d) of ``seqs`` (B, T), computed
    one layer at a time, in ``layer_types`` order, from the served
    weights."""
    lay = {k: jax.jit(partial(layer, kind=k, m=m, prec=prec))
           for k in stacked_groups(m)}
    x = params["emb"][jnp.asarray(seqs)].astype(F32) * m["embedding_multiplier"]
    seen = {k: 0 for k in lay}
    for kind in m["layer_types"]:
        i = seen[kind]
        seen[kind] = i + 1
        x = lay[kind](x, _f32({k: v[i] for k, v in params[kind].items()}))
    return jax.jit(lambda x, g: rms_norm(x, g, m["norm_eps"]))(
        x, params["out_norm"].astype(F32))


def head_logits(params, x, m: dict, prec: str = "f32", chunk: int = 8192):
    """Logits over the real vocabulary (the tied embedding as the head),
    divided by ``logits_scaling``."""
    return _head(params, x, m, prec, chunk) / m["logits_scaling"]

