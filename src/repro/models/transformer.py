"""Full model definitions for every assigned architecture family.

All stacks scan over layer-stacked parameters (``jax.lax.scan``) so HLO
size and compile time are O(1) in depth — required for the 100-layer
90 B and 64-layer 314 B dry-runs.  Families:

  dense | moe          decoder-only LM (GQA + RoPE [+ MoE MLP])
  ssm                  attention-free Mamba stack (falcon-mamba)
  hybrid               Mamba stack + one *shared* attention block
                       applied every k layers (zamba2)
  encdec               Whisper-style: non-causal encoder over stubbed
                       frame embeddings + causal decoder w/ cross-attn
  vlm                  Llama-3.2-Vision-style: cross-attention image
                       layers every k self-attention layers (stubbed
                       patch embeddings)

The dense and moe families, and a config with ``layer_types``
(granite-4.0-h-style), are a layer pattern that ``_walk`` runs: layers
of two kinds, Mamba-2 and attention, each followed by its second half
(``_mlp``); one stacked parameter group per kind, one ``lax.scan`` per
run of layers of one kind.  Every self-attention of every family is
``_attention``, and every cache entry is read with ``_take`` and written
with ``_put``.

The LM class exposes: init / abstract_params / forward / loss /
init_cache / prefill / decode_step.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from .layers import (
    apply_rope,
    blocked_attention,
    causal_conv1d,
    decode_attention,
    decode_attention_sharded,
    held_expert_mlp,
    mamba2_mixer,
    moe_mlp,
    rms_norm,
    selective_scan,
    selective_scan_step,
    swiglu,
)

MAX_LEARNED_POS = 32768  # learned-position table (whisper-style decoder)


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


def _pick(d: dict, names) -> dict:
    return {n: d[n] for n in names if n in d}


class _Init:
    """Tiny helper producing initialized leaves from one threaded rng."""

    def __init__(self, rng: jax.Array, dtype):
        self.rng = rng
        self.dtype = dtype

    def normal(self, shape, scale=0.02):
        self.rng, k = jax.random.split(self.rng)
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(self.dtype)

    def zeros(self, shape):
        return jnp.zeros(shape, self.dtype)

    def ones(self, shape):
        return jnp.ones(shape, self.dtype)

    def f32(self, value):
        return jnp.asarray(value, jnp.float32)


class LM:
    #: the stacked cache leaves of each kind of layer that ``_walk`` runs
    _KIND_STATE = {"mamba": ("conv", "ssm"), "attention": ("k", "v")}

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.pdt = _dtype(cfg.param_dtype)
        self.cdt = _dtype(cfg.compute_dtype)
        kinds = set(cfg.layer_types)
        if cfg.layer_types and (len(cfg.layer_types) != cfg.n_layers
                                or not kinds <= set(self._KIND_STATE)):
            raise ValueError(f"layer_types needs one of {sorted(self._KIND_STATE)}"
                             f" for each of the {cfg.n_layers} layers")
        if cfg.ssm is not None and (cfg.ssm.version == 2) != ("mamba" in kinds):
            raise ValueError("Mamba-2 (ssm.version 2) is the mixer of the "
                             "layer pattern's mamba layers, and only that")

    # ------------------------------------------------------------------ params

    def _attn_params(self, ini: _Init, lead: tuple[int, ...] = (),
                     cross: bool = False) -> dict:
        c = self.cfg
        hd = c.hd
        p = {
            "ln1": ini.ones(lead + (c.d_model,)),
            "wq": ini.normal(lead + (c.d_model, c.n_heads * hd)),
            "wk": ini.normal(lead + (c.d_model, c.n_kv_heads * hd)),
            "wv": ini.normal(lead + (c.d_model, c.n_kv_heads * hd)),
            "wo": ini.normal(lead + (c.n_heads * hd, c.d_model),
                             scale=0.02 / math.sqrt(2 * max(c.n_layers, 1))),
        }
        if c.qkv_bias and not cross:
            p["bq"] = ini.zeros(lead + (c.n_heads * hd,))
            p["bk"] = ini.zeros(lead + (c.n_kv_heads * hd,))
            p["bv"] = ini.zeros(lead + (c.n_kv_heads * hd,))
        return p

    def _mlp_params(self, ini: _Init, lead: tuple[int, ...] = ()) -> dict:
        """The second half of a layer (``_mlp``): ``ln2``, then under a
        layer pattern the router over all experts, the held experts
        (``wg``, ``wu``: (Eh, D, F); ``wd``) and the shared MLP; else the
        family's MoE or dense MLP."""
        c = self.cfg
        d = c.d_model
        p = {"ln2": ini.ones(lead + (d,))}
        if c.layer_types:
            if c.moe is not None:
                e, f = c.moe.held, c.moe.expert_d_ff
                p.update(router=ini.normal(lead + (d, c.moe.n_experts)),
                         wg=ini.normal(lead + (e, d, f)),
                         wu=ini.normal(lead + (e, d, f)),
                         wd=ini.normal(lead + (e, f, d)))
            if c.shared_d_ff:
                f = c.shared_d_ff
                p.update(shared_wg=ini.normal(lead + (d, f)),
                         shared_wu=ini.normal(lead + (d, f)),
                         shared_wd=ini.normal(lead + (f, d)))
            return p
        if c.moe is not None:
            e, f = c.moe.n_experts, c.moe.expert_d_ff
            p.update(router=ini.normal(lead + (d, e)),
                     wg=ini.normal(lead + (e, d, f)),
                     wu=ini.normal(lead + (e, d, f)),
                     wd=ini.normal(lead + (e, f, d)))
        elif c.mlp_type == "gelu":
            p.update(wu=ini.normal(lead + (d, c.d_ff)),
                     wd=ini.normal(lead + (c.d_ff, d)))
        else:
            p.update(wg=ini.normal(lead + (d, c.d_ff)),
                     wu=ini.normal(lead + (d, c.d_ff)),
                     wd=ini.normal(lead + (c.d_ff, d)))
        return p

    def _ssm_params(self, ini: _Init, lead: tuple[int, ...] = ()) -> dict:
        c = self.cfg
        s = c.ssm
        din = s.expand * c.d_model
        dt_rank = max(1, math.ceil(c.d_model / 16))
        a = np.tile(np.arange(1, s.state_dim + 1, dtype=np.float32),
                    (din, 1))
        a_log = np.log(a)
        for _ in lead:
            a_log = np.broadcast_to(a_log, lead + a_log.shape[-2:])
        return {
            "ln": ini.ones(lead + (c.d_model,)),
            "in_proj": ini.normal(lead + (c.d_model, 2 * din)),
            "conv_w": ini.normal(lead + (s.conv_dim, din), scale=0.1),
            "x_proj": ini.normal(lead + (din, dt_rank + 2 * s.state_dim)),
            "dt_proj": ini.normal(lead + (dt_rank, din), scale=0.1),
            "dt_bias": ini.zeros(lead + (din,)),
            "A_log": jnp.asarray(a_log, jnp.float32),
            "D": ini.f32(np.ones(lead + (din,), np.float32)),
            "out_proj": ini.normal(lead + (din, c.d_model)),
        }

    def _mamba2_params(self, ini: _Init, lead: tuple[int, ...]) -> dict:
        c = self.cfg
        s = c.ssm
        d = c.d_model
        din = s.expand * d
        nh = din // s.headdim
        conv_ch = din + 2 * s.n_groups * s.state_dim
        p = {"ln1": ini.ones(lead + (d,)),
             "in_proj": ini.normal(lead + (d, din + conv_ch + nh)),
             "conv_w": ini.normal(lead + (s.conv_dim, conv_ch), scale=0.1),
             "dt_bias": ini.zeros(lead + (nh,)),
             "A_log": jnp.broadcast_to(
                 jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)),
                 lead + (nh,)).astype(self.pdt),
             "D": ini.ones(lead + (nh,)),
             "norm": ini.ones(lead + (din,)),
             "out_proj": ini.normal(lead + (din, d))}
        if s.conv_bias:
            p["conv_b"] = ini.zeros(lead + (conv_ch,))
        return p

    def _layer_params(self, ini: _Init, kind: str, n: int) -> dict:
        """One kind's stacked group of ``n`` layers: its mixer (Mamba-2,
        or attention as in ``_attn_params``), then its second half."""
        mixer = (self._mamba2_params if kind == "mamba" else self._attn_params)
        return {**mixer(ini, (n,)), **self._mlp_params(ini, (n,))}

    def init(self, rng: jax.Array) -> dict:
        c = self.cfg
        ini = _Init(rng, self.pdt)
        p: dict = {
            "emb": ini.normal((c.padded_vocab, c.d_model)),
            "out_norm": ini.ones((c.d_model,)),
        }
        if not c.tie_embeddings:
            p["lm_head"] = ini.normal((c.d_model, c.padded_vocab))
        L = c.n_layers
        if self._layers():
            for kind, n in self._kinds().items():
                p[self._group(kind)] = self._layer_params(ini, kind, n)
        elif c.family in ("ssm", "hybrid"):
            p["blocks"] = self._ssm_params(ini, (L,))
            if c.family == "hybrid":
                p["shared_attn"] = {**self._attn_params(ini),
                                    **self._mlp_params(ini)}
        elif c.family == "encdec":
            p["pos_enc"] = ini.normal((c.enc_seq, c.d_model))
            p["pos_dec"] = ini.normal((MAX_LEARNED_POS, c.d_model))
            p["enc_blocks"] = {**self._attn_params(ini, (c.enc_layers,)),
                               **self._mlp_params(ini, (c.enc_layers,))}
            p["dec_blocks"] = {**self._attn_params(ini, (L,)),
                               **self._mlp_params(ini, (L,))}
            cross = self._attn_params(ini, (L,), cross=True)
            p["dec_cross"] = {("ln_x" if k == "ln1" else k): v
                              for k, v in cross.items()}
        elif c.family == "vlm":
            k = c.cross_attn_every
            units = L // k
            p["blocks"] = {**self._attn_params(ini, (units, k - 1)),
                           **self._mlp_params(ini, (units, k - 1))}
            cross = self._attn_params(ini, (units,), cross=True)
            p["cross_blocks"] = {
                **{("ln_x" if kk == "ln1" else kk): v for kk, v in cross.items()},
                **self._mlp_params(ini, (units,)),
            }
        else:
            raise ValueError(c.family)
        return p

    def abstract_params(self) -> dict:
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    # ------------------------------------------------------------------ cache entries

    @staticmethod
    def _take(stack: jax.Array, at: tuple) -> jax.Array:
        """The entry of a stacked cache leaf at the leading indices ``at``
        (a layer; a unit and a layer of the vision stack)."""
        n = len(at)
        return jax.lax.dynamic_slice(
            stack, at + (0,) * (stack.ndim - n),
            (1,) * n + stack.shape[n:]).reshape(stack.shape[n:])

    @staticmethod
    def _put(stack: jax.Array, new: jax.Array, at: tuple) -> jax.Array:
        """``new`` written into a stacked cache leaf from the indices
        ``at``, the leading ones then, for K/V, (batch, position); the
        rest from 0.  A whole entry, or K/V rows from a position."""
        new = new.reshape((1,) * (stack.ndim - new.ndim) + new.shape)
        return jax.lax.dynamic_update_slice(
            stack, new.astype(stack.dtype), at + (0,) * (stack.ndim - len(at)))

    # ------------------------------------------------------------------ mixers and the second half

    def _qkv(self, bp: dict, h: jax.Array, positions):
        c = self.cfg
        hd = c.hd
        b, s, _ = h.shape
        q = h @ bp["wq"]
        k = h @ bp["wk"]
        v = h @ bp["wv"]
        if "bq" in bp:
            q, k, v = q + bp["bq"], k + bp["bk"], v + bp["bv"]
        q = q.reshape(b, s, c.n_heads, hd)
        k = k.reshape(b, s, c.n_kv_heads, hd)
        v = v.reshape(b, s, c.n_kv_heads, hd)
        if c.rope_style != "none":
            q = apply_rope(q, positions, c.rope_theta, c.rope_style)
            k = apply_rope(k, positions, c.rope_theta, c.rope_style)
        return q, k, v

    def _attention(self, bp: dict, h: jax.Array, cache: dict, at: tuple,
                   positions, length, causal: bool = True):
        """Self-attention of the normed ``h``, scores scaled by
        ``attention_multiplier`` (0: 1/sqrt(hd)).  ``length`` None: over
        the whole sequence, its K/V written from position 0 into the
        entries at ``at`` of the stacked ``k``, ``v`` that ``cache``
        holds ({} in training).  Else one position decoded at
        ``length``: attended with the entries below ``length`` as stored
        and its own K/V beside them, then its K/V row written at
        ``length``; a sequence-sharded cache (``sharded_decode``) goes
        back whole, each shard its own part.  Returns (y, cache)."""
        c = self.cfg
        b, s, _ = h.shape
        q, k, v = self._qkv(bp, h, positions)
        scale = c.attention_multiplier
        if length is None:
            o = blocked_attention(q, k, v, causal=causal, scale=scale)
            cache = self._put_kv(cache, at + (0, 0), k, v)
        elif c.sharded_decode:
            from .sharding import get_batch_axes
            o, k, v = decode_attention_sharded(
                q, self._take(cache["k"], at), self._take(cache["v"], at),
                k, v, length, dp_axes=get_batch_axes(), scale=scale)
            cache = self._put_kv(cache, at + (0, 0), k, v)
        else:
            o = decode_attention(q, self._take(cache["k"], at),
                                 self._take(cache["v"], at), length,
                                 scale=scale, k_new=k, v_new=v)
            cache = self._put_kv(cache, at + (0, length), k, v)
        return o.reshape(b, s, -1) @ bp["wo"], cache

    def _put_kv(self, cache: dict, at: tuple, k, v) -> dict:
        if not cache:
            return cache
        return {"k": self._put(cache["k"], k, at),
                "v": self._put(cache["v"], v, at)}

    def _cross(self, cp: dict, x: jax.Array, src, cache: dict, at: tuple,
               length):
        """x + cross-attention of x to ``src`` (the encoder's output, the
        image embeddings), whose K/V are the entries at ``at`` of the
        stacked ``xk``, ``xv``: written where ``cache`` holds them over
        a sequence, read when decoding (``length`` set, ``src`` None).
        Returns (x, cache)."""
        c = self.cfg
        b, s, _ = x.shape
        h = rms_norm(x, cp["ln_x"], c.norm_eps)
        q = (h @ cp["wq"]).reshape(b, s, c.n_heads, c.hd)
        if length is None:
            t = src.shape[1]
            k = (src @ cp["wk"]).reshape(b, t, c.n_kv_heads, c.hd)
            v = (src @ cp["wv"]).reshape(b, t, c.n_kv_heads, c.hd)
            o = blocked_attention(q, k, v, causal=False)
            if cache:
                cache = {"xk": self._put(cache["xk"], k, at),
                         "xv": self._put(cache["xv"], v, at)}
        else:
            k, v = self._take(cache["xk"], at), self._take(cache["xv"], at)
            o = decode_attention(q, k, v, k.shape[1])
        return x + o.reshape(b, s, -1) @ cp["wo"], cache

    def _mamba2(self, bp: dict, h: jax.Array, cache: dict, at: tuple):
        """Mamba-2 mixer of the normed ``h``, from and into the layer's
        entries at ``at`` of the stacked ``conv``, ``ssm`` that ``cache``
        holds ({} in training).  Returns (y, cache)."""
        s = self.cfg.ssm
        st = {n: self._take(v, at) for n, v in cache.items()}
        y, conv, ssm = mamba2_mixer(
            bp, h, headdim=s.headdim, n_groups=s.n_groups,
            state_dim=s.state_dim, chunk=s.chunk, eps=self.cfg.norm_eps,
            conv0=st.get("conv"), ssm0=st.get("ssm"))
        if cache:
            cache = {"conv": self._put(cache["conv"], conv, at),
                     "ssm": self._put(cache["ssm"], ssm, at)}
        return y, cache

    def _mlp(self, bp: dict, x: jax.Array):
        """The second half of a layer, x + r * f(norm(x)).  Under a
        layer pattern f is the held experts plus the shared MLP;
        otherwise the family's MoE (``moe_mlp``, whose load-balancing
        loss is the aux loss) or its dense SwiGLU or GELU MLP.  r is the
        residual multiplier (1 but for granite).  Returns (x, aux loss,
        (token, expert) pairs routed to held experts)."""
        c = self.cfg
        h = rms_norm(x, bp["ln2"], c.norm_eps)
        aux, routed = jnp.float32(0.0), jnp.zeros((), jnp.int32)
        if c.layer_types:
            b, s, d = h.shape
            y = jnp.zeros_like(h)
            if c.moe is not None:
                e, routed = held_expert_mlp(
                    h.reshape(b * s, d), bp["router"], bp["wg"], bp["wu"],
                    bp["wd"], c.moe.top_k, c.moe.first_held)
                y = e.reshape(b, s, d)
            if c.shared_d_ff:
                y = y + swiglu(h, bp["shared_wg"], bp["shared_wu"],
                               bp["shared_wd"])
        elif c.moe is not None:
            y, aux = moe_mlp(h, bp["router"], bp["wg"], bp["wu"], bp["wd"],
                             c.moe.top_k, group_routing=c.moe_group_routing)
        elif c.mlp_type == "gelu":
            y = jax.nn.gelu(h @ bp["wu"]) @ bp["wd"]
        else:
            y = swiglu(h, bp["wg"], bp["wu"], bp["wd"])
        return x + c.residual_multiplier * y, aux, routed

    def _layer(self, kind: str, bp: dict, x: jax.Array, cache: dict,
               at: tuple, positions, length, causal: bool = True):
        """x + r * mixer(norm(x)), then the second half (``_mlp``); the
        mixer reads and writes its entries at ``at`` of ``cache``.
        Returns (x, cache, aux loss, routed pairs)."""
        c = self.cfg
        h = rms_norm(x, bp["ln1"], c.norm_eps)
        if kind == "mamba":
            y, cache = self._mamba2(bp, h, cache, at)
        else:
            y, cache = self._attention(bp, h, cache, at, positions, length,
                                       causal)
        x, aux, routed = self._mlp(bp, x + c.residual_multiplier * y)
        return x, cache, aux, routed

    def _ssm_block(self, bp: dict, x: jax.Array, h0=None, conv0=None):
        """Mamba (v1) block from the states ``h0``, ``conv0`` (zeros when
        None): over the sequence, or, one position with a state in (a
        decode step), one step of the recurrence.  Returns (x + y,
        h_fin, conv_fin)."""
        c = self.cfg
        s = c.ssm
        din = s.expand * c.d_model
        dt_rank = bp["dt_proj"].shape[-2]
        h = rms_norm(x, bp["ln"], c.norm_eps)
        xz = h @ bp["in_proj"]
        xi, z = xz[..., :din], xz[..., din:]
        xi, conv_fin = causal_conv1d(xi, bp["conv_w"], conv0)
        xi = jax.nn.silu(xi)
        proj = xi @ bp["x_proj"]
        dt = proj[..., :dt_rank] @ bp["dt_proj"] + bp["dt_bias"]
        B = proj[..., dt_rank:dt_rank + s.state_dim]
        C = proj[..., dt_rank + s.state_dim:]
        A = -jnp.exp(bp["A_log"])
        if x.shape[1] == 1 and h0 is not None:
            y, h_fin = selective_scan_step(xi[:, 0], dt[:, 0], A, B[:, 0],
                                           C[:, 0], bp["D"], h0)
            y = y[:, None]
        else:
            y, h_fin = selective_scan(
                xi, dt, A, B, C, bp["D"], h0=h0,
                scan_dtype=_dtype(c.ssm_scan_dtype))
        y = y * jax.nn.silu(z)
        return x + y @ bp["out_proj"], h_fin, conv_fin

    # ------------------------------------------------------------------ the stacks

    def _layers(self) -> tuple[str, ...]:
        """The kind of each layer of the stack that ``_walk`` runs: the
        ``layer_types`` pattern; ``n_layers`` attention layers for the
        dense and moe families; none for the families whose stacks are
        their own (ssm, hybrid, encdec, vlm)."""
        c = self.cfg
        if c.layer_types:
            return c.layer_types
        return ("attention",) * c.n_layers if c.family in ("dense", "moe") else ()

    def _group(self, kind: str) -> str:
        """The stacked parameter group of a kind's layers: one group per
        kind, named by it, for a ``layer_types`` pattern; the one group
        ``blocks`` of a stack given by its family."""
        return kind if self.cfg.layer_types else "blocks"

    def _kinds(self) -> dict[str, int]:
        """Layers of each kind in ``_layers()``, in order of first use."""
        out: dict[str, int] = {}
        for t in self._layers():
            out[t] = out.get(t, 0) + 1
        return out

    def _runs(self) -> list[tuple[str, int, int]]:
        """``_layers()`` as runs of one kind: (kind, first, stop), the
        indices of the run's layers in that kind's stacked group."""
        runs: list[tuple[str, int, int]] = []
        seen: dict[str, int] = {}
        for t in self._layers():
            i = seen.get(t, 0)
            seen[t] = i + 1
            if runs and runs[-1][0] == t:
                runs[-1] = (t, runs[-1][1], i + 1)
            else:
                runs.append((t, i, i + 1))
        return runs

    def _walk(self, params: dict, x, state: dict, positions, length,
              remat: bool = False):
        """The layer pattern over x, one ``lax.scan`` per run of layers of
        one kind.  A step's weights are its layer's in the kind's stacked
        group: the scan's input where the run is the whole group, else
        indexed; its mixer reads and writes its entries of the stacked
        leaves in ``state`` ({} in training).  Returns (x, state, aux
        loss, (token, expert) pairs routed to held experts)."""
        kinds = self._kinds()
        aux, routed = jnp.float32(0.0), jnp.zeros((), jnp.int32)
        for kind, first, stop in self._runs():
            group = params[self._group(kind)]

            def body(carry, blk, kind=kind, group=group):
                x, st, aux, routed = carry
                i, bp = blk
                if bp is None:
                    bp = jax.tree.map(
                        lambda t: jax.lax.dynamic_index_in_dim(t, i, 0, False),
                        group)
                x, st, a, r = self._layer(kind, bp, x, st, (i,), positions,
                                          length)
                return (x, st, aux + a, routed + r), None

            if remat:
                body = jax.checkpoint(body)
            whole = stop - first == kinds[kind]
            (x, st, aux, routed), _ = jax.lax.scan(
                body, (x, _pick(state, self._KIND_STATE[kind]), aux, routed),
                (jnp.arange(first, stop), group if whole else None))
            state = {**state, **st}
        return x, state, aux, routed

    def _mamba1_stack(self, params: dict, x, state: dict, positions, length,
                      remat: bool = False):
        """The ssm and hybrid families: a Mamba (v1) layer a step, its
        states the entries of ``h``, ``conv``; in ``hybrid``, after every
        ``shared_attn_every``-th, the one shared attention block, whose
        K/V of each application are entries of their own."""
        c = self.cfg
        every = c.shared_attn_every if c.family == "hybrid" else 0

        def body(carry, blk):
            x, st = carry
            bp, i = blk
            one = {n: self._take(st[n], (i,)) for n in ("h", "conv") if n in st}
            x, h, conv = self._ssm_block(bp, x, one.get("h"), one.get("conv"))
            if st:
                st = {**st, "h": self._put(st["h"], h, (i,)),
                      "conv": self._put(st["conv"], conv, (i,))}
            if every:
                def attend(x, kv):
                    x, kv, _, _ = self._layer("attention", params["shared_attn"],
                                              x, kv, (i // every,), positions,
                                              length)
                    return x, kv
                x, kv = jax.lax.cond((i + 1) % every == 0, attend,
                                     lambda x, kv: (x, kv), x,
                                     _pick(st, ("k", "v")))
                st = {**st, **kv}
            return (x, st), None

        if remat:
            body = jax.checkpoint(body)
        (x, state), _ = jax.lax.scan(
            body, (x, state), (params["blocks"], jnp.arange(c.n_layers)))
        return x, state, jnp.float32(0.0), jnp.zeros((), jnp.int32)

    def _encdec(self, params: dict, batch, x, state: dict, positions, length,
                remat: bool = False):
        """Whisper-style: over a sequence the non-causal encoder of the
        stubbed frame embeddings first (decoding, its K/V are cached in
        ``xk``, ``xv``); then the decoder, learned positions added, a
        layer causal self-attention, cross-attention to the encoder's
        output and the MLP."""
        c = self.cfg
        aux = jnp.float32(0.0)
        e = None
        if length is None:
            e = (batch["frames"].astype(self.cdt)
                 + params["pos_enc"][None].astype(self.cdt))
            e_pos = jnp.arange(c.enc_seq)

            def enc_body(carry, bp):
                e, aux = carry
                e, _, a, _ = self._layer("attention", bp, e, {}, (), e_pos,
                                         None, causal=False)
                return (e, aux + a), None
            if remat:
                enc_body = jax.checkpoint(enc_body)
            (e, aux), _ = jax.lax.scan(enc_body, (e, aux), params["enc_blocks"])
        x = x + params["pos_dec"][positions][None].astype(self.cdt)

        def dec_body(carry, blk):
            x, st, aux = carry
            bp, cp, i = blk
            h = rms_norm(x, bp["ln1"], c.norm_eps)
            y, kv = self._attention(bp, h, _pick(st, ("k", "v")), (i,),
                                    positions, length)
            x, xkv = self._cross(cp, x + y, e, _pick(st, ("xk", "xv")), (i,),
                                 length)
            x, a, _ = self._mlp(bp, x)
            return (x, {**kv, **xkv}, aux + a), None
        if remat:
            dec_body = jax.checkpoint(dec_body)
        (x, state, aux), _ = jax.lax.scan(
            dec_body, (x, state, aux),
            (params["dec_blocks"], params["dec_cross"], jnp.arange(c.n_layers)))
        return x, state, aux, jnp.zeros((), jnp.int32)

    def _vlm(self, params: dict, batch, x, state: dict, positions, length,
             remat: bool = False):
        """Llama-3.2-Vision-style: units of ``cross_attn_every - 1``
        self-attention layers (K/V entries at (unit, layer)) and one
        cross-attention layer to the stubbed image embeddings (decoding,
        their K/V are cached in ``xk``, ``xv``)."""
        c = self.cfg
        img = None if length is not None else batch["img_embeds"].astype(self.cdt)
        units = c.n_layers // c.cross_attn_every

        def unit(carry, blk):
            x, st, aux = carry
            sp, cp, u = blk

            def self_body(carry, blk):
                x, kv, aux = carry
                bp, j = blk
                x, kv, a, _ = self._layer("attention", bp, x, kv, (u, j),
                                          positions, length)
                return (x, kv, aux + a), None
            (x, kv, aux), _ = jax.lax.scan(
                self_body, (x, _pick(st, ("k", "v")), aux),
                (sp, jnp.arange(c.cross_attn_every - 1)))
            x, xkv = self._cross(cp, x, img, _pick(st, ("xk", "xv")), (u,),
                                 length)
            x, a, _ = self._mlp(cp, x)
            return (x, {**kv, **xkv}, aux + a), None
        if remat:
            unit = jax.checkpoint(unit)
        (x, state, aux), _ = jax.lax.scan(
            unit, (x, state, jnp.float32(0.0)),
            (params["blocks"], params["cross_blocks"], jnp.arange(units)))
        return x, state, aux, jnp.zeros((), jnp.int32)

    def _stack(self, params: dict, batch, x, state: dict, positions, length,
               remat: bool = False):
        """The model's layers over the embedded x: over a sequence
        (``length`` None) writing each layer's cache entries where
        ``state`` holds the cache's per-stream leaves ({} in training),
        or one position decoded at ``length``.  Returns (x, state, aux
        loss, (token, expert) pairs routed to held experts)."""
        c = self.cfg
        if self._layers():
            return self._walk(params, x, state, positions, length, remat)
        if c.family in ("ssm", "hybrid"):
            return self._mamba1_stack(params, x, state, positions, length,
                                      remat)
        if c.family == "encdec":
            return self._encdec(params, batch, x, state, positions, length,
                                remat)
        if c.family == "vlm":
            return self._vlm(params, batch, x, state, positions, length, remat)
        raise ValueError(c.family)

    def _embed(self, params: dict, tokens) -> jax.Array:
        return (params["emb"][tokens].astype(self.cdt)
                * self.cfg.embedding_multiplier)

    def _final_hidden(self, params: dict, x):
        """The final norm, and the logits' divisor folded into the hidden
        states (the head is linear), so that ``x @ lm_head`` are logits."""
        c = self.cfg
        return rms_norm(x, params["out_norm"], c.norm_eps) / c.logits_scaling

    # ------------------------------------------------------------------ forward (train / prefill-style)

    def forward(self, params: dict, batch: dict,
                remat: bool = True) -> tuple[jax.Array, jax.Array]:
        """Returns (final hidden states (B,S,D), aux loss scalar)."""
        tokens = batch["tokens"]
        x, _, aux, _ = self._stack(params, batch, self._embed(params, tokens),
                                   {}, jnp.arange(tokens.shape[1]), None,
                                   remat)
        return self._final_hidden(params, x), aux

    # ------------------------------------------------------------------ loss

    def lm_head(self, params: dict) -> jax.Array:
        if self.cfg.tie_embeddings:
            return params["emb"].T
        return params["lm_head"]

    def loss(self, params: dict, batch: dict, remat: bool = True,
             loss_chunk: int = 512) -> jax.Array:
        """Causal LM cross-entropy, logits computed in sequence chunks so
        the (B, S, V) tensor is never materialized."""
        x, aux = self.forward(params, batch, remat=remat)
        targets = batch["labels"]
        head = self.lm_head(params)
        b, s, d = x.shape
        chunk = min(loss_chunk, s)
        n_chunks = s // chunk
        assert s % chunk == 0, (s, chunk)
        xc = x.reshape(b, n_chunks, chunk, d)
        tc = targets.reshape(b, n_chunks, chunk)

        def step(tot, blk):
            xb, tb = blk   # (B, chunk, D), (B, chunk)
            logits = (xb @ head).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, tb[..., None], axis=-1)[..., 0]
            return tot + jnp.sum(lse - gold), None

        # remat per chunk so the (B, chunk, V) logits are recomputed in
        # the backward instead of being stacked as scan residuals
        tot, _ = jax.lax.scan(
            jax.checkpoint(step), jnp.float32(0.0),
            (jnp.moveaxis(xc, 1, 0), jnp.moveaxis(tc, 1, 0)))
        return tot / (b * s) + 0.01 * aux

    # ------------------------------------------------------------------ decode

    def init_cache(self, batch: int, max_len: int) -> dict:
        c = self.cfg
        hd = c.hd
        cache: dict = {"len": jnp.zeros((), jnp.int32)}
        if self._layers():
            return {**cache, **self._layer_cache(batch, max_len)}
        if c.family in ("ssm", "hybrid"):
            s = c.ssm
            din = s.expand * c.d_model
            cache["h"] = jnp.zeros((c.n_layers, batch, din, s.state_dim),
                                   jnp.float32)
            cache["conv"] = jnp.zeros((c.n_layers, batch, s.conv_dim - 1, din),
                                      self.cdt)
            if c.family == "hybrid":
                n_apps = c.n_layers // c.shared_attn_every
                cache["k"] = jnp.zeros(
                    (n_apps, batch, max_len, c.n_kv_heads, hd), self.cdt)
                cache["v"] = jnp.zeros_like(cache["k"])
        elif c.family == "encdec":
            cache["k"] = jnp.zeros(
                (c.n_layers, batch, max_len, c.n_kv_heads, hd), self.cdt)
            cache["v"] = jnp.zeros_like(cache["k"])
            cache["xk"] = jnp.zeros(
                (c.n_layers, batch, c.enc_seq, c.n_kv_heads, hd), self.cdt)
            cache["xv"] = jnp.zeros_like(cache["xk"])
        elif c.family == "vlm":
            kk = c.cross_attn_every
            units = c.n_layers // kk
            cache["k"] = jnp.zeros(
                (units, kk - 1, batch, max_len, c.n_kv_heads, hd), self.cdt)
            cache["v"] = jnp.zeros_like(cache["k"])
            cache["xk"] = jnp.zeros(
                (units, batch, c.img_tokens, c.n_kv_heads, hd), self.cdt)
            cache["xv"] = jnp.zeros_like(cache["xk"])
        return cache

    def _layer_cache(self, batch: int, max_len: int) -> dict:
        """The walked stack's cache: ``conv`` (L, B, K-1, C) and ``ssm``
        (L, B, H, P, N), float32, for the mamba layers; ``k``, ``v`` (L,
        B, T, Hkv, hd) for the attention layers.  With held experts, two
        int32 counters summed over decode steps: ``expert_routed``, the
        (token, expert) pairs that landed on a held expert, and
        ``expert_rows``, the rows the expert matmuls computed (they wrap
        past 2**31)."""
        c = self.cfg
        kinds = self._kinds()
        out: dict = {}
        if "mamba" in kinds:
            s = c.ssm
            din = s.expand * c.d_model
            n = kinds["mamba"]
            out["conv"] = jnp.zeros(
                (n, batch, s.conv_dim - 1, din + 2 * s.n_groups * s.state_dim),
                self.cdt)
            out["ssm"] = jnp.zeros(
                (n, batch, din // s.headdim, s.headdim, s.state_dim), jnp.float32)
        if "attention" in kinds:
            kv = (kinds["attention"], batch, max_len, c.n_kv_heads, c.hd)
            out["k"] = jnp.zeros(kv, self.cdt)
            out["v"] = jnp.zeros(kv, self.cdt)
        if c.layer_types and c.moe is not None:
            out["expert_routed"] = jnp.zeros((), jnp.int32)
            out["expert_rows"] = jnp.zeros((), jnp.int32)
        return out

    @staticmethod
    def _state(cache: dict) -> dict:
        """The cache's per-stream leaves: all but the scalars (the
        length, the counters)."""
        return {k: v for k, v in cache.items() if v.ndim}

    def decode_step(self, params: dict, cache: dict,
                    token: jax.Array) -> tuple[dict, jax.Array]:
        """token: (B,) int32 -> (new_cache, logits (B, V))."""
        c = self.cfg
        length = cache["len"]
        x, state, _, routed = self._stack(
            params, None, self._embed(params, token)[:, None],
            self._state(cache), jnp.full((1,), length, jnp.int32), length)
        cache = {**cache, **state, "len": length + 1}
        if "expert_rows" in cache:
            cache["expert_routed"] = cache["expert_routed"] + routed
            cache["expert_rows"] = (cache["expert_rows"]
                                    + token.shape[0] * c.moe.held * c.n_layers)
        x = self._final_hidden(params, x)
        return cache, (x[:, 0] @ self.lm_head(params)).astype(jnp.float32)

    # ------------------------------------------------------------------ prefill

    def prefill(self, params: dict, batch: dict, max_len: int) -> tuple[dict, jax.Array]:
        """Run the full prompt, writing each layer's cache entries into a
        fresh decode cache; return it and the last position's logits."""
        tokens = batch["tokens"]
        b, s = tokens.shape
        cache = self.init_cache(b, max_len)
        x, state, _, _ = self._stack(params, batch, self._embed(params, tokens),
                                     self._state(cache), jnp.arange(s), None)
        x = self._final_hidden(params, x[:, -1:])
        logits = (x[:, 0] @ self.lm_head(params)).astype(jnp.float32)
        return {**cache, **state, "len": jnp.asarray(s, jnp.int32)}, logits

    def cache_batch_axes(self) -> dict:
        """The batch axis of each leaf of :meth:`init_cache`'s cache, by
        name: 1, after the leading per-layer axis, except the vision
        stack's self-attention K/V (units, layers per unit, batch, ...);
        None for the scalars (the length, counters), which hold no
        stream's state."""
        c = self.cfg
        deep = ("k", "v") if c.family == "vlm" and not c.layer_types else ()
        shapes = jax.eval_shape(lambda: self.init_cache(1, 1))
        return {k: None if not v.shape else 2 if k in deep else 1
                for k, v in shapes.items()}
