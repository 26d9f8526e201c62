"""Full model definitions for every assigned architecture family.

All stacks scan over layer-stacked parameters (``jax.lax.scan``) so HLO
size and compile time are O(1) in depth — required for the 100-layer
90 B and 64-layer 314 B dry-runs.  Families:

  dense | moe          decoder-only LM (GQA + RoPE [+ MoE MLP])
  ssm                  attention-free Mamba stack (falcon-mamba)
  hybrid               Mamba2-style stack + one *shared* attention block
                       applied every k layers (zamba2)
  encdec               Whisper-style: non-causal encoder over stubbed
                       frame embeddings + causal decoder w/ cross-attn
  vlm                  Llama-3.2-Vision-style: cross-attention image
                       layers every k self-attention layers (stubbed
                       patch embeddings)

A config with ``layer_types`` (granite-4.0-h-style) replaces the family's
stack by a pattern of layers of two kinds, Mamba-2 and attention, each
followed by an MoE and a shared MLP; one stacked parameter group per
kind, one ``lax.scan`` per run of layers of one kind.

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
    #: the stacked cache leaves of each kind of the layer pattern
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
        c = self.cfg
        if c.moe is not None:
            e, f = c.moe.n_experts, c.moe.expert_d_ff
            return {
                "ln2": ini.ones(lead + (c.d_model,)),
                "router": ini.normal(lead + (c.d_model, e)),
                "wg": ini.normal(lead + (e, c.d_model, f)),
                "wu": ini.normal(lead + (e, c.d_model, f)),
                "wd": ini.normal(lead + (e, f, c.d_model)),
            }
        if c.mlp_type == "gelu":
            return {
                "ln2": ini.ones(lead + (c.d_model,)),
                "wu": ini.normal(lead + (c.d_model, c.d_ff)),
                "wd": ini.normal(lead + (c.d_ff, c.d_model)),
            }
        return {
            "ln2": ini.ones(lead + (c.d_model,)),
            "wg": ini.normal(lead + (c.d_model, c.d_ff)),
            "wu": ini.normal(lead + (c.d_model, c.d_ff)),
            "wd": ini.normal(lead + (c.d_ff, c.d_model)),
        }

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
        if c.layer_types:
            for kind, n in self._kinds().items():
                p[kind] = self._pattern_params(ini, kind, n)
        elif c.family in ("dense", "moe"):
            p["blocks"] = {**self._attn_params(ini, (L,)),
                           **self._mlp_params(ini, (L,))}
        elif c.family == "ssm":
            p["blocks"] = self._ssm_params(ini, (L,))
        elif c.family == "hybrid":
            p["blocks"] = self._ssm_params(ini, (L,))
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
            selfs = units * (k - 1)
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

    # ------------------------------------------------------------------ attention pieces

    def _qkv(self, bp: dict, h: jax.Array, positions, rope: bool = True):
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
        if rope and c.rope_style != "none":
            q = apply_rope(q, positions, c.rope_theta, c.rope_style)
            k = apply_rope(k, positions, c.rope_theta, c.rope_style)
        return q, k, v

    def _self_attn(self, bp: dict, x: jax.Array, positions, causal: bool,
                   rope: bool = True) -> jax.Array:
        b, s, _ = x.shape
        h = rms_norm(x, bp["ln1"], self.cfg.norm_eps)
        q, k, v = self._qkv(bp, h, positions, rope)
        o = blocked_attention(q, k, v, causal=causal)
        return x + o.reshape(b, s, -1) @ bp["wo"]

    def _cross_attn(self, bp: dict, x: jax.Array, kv_src: jax.Array) -> jax.Array:
        c = self.cfg
        hd = c.hd
        b, s, _ = x.shape
        t = kv_src.shape[1]
        h = rms_norm(x, bp["ln_x"], c.norm_eps)
        q = (h @ bp["wq"]).reshape(b, s, c.n_heads, hd)
        k = (kv_src @ bp["wk"]).reshape(b, t, c.n_kv_heads, hd)
        v = (kv_src @ bp["wv"]).reshape(b, t, c.n_kv_heads, hd)
        o = blocked_attention(q, k, v, causal=False)
        return x + o.reshape(b, s, -1) @ bp["wo"]

    def _mlp(self, bp: dict, x: jax.Array):
        c = self.cfg
        h = rms_norm(x, bp["ln2"], c.norm_eps)
        if c.moe is not None:
            y, aux = moe_mlp(h, bp["router"], bp["wg"], bp["wu"], bp["wd"],
                             c.moe.top_k,
                             group_routing=c.moe_group_routing)
            return x + y, aux
        if c.mlp_type == "gelu":
            y = jax.nn.gelu(h @ bp["wu"]) @ bp["wd"]
            return x + y, jnp.float32(0.0)
        return x + swiglu(h, bp["wg"], bp["wu"], bp["wd"]), jnp.float32(0.0)

    def _ssm_block(self, bp: dict, x: jax.Array, h0=None, conv0=None):
        """Mamba block over a full sequence.  Returns (y, h_fin, conv_fin)."""
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
        y, h_fin = selective_scan(
            xi, dt, A, B, C, bp["D"], h0=h0,
            scan_dtype=_dtype(c.ssm_scan_dtype))
        y = y * jax.nn.silu(z)
        return x + y @ bp["out_proj"], h_fin, conv_fin

    # ------------------------------------------------------------------ forward (train / prefill-style)

    def forward(self, params: dict, batch: dict,
                remat: bool = True) -> tuple[jax.Array, jax.Array]:
        """Returns (final hidden states (B,S,D), aux loss scalar)."""
        c = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = params["emb"][tokens].astype(self.cdt)
        positions = jnp.arange(s)
        aux0 = jnp.float32(0.0)

        if c.layer_types:
            x, _, _ = self._walk(params, x * c.embedding_multiplier, {},
                                 positions, None, remat=remat)
            return self._final_hidden(params, x), aux0
        if c.family in ("dense", "moe"):
            def body(carry, bp):
                x, aux = carry
                x = self._self_attn(bp, x, positions, causal=True)
                x, a = self._mlp(bp, x)
                return (x, aux + a), None
            if remat:
                body = jax.checkpoint(body)
            (x, aux), _ = jax.lax.scan(body, (x, aux0), params["blocks"])

        elif c.family == "ssm":
            def body(carry, bp):
                x, aux = carry
                x, _, _ = self._ssm_block(bp, x)
                return (x, aux), None
            if remat:
                body = jax.checkpoint(body)
            (x, aux), _ = jax.lax.scan(body, (x, aux0), params["blocks"])

        elif c.family == "hybrid":
            k = c.shared_attn_every
            shared = params["shared_attn"]

            def body(carry, blk):
                x, aux = carry
                bp, idx = blk
                x, _, _ = self._ssm_block(bp, x)
                def with_attn(x):
                    x = self._self_attn(shared, x, positions, causal=True)
                    x, _ = self._mlp(shared, x)
                    return x
                x = jax.lax.cond((idx + 1) % k == 0, with_attn, lambda x: x, x)
                return (x, aux), None
            if remat:
                body = jax.checkpoint(body)
            (x, aux), _ = jax.lax.scan(
                body, (x, aux0), (params["blocks"], jnp.arange(c.n_layers)))

        elif c.family == "encdec":
            frames = batch["frames"].astype(self.cdt)   # stubbed frontend
            e = frames + params["pos_enc"][None].astype(self.cdt)
            e_pos = jnp.arange(c.enc_seq)

            def enc_body(carry, bp):
                e, aux = carry
                e = self._self_attn(bp, e, e_pos, causal=False, rope=False)
                e, a = self._mlp(bp, e)
                return (e, aux + a), None
            if remat:
                enc_body = jax.checkpoint(enc_body)
            (e, aux), _ = jax.lax.scan(enc_body, (e, aux0), params["enc_blocks"])

            x = x + params["pos_dec"][positions][None].astype(self.cdt)

            def dec_body(carry, blk):
                x, aux = carry
                bp, cp = blk
                x = self._self_attn(bp, x, positions, causal=True, rope=False)
                x = self._cross_attn(cp, x, e)
                x, a = self._mlp(bp, x)
                return (x, aux + a), None
            if remat:
                dec_body = jax.checkpoint(dec_body)
            (x, aux), _ = jax.lax.scan(
                dec_body, (x, aux), (params["dec_blocks"], params["dec_cross"]))
            aux = aux

        elif c.family == "vlm":
            img = batch["img_embeds"].astype(self.cdt)  # stubbed frontend
            kk = c.cross_attn_every

            def unit_body(carry, blk):
                x, aux = carry
                sp, cp = blk     # sp leaves: (k-1, ...), cp leaves: (...)

                def self_body(carry2, bp):
                    x, aux = carry2
                    x = self._self_attn(bp, x, positions, causal=True)
                    x, a = self._mlp(bp, x)
                    return (x, aux + a), None
                (x, aux), _ = jax.lax.scan(self_body, (x, aux), sp)
                x = self._cross_attn(cp, x, img)
                x, a = self._mlp(cp, x)
                return (x, aux + a), None
            if remat:
                unit_body = jax.checkpoint(unit_body)
            (x, aux), _ = jax.lax.scan(
                unit_body, (x, aux0),
                (params["blocks"], params["cross_blocks"]))
        else:
            raise ValueError(c.family)

        x = rms_norm(x, params["out_norm"], c.norm_eps)
        return x, aux

    # ------------------------------------------------------------------ loss

    def lm_head(self, params: dict) -> jax.Array:
        if self.cfg.tie_embeddings:
            return params["emb"].T
        return params["lm_head"]

    def loss(self, params: dict, batch: dict, remat: bool = True,
             loss_chunk: int = 512) -> jax.Array:
        """Causal LM cross-entropy, logits computed in sequence chunks so
        the (B, S, V) tensor is never materialized."""
        c = self.cfg
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
        kv = (c.n_layers, batch, max_len, c.n_kv_heads, hd)
        cache: dict = {"len": jnp.zeros((), jnp.int32)}
        if c.layer_types:
            return {**cache, **self._pattern_cache(batch, max_len)}
        if c.family in ("dense", "moe"):
            cache["k"] = jnp.zeros(kv, self.cdt)
            cache["v"] = jnp.zeros(kv, self.cdt)
        elif c.family == "ssm":
            s = c.ssm
            din = s.expand * c.d_model
            cache["h"] = jnp.zeros((c.n_layers, batch, din, s.state_dim),
                                   jnp.float32)
            cache["conv"] = jnp.zeros((c.n_layers, batch, s.conv_dim - 1, din),
                                      self.cdt)
        elif c.family == "hybrid":
            s = c.ssm
            din = s.expand * c.d_model
            n_apps = c.n_layers // c.shared_attn_every
            cache["h"] = jnp.zeros((c.n_layers, batch, din, s.state_dim),
                                   jnp.float32)
            cache["conv"] = jnp.zeros((c.n_layers, batch, s.conv_dim - 1, din),
                                      self.cdt)
            cache["k"] = jnp.zeros((n_apps, batch, max_len, c.n_kv_heads, hd),
                                   self.cdt)
            cache["v"] = jnp.zeros((n_apps, batch, max_len, c.n_kv_heads, hd),
                                   self.cdt)
        elif c.family == "encdec":
            cache["k"] = jnp.zeros(kv, self.cdt)
            cache["v"] = jnp.zeros(kv, self.cdt)
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

    def _attn_decode(self, bp: dict, x1: jax.Array, kc, vc, length,
                     rope: bool = True):
        """One-token self-attention against a cache slice.
        x1: (B, 1, D); kc/vc: (B, T, Hkv, hd)."""
        c = self.cfg
        hd = c.hd
        b = x1.shape[0]
        h = rms_norm(x1, bp["ln1"], c.norm_eps)
        q = h @ bp["wq"]
        k = h @ bp["wk"]
        v = h @ bp["wv"]
        if "bq" in bp:
            q, k, v = q + bp["bq"], k + bp["bk"], v + bp["bv"]
        q = q.reshape(b, 1, c.n_heads, hd)
        k = k.reshape(b, 1, c.n_kv_heads, hd)
        v = v.reshape(b, 1, c.n_kv_heads, hd)
        if rope:
            pos = jnp.full((1,), length, jnp.int32)
            q = apply_rope(q, pos, c.rope_theta, c.rope_style)
            k = apply_rope(k, pos, c.rope_theta, c.rope_style)
        if c.sharded_decode:
            from .layers import decode_attention_sharded
            from .sharding import get_batch_axes
            o, kc, vc = decode_attention_sharded(
                q, kc, vc, k, v, length, dp_axes=get_batch_axes())
            return x1 + o.reshape(b, 1, -1) @ bp["wo"], kc, vc
        kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype),
                                          (0, length, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype),
                                          (0, length, 0, 0))
        o = decode_attention(q, kc, vc, length + 1)
        return x1 + o.reshape(b, 1, -1) @ bp["wo"], kc, vc

    def _cross_decode(self, bp: dict, x1: jax.Array, xk, xv):
        c = self.cfg
        b = x1.shape[0]
        h = rms_norm(x1, bp["ln_x"], c.norm_eps)
        q = (h @ bp["wq"]).reshape(b, 1, c.n_heads, c.hd)
        o = decode_attention(q, xk, xv, xk.shape[1])
        return x1 + o.reshape(b, 1, -1) @ bp["wo"]

    def decode_step(self, params: dict, cache: dict,
                    token: jax.Array) -> tuple[dict, jax.Array]:
        """token: (B,) int32 -> (new_cache, logits (B, V))."""
        c = self.cfg
        b = token.shape[0]
        length = cache["len"]
        x = params["emb"][token][:, None].astype(self.cdt)   # (B, 1, D)

        if c.layer_types:
            return self._pattern_decode(params, cache, x)
        if c.family in ("dense", "moe"):
            # K and V ride in the carry, so that a donated cache is
            # updated in place (as the scan's outputs they would fill a
            # new buffer on every step), and only the token's position
            # is written back: the whole layer slice would be written
            # otherwise.  A sequence-sharded slice goes back whole, each
            # shard its own part.
            def put(stack, new, i):
                if c.sharded_decode:
                    return jax.lax.dynamic_update_index_in_dim(stack, new, i, 0)
                row = jax.lax.dynamic_slice_in_dim(new, length, 1, 1)
                return jax.lax.dynamic_update_slice(stack, row[None],
                                                    (i, 0, length, 0, 0))

            def body(carry, blk):
                x, ks, vs = carry
                bp, i = blk
                kc = jax.lax.dynamic_index_in_dim(ks, i, 0, False)
                vc = jax.lax.dynamic_index_in_dim(vs, i, 0, False)
                x, kc, vc = self._attn_decode(bp, x, kc, vc, length)
                x, _ = self._mlp(bp, x)
                return (x, put(ks, kc, i), put(vs, vc, i)), None
            (x, ks, vs), _ = jax.lax.scan(
                body, (x, cache["k"], cache["v"]),
                (params["blocks"], jnp.arange(c.n_layers)))
            cache = {**cache, "k": ks, "v": vs}

        elif c.family == "ssm":
            s = c.ssm
            din = s.expand * c.d_model

            def body(x, blk):
                bp, h0, conv0 = blk
                xin = x
                hh = rms_norm(x, bp["ln"], c.norm_eps)
                xz = hh @ bp["in_proj"]
                xi, z = xz[..., :din], xz[..., din:]
                xi, conv_new = causal_conv1d(xi, bp["conv_w"], conv0)
                xi = jax.nn.silu(xi)[:, 0]
                dt_rank = bp["dt_proj"].shape[-2]
                proj = xi @ bp["x_proj"]
                dt = proj[..., :dt_rank] @ bp["dt_proj"] + bp["dt_bias"]
                B = proj[..., dt_rank:dt_rank + s.state_dim]
                C = proj[..., dt_rank + s.state_dim:]
                A = -jnp.exp(bp["A_log"])
                y, h_new = selective_scan_step(xi, dt, A, B, C, bp["D"], h0)
                y = y[:, None] * jax.nn.silu(z)
                return xin + y @ bp["out_proj"], (h_new, conv_new)
            x, (hs, convs) = jax.lax.scan(
                body, x, (params["blocks"], cache["h"], cache["conv"]))
            cache = {**cache, "h": hs, "conv": convs}

        elif c.family == "hybrid":
            s = c.ssm
            din = s.expand * c.d_model
            k_every = c.shared_attn_every
            shared = params["shared_attn"]

            def body(carry, blk):
                x, kall, vall = carry
                bp, h0, conv0, idx = blk
                xin = x
                hh = rms_norm(x, bp["ln"], c.norm_eps)
                xz = hh @ bp["in_proj"]
                xi, z = xz[..., :din], xz[..., din:]
                xi, conv_new = causal_conv1d(xi, bp["conv_w"], conv0)
                xi = jax.nn.silu(xi)[:, 0]
                dt_rank = bp["dt_proj"].shape[-2]
                proj = xi @ bp["x_proj"]
                dt = proj[..., :dt_rank] @ bp["dt_proj"] + bp["dt_bias"]
                B = proj[..., dt_rank:dt_rank + s.state_dim]
                C = proj[..., dt_rank + s.state_dim:]
                A = -jnp.exp(bp["A_log"])
                y, h_new = selective_scan_step(xi, dt, A, B, C, bp["D"], h0)
                x = xin + (y[:, None] * jax.nn.silu(z)) @ bp["out_proj"]

                app = idx // k_every

                def with_attn(ops):
                    x, kall, vall = ops
                    kc = jax.lax.dynamic_index_in_dim(kall, app, 0, False)
                    vc = jax.lax.dynamic_index_in_dim(vall, app, 0, False)
                    x, kc, vc = self._attn_decode(shared, x, kc, vc, length)
                    x, _ = self._mlp(shared, x)
                    kall = jax.lax.dynamic_update_index_in_dim(kall, kc, app, 0)
                    vall = jax.lax.dynamic_update_index_in_dim(vall, vc, app, 0)
                    return x, kall, vall
                x, kall, vall = jax.lax.cond(
                    (idx + 1) % k_every == 0, with_attn, lambda o: o,
                    (x, kall, vall))
                return (x, kall, vall), (h_new, conv_new)
            (x, kall, vall), (hs, convs) = jax.lax.scan(
                body, (x, cache["k"], cache["v"]),
                (params["blocks"], cache["h"], cache["conv"],
                 jnp.arange(c.n_layers)))
            cache = {**cache, "h": hs, "conv": convs, "k": kall, "v": vall}

        elif c.family == "encdec":
            x = x + params["pos_dec"][length][None, None].astype(self.cdt)

            def body(x, blk):
                bp, cp, kc, vc, xk, xv = blk
                x, kc, vc = self._attn_decode(bp, x, kc, vc, length, rope=False)
                x = self._cross_decode(cp, x, xk, xv)
                x, _ = self._mlp(bp, x)
                return x, (kc, vc)
            x, (ks, vs) = jax.lax.scan(
                body, x, (params["dec_blocks"], params["dec_cross"],
                          cache["k"], cache["v"], cache["xk"], cache["xv"]))
            cache = {**cache, "k": ks, "v": vs}

        elif c.family == "vlm":
            def unit(x, blk):
                sp, cp, kc, vc, xk, xv = blk   # kc: (k-1, B, T, Hkv, hd)

                def self_body(x, sblk):
                    bp, kc1, vc1 = sblk
                    x, kc1, vc1 = self._attn_decode(bp, x, kc1, vc1, length)
                    x, _ = self._mlp(bp, x)
                    return x, (kc1, vc1)
                x, (kc, vc) = jax.lax.scan(self_body, x, (sp, kc, vc))
                x = self._cross_decode(cp, x, xk, xv)
                x, _ = self._mlp(cp, x)
                return x, (kc, vc)
            x, (ks, vs) = jax.lax.scan(
                unit, x, (params["blocks"], params["cross_blocks"],
                          cache["k"], cache["v"], cache["xk"], cache["xv"]))
            cache = {**cache, "k": ks, "v": vs}
        else:
            raise ValueError(c.family)

        x = rms_norm(x, params["out_norm"], c.norm_eps)
        logits = (x[:, 0] @ self.lm_head(params)).astype(jnp.float32)
        cache["len"] = length + 1
        return cache, logits

    # ------------------------------------------------------------------ prefill

    def prefill(self, params: dict, batch: dict, max_len: int) -> tuple[dict, jax.Array]:
        """Run the full prompt, build the decode cache, return last logits.

        For dense families the per-layer K/V from the forward pass are
        recomputed here layer-by-layer (scan) into the cache.
        """
        c = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        cache = self.init_cache(b, max_len)
        positions = jnp.arange(s)
        x = params["emb"][tokens].astype(self.cdt)

        if c.layer_types:
            x, state, _ = self._walk(params, x * c.embedding_multiplier,
                                     self._state(cache), positions, None)
            x = self._final_hidden(params, x[:, -1:])
            logits = (x[:, 0] @ self.lm_head(params)).astype(jnp.float32)
            return {**cache, **state, "len": jnp.asarray(s, jnp.int32)}, logits
        if c.family in ("dense", "moe"):
            def body(x, bp):
                h = rms_norm(x, bp["ln1"], c.norm_eps)
                q, k, v = self._qkv(bp, h, positions)
                o = blocked_attention(q, k, v, causal=True)
                x = x + o.reshape(b, s, -1) @ bp["wo"]
                x, _ = self._mlp(bp, x)
                return x, (k.astype(self.cdt), v.astype(self.cdt))
            x, (ks, vs) = jax.lax.scan(body, x, params["blocks"])
            cache["k"] = jax.lax.dynamic_update_slice(
                cache["k"], ks, (0, 0, 0, 0, 0))
            cache["v"] = jax.lax.dynamic_update_slice(
                cache["v"], vs, (0, 0, 0, 0, 0))
        elif c.family == "ssm":
            def body(x, bp):
                x, h_fin, conv_fin = self._ssm_block(bp, x)
                return x, (h_fin, conv_fin.astype(self.cdt))
            x, (hs, convs) = jax.lax.scan(body, x, params["blocks"])
            cache["h"], cache["conv"] = hs, convs
        elif c.family == "hybrid":
            k_every = c.shared_attn_every
            shared = params["shared_attn"]

            def body(carry, blk):
                x, kall, vall = carry
                bp, idx = blk
                x, h_fin, conv_fin = self._ssm_block(bp, x)

                def with_attn(ops):
                    x, kall, vall = ops
                    app = idx // k_every
                    h = rms_norm(x, shared["ln1"], c.norm_eps)
                    q, kk, vv = self._qkv(shared, h, positions)
                    o = blocked_attention(q, kk, vv, causal=True)
                    x = x + o.reshape(b, s, -1) @ shared["wo"]
                    x, _ = self._mlp(shared, x)
                    pad = kall.shape[2] - s
                    kk = jnp.pad(kk.astype(self.cdt),
                                 ((0, 0), (0, pad), (0, 0), (0, 0)))
                    vv = jnp.pad(vv.astype(self.cdt),
                                 ((0, 0), (0, pad), (0, 0), (0, 0)))
                    kall = jax.lax.dynamic_update_index_in_dim(kall, kk, app, 0)
                    vall = jax.lax.dynamic_update_index_in_dim(vall, vv, app, 0)
                    return x, kall, vall
                x, kall, vall = jax.lax.cond(
                    (idx + 1) % k_every == 0, with_attn, lambda o: o,
                    (x, kall, vall))
                return (x, kall, vall), (h_fin, conv_fin.astype(self.cdt))
            (x, kall, vall), (hs, convs) = jax.lax.scan(
                body, (x, cache["k"], cache["v"]),
                (params["blocks"], jnp.arange(c.n_layers)))
            cache.update(h=hs, conv=convs, k=kall, v=vall)
        elif c.family == "encdec":
            frames = batch["frames"].astype(self.cdt)
            e = frames + params["pos_enc"][None].astype(self.cdt)
            e_pos = jnp.arange(c.enc_seq)

            def enc_body(e, bp):
                e = self._self_attn(bp, e, e_pos, causal=False, rope=False)
                e, _ = self._mlp(bp, e)
                return e, None
            e, _ = jax.lax.scan(enc_body, e, params["enc_blocks"])
            x = x + params["pos_dec"][positions][None].astype(self.cdt)

            def dec_body(x, blk):
                bp, cp = blk
                h = rms_norm(x, bp["ln1"], c.norm_eps)
                q, k, v = self._qkv(bp, h, positions, rope=False)
                o = blocked_attention(q, k, v, causal=True)
                x = x + o.reshape(b, s, -1) @ bp["wo"]
                x = self._cross_attn(cp, x, e)
                xk = (e @ cp["wk"]).reshape(b, -1, c.n_kv_heads, c.hd)
                xv = (e @ cp["wv"]).reshape(b, -1, c.n_kv_heads, c.hd)
                x, _ = self._mlp(bp, x)
                return x, (k.astype(self.cdt), v.astype(self.cdt),
                           xk.astype(self.cdt), xv.astype(self.cdt))
            x, (ks, vs, xks, xvs) = jax.lax.scan(
                dec_body, x, (params["dec_blocks"], params["dec_cross"]))
            cache["k"] = jax.lax.dynamic_update_slice(
                cache["k"], ks, (0, 0, 0, 0, 0))
            cache["v"] = jax.lax.dynamic_update_slice(
                cache["v"], vs, (0, 0, 0, 0, 0))
            cache["xk"], cache["xv"] = xks, xvs
        elif c.family == "vlm":
            img = batch["img_embeds"].astype(self.cdt)

            def unit(x, blk):
                sp, cp = blk

                def self_body(x, bp):
                    h = rms_norm(x, bp["ln1"], c.norm_eps)
                    q, k, v = self._qkv(bp, h, positions)
                    o = blocked_attention(q, k, v, causal=True)
                    x = x + o.reshape(b, s, -1) @ bp["wo"]
                    x, _ = self._mlp(bp, x)
                    return x, (k.astype(self.cdt), v.astype(self.cdt))
                x, (ks, vs) = jax.lax.scan(self_body, x, sp)
                x = self._cross_attn(cp, x, img)
                xk = (img @ cp["wk"]).reshape(b, -1, c.n_kv_heads, c.hd)
                xv = (img @ cp["wv"]).reshape(b, -1, c.n_kv_heads, c.hd)
                x, _ = self._mlp(cp, x)
                return x, (ks, vs, xk.astype(self.cdt), xv.astype(self.cdt))
            x, (ks, vs, xks, xvs) = jax.lax.scan(
                unit, x, (params["blocks"], params["cross_blocks"]))
            cache["k"] = jax.lax.dynamic_update_slice(
                cache["k"], ks, (0, 0, 0, 0, 0, 0))
            cache["v"] = jax.lax.dynamic_update_slice(
                cache["v"], vs, (0, 0, 0, 0, 0, 0))
            cache["xk"], cache["xv"] = xks, xvs
        else:
            raise ValueError(c.family)

        x = rms_norm(x, params["out_norm"], c.norm_eps)
        logits = (x[:, -1] @ self.lm_head(params)).astype(jnp.float32)
        cache["len"] = jnp.asarray(s, jnp.int32)
        return cache, logits

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

    # ------------------------------------------------------------------ layer pattern

    def _kinds(self) -> dict[str, int]:
        """Layers of each kind in ``layer_types``, in order of first use."""
        out: dict[str, int] = {}
        for t in self.cfg.layer_types:
            out[t] = out.get(t, 0) + 1
        return out

    def _runs(self) -> list[tuple[str, int, int]]:
        """``layer_types`` as runs of one kind: (kind, first, stop), the
        indices of the run's layers in that kind's stacked group."""
        runs: list[tuple[str, int, int]] = []
        seen: dict[str, int] = {}
        for t in self.cfg.layer_types:
            i = seen.get(t, 0)
            seen[t] = i + 1
            if runs and runs[-1][0] == t:
                runs[-1] = (t, runs[-1][1], i + 1)
            else:
                runs.append((t, i, i + 1))
        return runs

    def _pattern_params(self, ini: _Init, kind: str, n: int) -> dict:
        """One kind's stacked group: its mixer (Mamba-2, or attention as
        in ``_attn_params``), then ``ln2``, the router over all experts,
        the held experts (``wg``, ``wu``: (Eh, D, F); ``wd``) and the
        shared MLP."""
        c = self.cfg
        d, lead = c.d_model, (n,)
        if kind == "mamba":
            s = c.ssm
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
        else:
            p = self._attn_params(ini, lead)
        p["ln2"] = ini.ones(lead + (d,))
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

    def _pattern_cache(self, batch: int, max_len: int) -> dict:
        """Per kind: ``conv`` (L, B, K-1, C) and ``ssm`` (L, B, H, P, N),
        float32, for the mamba layers; ``k``, ``v`` for the attention
        layers only.  With experts, two int32 counters summed over decode
        steps: ``expert_routed``, the (token, expert) pairs that landed on
        a held expert, and ``expert_rows``, the rows the expert matmuls
        computed (they wrap past 2**31)."""
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
        if c.moe is not None:
            out["expert_routed"] = jnp.zeros((), jnp.int32)
            out["expert_rows"] = jnp.zeros((), jnp.int32)
        return out

    def _state(self, cache: dict) -> dict:
        return {k: cache[k] for names in self._KIND_STATE.values()
                for k in names if k in cache}

    def _pattern_attn(self, bp: dict, h, st: dict, positions, length):
        """Attention mixer: over the whole sequence (``length`` None;
        writes K/V from position 0 when ``st`` holds the cache slices), or
        one position decoded at ``length`` through the grouped
        ``decode_attention``."""
        c = self.cfg
        b, s, _ = h.shape
        q, k, v = self._qkv(bp, h, positions)
        if length is None:
            o = blocked_attention(q, k, v, causal=True,
                                  scale=c.attention_multiplier)
            if st:
                st = {"k": jax.lax.dynamic_update_slice(
                          st["k"], k.astype(st["k"].dtype), (0, 0, 0, 0)),
                      "v": jax.lax.dynamic_update_slice(
                          st["v"], v.astype(st["v"].dtype), (0, 0, 0, 0))}
        else:
            kc = jax.lax.dynamic_update_slice(
                st["k"], k.astype(st["k"].dtype), (0, length, 0, 0))
            vc = jax.lax.dynamic_update_slice(
                st["v"], v.astype(st["v"].dtype), (0, length, 0, 0))
            o = decode_attention(q, kc, vc, length + 1,
                                 scale=c.attention_multiplier)
            st = {"k": kc, "v": vc}
        return o.reshape(b, s, -1) @ bp["wo"], st

    def _pattern_mamba(self, bp: dict, h, st: dict):
        """Mamba-2 mixer, carrying the layer's conv and ssm state where
        ``st`` holds them (prefill and decode)."""
        s = self.cfg.ssm
        y, conv, ssm = mamba2_mixer(
            bp, h, headdim=s.headdim, n_groups=s.n_groups,
            state_dim=s.state_dim, chunk=s.chunk, eps=self.cfg.norm_eps,
            conv0=st.get("conv"), ssm0=st.get("ssm"))
        return y, ({"conv": conv, "ssm": ssm} if st else st)

    def _pattern_layer(self, kind: str, bp: dict, x, st: dict, positions,
                       length):
        """x + r * mixer(norm(x)), then x + r * (experts + shared MLP)
        of norm(x), r the residual multiplier.  Returns (x, the layer's
        cache slices, (token, expert) pairs routed to held experts)."""
        c = self.cfg
        h = rms_norm(x, bp["ln1"], c.norm_eps)
        if kind == "mamba":
            y, st = self._pattern_mamba(bp, h, st)
        else:
            y, st = self._pattern_attn(bp, h, st, positions, length)
        x = x + c.residual_multiplier * y
        h = rms_norm(x, bp["ln2"], c.norm_eps)
        b, s, d = h.shape
        y = jnp.zeros_like(h)
        routed = jnp.zeros((), jnp.int32)
        if c.moe is not None:
            e, routed = held_expert_mlp(
                h.reshape(b * s, d), bp["router"], bp["wg"], bp["wu"],
                bp["wd"], c.moe.top_k, c.moe.first_held)
            y = e.reshape(b, s, d)
        if c.shared_d_ff:
            y = y + swiglu(h, bp["shared_wg"], bp["shared_wu"], bp["shared_wd"])
        return x + c.residual_multiplier * y, st, routed

    def _walk(self, params: dict, x, state: dict, positions, length,
              remat: bool = False):
        """The layer pattern over x, one ``lax.scan`` per run of layers of
        one kind.  Each step takes its layer's weights, and its slices of
        the cache leaves in ``state``, at the layer's index in its kind's
        stacked group, and writes the slices back there.  Returns (x,
        state, (token, expert) pairs routed to held experts)."""
        routed = jnp.zeros((), jnp.int32)
        for kind, first, stop in self._runs():
            group = params[kind]
            names = [n for n in self._KIND_STATE[kind] if n in state]

            def body(carry, i, kind=kind, group=group, names=names):
                x, st, routed = carry
                bp = jax.tree.map(
                    lambda t: jax.lax.dynamic_index_in_dim(t, i, 0, False), group)
                one = {n: jax.lax.dynamic_index_in_dim(st[n], i, 0, False)
                       for n in names}
                x, one, r = self._pattern_layer(kind, bp, x, one, positions,
                                                length)
                st = {n: jax.lax.dynamic_update_index_in_dim(
                          st[n], one[n].astype(st[n].dtype), i, 0)
                      for n in names}
                return (x, st, routed + r), None

            if remat:
                body = jax.checkpoint(body)
            (x, st, routed), _ = jax.lax.scan(
                body, (x, {n: state[n] for n in names}, routed),
                jnp.arange(first, stop))
            state = {**state, **st}
        return x, state, routed

    def _final_hidden(self, params: dict, x):
        """The final norm, and the logits' divisor folded into the hidden
        states (the head is linear), so that ``x @ lm_head`` are logits."""
        c = self.cfg
        return rms_norm(x, params["out_norm"], c.norm_eps) / c.logits_scaling

    def _pattern_decode(self, params: dict, cache: dict, x):
        c = self.cfg
        length = cache["len"]
        x, state, routed = self._walk(
            params, x * c.embedding_multiplier, self._state(cache),
            jnp.full((1,), length, jnp.int32), length)
        cache = {**cache, **state, "len": length + 1}
        if c.moe is not None:
            cache["expert_routed"] = cache["expert_routed"] + routed
            cache["expert_rows"] = (cache["expert_rows"]
                                    + x.shape[0] * c.moe.held * c.n_layers)
        x = self._final_hidden(params, x)
        return cache, (x[:, 0] @ self.lm_head(params)).astype(jnp.float32)
