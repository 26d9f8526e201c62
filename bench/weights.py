"""Weights made from the seed, for the program and for the reference.

:func:`serve_params` fills a parameter tree of the given shapes on the
device in one jitted call, in the dtype it is served in; it is a pure
function of ``(shapes, seed)``.  Stacked ``(layers, ...)`` leaves are
drawn one layer at a time (``lax.map``), so no full-size float32
temporary of a leaf is ever live.  Matrices get N(0, 0.02) (output
projections 0.02/sqrt(2L)), biases N(0, 0.02) and norm gains
1 + N(0, 0.05): every term of the equations is exercised.  The
benchmark hands these to the serving engine, and the reference makes
them again from the same seed once the program is gone.

Leaves are named by the program's tree (``emb``, ``out_norm``,
``lm_head``, ``blocks/{ln1,wq,wk,wv,wo,bq,bk,bv,ln2,wg,wu,wd}``); the
reference reads them by those names.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
           "float16": jnp.float16}


def dtype_of(name: str):
    return _DTYPES[name]


def tree_shapes(model: dict, padded_vocab: int) -> dict:
    """Shapes of the dense decoder's parameter tree, from the config's
    sizes (the names and layout the program uses)."""
    d, L = model["d_model"], model["n_layers"]
    hd = model.get("head_dim") or d // model["n_heads"]
    hq, hkv, ff = model["n_heads"] * hd, model["n_kv_heads"] * hd, model["d_ff"]
    shapes = {"emb": (padded_vocab, d), "out_norm": (d,)}
    if not model["tie_embeddings"]:
        shapes["lm_head"] = (d, padded_vocab)
    blocks = {"ln1": (L, d), "wq": (L, d, hq), "wk": (L, d, hkv),
              "wv": (L, d, hkv), "wo": (L, hq, d)}
    if model["qkv_bias"]:
        blocks.update(bq=(L, hq), bk=(L, hkv), bv=(L, hkv))
    blocks.update(ln2=(L, d), wg=(L, d, ff), wu=(L, d, ff), wd=(L, ff, d))
    shapes["blocks"] = blocks
    return shapes


def _flat(shapes: dict, prefix: str = ""):
    for k in sorted(shapes):
        v = shapes[k]
        if isinstance(v, dict):
            yield from _flat(v, f"{prefix}{k}/")
        else:
            yield f"{prefix}{k}", tuple(v)


def _unflat(items: dict) -> dict:
    out: dict = {}
    for path, v in items.items():
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return out


def _leaf_kind(path: str) -> str:
    name = path.rsplit("/", 1)[-1]
    if name in ("ln1", "ln2", "out_norm"):
        return "gain"
    if name in ("bq", "bk", "bv"):
        return "bias"
    return "matrix"


def serve_params(shapes: dict, seed: int, dtype, n_layers: int) -> dict:
    """The served weights, made on the default device in one jitted call."""
    flat = dict(_flat(shapes))
    wo_scale = 0.02 / math.sqrt(2 * n_layers)

    def draw(key, path, shape):
        kind = _leaf_kind(path)
        scale = wo_scale if path.endswith("/wo") else (
            0.05 if kind == "gain" else 0.02)
        shift = 1.0 if kind == "gain" else 0.0

        def one(k, shp):
            x = jax.random.normal(k, shp, jnp.float32) * scale + shift
            return x.astype(dtype)

        if path.startswith("blocks/"):
            keys = jax.random.split(key, shape[0])
            return jax.lax.map(lambda k: one(k, shape[1:]), keys)
        return one(key, shape)

    def make(seed_key):
        out = {}
        for i, (path, shape) in enumerate(flat.items()):
            out[path] = draw(jax.random.fold_in(seed_key, i), path, shape)
        return _unflat(out)

    return jax.jit(make)(jax.random.PRNGKey(seed))
