"""Weights made from the seed, for the program and for the reference.

:func:`serve_params` fills a parameter tree on the device in one jitted
call, in the dtype it is served in; it is a pure function of
``(tree, seed)``.  The tree comes from the configuration's reference
(``param_tree``): nested groups of :class:`Leaf`, each with its shape
and how it is drawn, N(shift, scale).  Leaves of the groups that carry a
leading per-layer axis (the reference's ``stacked_groups``) are drawn
one layer at a time (``lax.map``), so no full-size float32 temporary of
a leaf is ever live.  The benchmark hands these to the program, and the
reference makes them again from the same seed once the program is gone.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
           "float16": jnp.float16}


class Leaf(NamedTuple):
    """One leaf of a parameter tree: its shape, drawn N(shift, scale)."""
    shape: tuple
    scale: float = 0.02
    shift: float = 0.0


def dtype_of(name: str):
    return _DTYPES[name]


def _flat(tree: dict, prefix: str = ""):
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            yield from _flat(v, f"{prefix}{k}/")
        else:
            yield f"{prefix}{k}", v


def _unflat(items: dict) -> dict:
    out: dict = {}
    for path, v in items.items():
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return out


def shapes_of(tree: dict) -> dict:
    """The tree with each leaf replaced by its shape."""
    return _unflat({p: tuple(leaf.shape) for p, leaf in _flat(tree)})


def _layers(path: str, shape: tuple, stacked: dict) -> int:
    """The per-layer count of the leaf's group, 0 where it has none."""
    for group, n in stacked.items():
        if path.startswith(group + "/"):
            if not shape or shape[0] != n:
                raise ValueError(f"{path} {shape} has no leading axis of "
                                 f"{group}'s {n} layers")
            return n
    return 0


def serve_params(tree: dict, seed: int, dtype, stacked: dict) -> dict:
    """The served weights, made on the default device in one jitted call.
    ``stacked`` maps each group whose leaves carry a leading per-layer
    axis to its count of layers."""
    flat = dict(_flat(tree))

    def draw(key, path, leaf):
        def one(k, shp):
            x = jax.random.normal(k, shp, jnp.float32) * leaf.scale + leaf.shift
            return x.astype(dtype)

        n = _layers(path, tuple(leaf.shape), stacked)
        if n:
            keys = jax.random.split(key, n)
            return jax.lax.map(lambda k: one(k, tuple(leaf.shape[1:])), keys)
        return one(key, tuple(leaf.shape))

    def make(seed_key):
        out = {}
        for i, (path, leaf) in enumerate(flat.items()):
            out[path] = draw(jax.random.fold_in(seed_key, i), path, leaf)
        return _unflat(out)

    return jax.jit(make)(jax.random.PRNGKey(seed))
