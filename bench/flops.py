"""Operations and bytes of the benchmark's programs, from shapes alone.

These count what the model's mathematics needs, whatever implements it:
a change of kernel, fusion or recomputation leaves them unchanged.  One
multiply-add is 2 operations.  Element-wise work (norms, rotary, softmax,
biases) is left out: it is under 1% of the matmul work at these widths.

All functions take the configuration's ``model`` dict (the sizes in
``bench/configs/<config>.json``).
"""

from __future__ import annotations


def _hd(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


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


def least_time_s(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The larger of operations over peak and bytes over bandwidth, and
    which of the two bounds it."""
    t_c = flops / peak["flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
